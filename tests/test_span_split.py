"""scripts/span_split.py: the reduction of a traced run by the engine's
spans, on a synthetic trace and window."""
import importlib.util
import pathlib
from types import SimpleNamespace as NS

import numpy as np
import pytest

from repro.serve.engine import Request

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def split():
    spec = importlib.util.spec_from_file_location(
        "span_split", ROOT / "scripts" / "span_split.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _summary(busy, t0=0.0, t1=1.0):
    from bench.trace import Summary
    return Summary(host_t0=t0, host_t1=t1, busy=busy, ops=[], modules=[],
                   host=[])


def _ev(name, start_ns, dur_ns, **stats):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns,
              stats=list(stats.items()))


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.mark", 1000, 10),
        _ev("bench.step", 2000, 500),
        _ev("engine.step", 2010, 480, step_num=4),
        _ev("engine.sync", 2100, 50),
        _ev("engine.admit", 3000, 20, rid=1),
        _ev("jit_compile", 3000, 5)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_decode_step(123)", 0, 1),
                                       _ev("jit_prefill_chunk(9)", 2, 1),
                                       _ev("jit_decode_step(123)", 4, 1)]),
        NS(name="XLA Ops", events=[_ev("%fusion.1 = f32[]", 0, 1)])])
    return [host, dev]


def test_host_spans_on_the_harness_clock(split):
    spans = split.host_spans(_planes(), mark_host=10.0)
    assert [n for n, _, _, _ in spans] == [
        "bench.mark", "bench.step", "engine.step", "engine.sync",
        "engine.admit"]
    name, start, dur, args = spans[2]
    assert start == pytest.approx(10.0 + 1010e-9)
    assert dur == pytest.approx(480e-9)
    assert args == {"step_num": 4}
    assert split.module_names(_planes()) == ["jit_decode_step",
                                             "jit_prefill_chunk"]
    with pytest.raises(ValueError):
        split.host_spans(_planes()[1:], mark_host=0.0)


def test_idle_split_inside_and_outside_the_sync(split):
    # two steps; the device runs 0.10-0.12 and 0.16-0.18 in the first and
    # 0.55-0.60 in the second; each step syncs for 0.04 s
    spans = [("engine.step", 0.10, 0.10, {}),
             ("engine.sync", 0.14, 0.04, {}),
             ("engine.step", 0.50, 0.20, {}),
             ("engine.sync", 0.60, 0.04, {}),
             ("engine.step", 0.95, 0.10, {})]      # ends past the window
    s = _summary([[0.10, 0.12], [0.16, 0.18], [0.55, 0.60]])
    out = split.idle_split(s, spans)
    assert out["engine_steps"] == 2
    # sync idle: 0.02 (0.14-0.16) and 0.04; launch idle: 0.04 and 0.11
    assert out["sync_idle_ms"] == pytest.approx(30.0)
    assert out["launch_idle_ms"] == pytest.approx(75.0)
    assert split.idle_split(_summary([]), []) == {"engine_steps": 0}


def test_idle_gaps_take_the_innermost_span(split):
    spans = [("bench.mark", 0.0, 0.001, {}),
             ("bench.step", 0.1, 0.3, {}),
             ("engine.step", 0.1, 0.3, {}),
             ("engine.sync", 0.2, 0.1, {}),
             ("bench.admit", 0.6, 0.1, {})]
    s = _summary([[0.1, 0.2], [0.4, 0.6], [0.7, 0.9]])
    gaps = split.idle_gaps(s, spans)
    assert gaps[0][1] == "engine.sync"
    assert sorted((round(g, 9), n) for g, n in gaps) == [
        (0.1, "bench.admit"), (0.1, "none"), (0.1, "none"),
        (0.2, "engine.sync")]


def test_unnested_counts_engine_spans_outside_the_harness(split):
    spans = [("bench.step", 0.1, 0.3, {}),
             ("engine.step", 0.1, 0.3, {}),
             ("bench.admit", 0.5, 0.1, {}),
             ("engine.admit", 0.52, 0.01, {}),
             ("engine.admit", 0.7, 0.01, {}),       # outside both
             ("engine.step", 1.5, 0.1, {})]         # outside the window
    assert split.unnested(spans, 0.0, 1.0) == 1


def test_waits_from_the_program_stamps(split):
    def req(rid, admit, first):
        r = Request(rid=rid, tokens=[1], max_new=1)
        r.t_admit, r.t_first = admit, first
        return r

    recs = [NS(rid=0, due=0.5), NS(rid=1, due=1.0), NS(rid=2, due=2.0),
            NS(rid=3, due=9.5), NS(rid=4, due=11.0)]
    reqs = {0: req(0, 0.6, 0.9),        # before the window: left out
            1: req(1, 1.5, 3.0),        # queue 0.5, prefill 1.5
            2: req(2, 2.0, 11.0),       # queue 0, no first token by t1
            3: req(3, None, None),      # not admitted by t1
            4: req(4, 11.0, 12.0)}      # after the window
    data = NS(recs=recs, t0=1.0, t1=10.0)
    out = split.waits(data, reqs)
    assert out["window_requests"] == 3
    assert out["queue_wait_p90_ms"] == pytest.approx(
        np.percentile([0.5, 0.0, 0.5], 90) * 1e3)
    assert out["prefill_wait_p90_ms"] == pytest.approx(
        np.percentile([1.5, 8.0], 90) * 1e3)
    assert split.waits(NS(recs=[], t0=0, t1=1), {}) == {
        "queue_wait_p90_ms": None, "prefill_wait_p90_ms": None,
        "window_requests": 0}


def test_attach_points_every_layer_at_the_observer(split):
    from repro.obs.runtime import Observer
    eng = NS(sched=NS(), alloc=NS(), drafter=None, compilations={"x": 1})
    obs = Observer()
    split.attach(eng, obs)
    assert eng.obs is eng.sched.obs is eng.alloc.obs is obs
    assert obs.census() == {"x": 1}
