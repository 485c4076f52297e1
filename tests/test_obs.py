"""Observability subsystem: metrics registry, spans, and the Observer
seam through the serving engine.

Five layers of gate:

  * **Instruments** — the log-bucket histogram's quantiles against a
    numpy reference (within the documented ~12% bucket resolution),
    counter/gauge/label plumbing, and a golden Prometheus text
    exposition checked byte-for-byte plus through the format validator
    (which itself is tested against deliberately malformed dumps).
  * **Spans** — the engine's ``engine.*`` spans through an injected
    factory: names, nesting and arguments, and on a real
    ``jax.profiler`` trace of a CPU run (none without a factory).
  * **Observer-through-engine** — observer-on output token-identical to
    observer-off (observability must never change scheduling or
    sampling), metric coverage on real runs: prefix-cache warm hits,
    speculation counters agreeing with the engine's own ledger, census
    export, TTFT and its two parts matching retirements.
  * **Named executables** — the engine's jitted steps lower to
    ``jit_<function>`` modules, as the profiler names their launches.
  * **Clock unification** — the serving stack has exactly ONE
    ``time.*`` call site (``repro.obs.trace.now``), and every
    ``Request.t_*`` mark falls inside a ``now()``-bracketed run.
"""
import functools
import glob
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.retrace_guard import census, retrace_guard
from repro.configs.base import get_config, shrink
from repro.core.famous import FamousConfig
from repro.launch.serve import profiler_span
from repro.models import module, transformer
from repro.obs.metrics import (LOG_BUCKETS, Histogram, MetricsRegistry,
                               log_buckets, validate_prometheus_text)
from repro.obs.runtime import (NULL_OBSERVER, NULL_SPAN, NullObserver,
                               Observer)
from repro.obs.trace import now
from repro.serve.engine import Request, ServingEngine

MAX_SEQ = 32
CHUNK = 8


@functools.lru_cache(maxsize=None)
def _cfg_params():
    cfg = shrink(get_config("qwen2-7b"))
    params = module.init_params(transformer.model_spec(cfg),
                                jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _requests(cfg, n=6, seed=0, max_new=4, shared_head=0, rid0=0):
    rng = np.random.default_rng(seed)
    head = list(rng.integers(0, cfg.vocab_size, size=shared_head))
    return [Request(rid=rid0 + i,
                    tokens=head + list(rng.integers(
                        0, cfg.vocab_size,
                        size=int(rng.integers(2, 10)))),
                    max_new=max_new)
            for i in range(n)]


def _engine(observer=None, **kw):
    cfg, params = _cfg_params()
    return ServingEngine(params, cfg, FamousConfig(impl="xla"),
                         n_slots=2, max_seq=MAX_SEQ, chunk=CHUNK,
                         observer=observer, **kw)


# ---------------------------------------------------------------------------
# histogram vs numpy reference
# ---------------------------------------------------------------------------


def test_log_buckets_schema():
    b = log_buckets(1e-2, 1e2, per_decade=10)
    assert len(b) == 41
    assert b[0] == pytest.approx(1e-2) and b[-1] == pytest.approx(1e2)
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(10 ** 0.1) for r in ratios)
    # the default schema really is 20/decade over ten decades
    assert len(LOG_BUCKETS) == 201
    assert LOG_BUCKETS[0] == pytest.approx(1e-5)
    assert LOG_BUCKETS[-1] == pytest.approx(1e5)


def test_histogram_quantiles_match_numpy_within_bucket_resolution():
    rng = np.random.default_rng(3)
    for scale in (1e-3, 1.0, 50.0):
        values = rng.lognormal(mean=math.log(scale), sigma=1.0, size=2000)
        h = Histogram.of(values)
        assert h.count() == 2000
        assert h.sum() == pytest.approx(values.sum())
        for q in (5, 25, 50, 75, 95, 99):
            ref = float(np.percentile(values, q))
            got = h.percentile(q)
            # one log bucket is a 10^(1/20) ~ 12.2% span; interpolation
            # keeps the estimate inside the containing bucket
            assert ref / 1.13 <= got <= ref * 1.13, (scale, q, ref, got)


def test_histogram_edge_cases():
    h = Histogram("h", "h")
    assert math.isnan(h.quantile(0.5))
    h.observe(1e9)              # beyond the last bound -> +Inf bucket
    assert h.count() == 1
    assert h.quantile(0.5) == pytest.approx(LOG_BUCKETS[-1])  # clamped
    h2 = Histogram("h2", "h2", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 3.0):
        h2.observe(v)
    assert h2.count() == 4 and h2.sum() == pytest.approx(8.0)
    assert 0.0 < h2.quantile(0.1) <= 1.0
    assert 2.0 < h2.quantile(0.9) <= 4.0
    # labelled cells are independent
    h3 = Histogram("h3", "h3", ("phase",), buckets=(1.0,))
    h3.observe(0.5, phase="a")
    assert h3.count(phase="a") == 1 and h3.count(phase="b") == 0


# ---------------------------------------------------------------------------
# Prometheus exposition: golden render + validator
# ---------------------------------------------------------------------------


def _golden_registry():
    reg = MetricsRegistry()
    reg.counter("t_reqs_total", "requests served", ("status",)) \
        .inc(3, status='o"k')
    reg.gauge("t_depth", "queue depth").set(1.5)
    h = reg.histogram("t_lat_seconds", "latency", buckets=(1.0, 2.0))
    h.observe(0.5)
    h.observe(3.0)
    return reg


GOLDEN = """\
# HELP t_depth queue depth
# TYPE t_depth gauge
t_depth 1.5
# HELP t_lat_seconds latency
# TYPE t_lat_seconds histogram
t_lat_seconds_bucket{le="1"} 1
t_lat_seconds_bucket{le="2"} 1
t_lat_seconds_bucket{le="+Inf"} 2
t_lat_seconds_sum 3.5
t_lat_seconds_count 2
# HELP t_reqs_total requests served
# TYPE t_reqs_total counter
t_reqs_total{status="o\\"k"} 3
"""


def test_prometheus_exposition_golden():
    assert _golden_registry().prometheus_text() == GOLDEN


def test_validator_accepts_and_counts_samples():
    assert validate_prometheus_text(GOLDEN) == 7
    # a full default-schema registry validates too
    reg = MetricsRegistry()
    h = reg.histogram("big_seconds", "h", ("phase",))
    for i in range(50):
        h.observe(10.0 ** (i % 7 - 3), phase="decode")
    assert validate_prometheus_text(reg.prometheus_text()) \
        == len(LOG_BUCKETS) + 3


@pytest.mark.parametrize("bad", [
    "no_type_line 1\n",
    "# TYPE x wat\nx 1\n",
    "# TYPE x counter\nx{unclosed 1\n",
    "# TYPE x counter\nx notafloat\n",
    # non-cumulative buckets
    "# TYPE h histogram\n"
    'h_bucket{le="1"} 5\nh_bucket{le="2"} 3\nh_bucket{le="+Inf"} 5\n'
    "h_count 5\n",
    # missing +Inf bucket
    '# TYPE h histogram\nh_bucket{le="1"} 1\nh_count 1\n',
    # _count disagrees with the +Inf bucket
    "# TYPE h histogram\n"
    'h_bucket{le="+Inf"} 2\nh_sum 1\nh_count 3\n',
    # bucket without an le label
    "# TYPE h histogram\nh_bucket 2\nh_count 2\n",
])
def test_validator_rejects_malformed(bad):
    with pytest.raises(ValueError):
        validate_prometheus_text(bad)


def test_label_escaping_survives_the_validator():
    reg = MetricsRegistry()
    reg.counter("esc_total", "c", ("k",)).inc(1, k='a\\b"c\nd')
    text = reg.prometheus_text()
    assert validate_prometheus_text(text) == 1
    assert '\\\\' in text and '\\"' in text and "\\n" in text


def test_registry_idempotent_and_schema_checked():
    reg = MetricsRegistry()
    c1 = reg.counter("c_total", "help", ("a",))
    assert reg.counter("c_total", "help", ("a",)) is c1
    with pytest.raises(AssertionError):
        reg.counter("c_total", "help", ("b",))   # different labels
    with pytest.raises(AssertionError):
        reg.gauge("c_total", "help", ("a",))     # different kind
    c1.inc(2, a="x")
    assert reg.snapshot() == {'c_total{a="x"}': 2.0}


# ---------------------------------------------------------------------------
# observer through the engine
# ---------------------------------------------------------------------------


class SpanLog:
    """A span factory that keeps each span's name, arguments (those set
    at its end too), parent and order, as a profiler would."""

    def __init__(self):
        self.spans = []
        self._open = []

    def __call__(self, name, **args):
        return _Span(self, name, args)


class _Span:
    def __init__(self, log, name, args):
        self.log, self.rec = log, {"name": name, "args": dict(args)}

    def __enter__(self):
        log = self.log
        self.rec["parent"] = log.spans[log._open[-1]] if log._open else None
        log._open.append(len(log.spans))
        log.spans.append(self.rec)
        return self

    def set_metadata(self, **args):
        self.rec["args"].update(args)

    def __exit__(self, *exc):
        self.log._open.pop()
        self.rec["closed"] = True


@functools.lru_cache(maxsize=None)
def _paired_runs():
    """One observer-off and one observer-on (metrics and spans) engine
    over the same workload; cached so every assertion below shares the
    two runs."""
    cfg, _ = _cfg_params()
    spans = SpanLog()
    obs = Observer(span=spans)
    off = _engine(observer=None, cache_kind="paged", page_size=8)
    on = _engine(observer=obs, cache_kind="paged", page_size=8)
    done_off = sorted(off.run(_requests(cfg)), key=lambda r: r.rid)
    done_on = sorted(on.run(_requests(cfg)), key=lambda r: r.rid)
    return done_off, done_on, obs, on, spans


def test_observer_on_is_token_identical_to_off():
    done_off, done_on, _, _, _ = _paired_runs()
    assert [r.out for r in done_on] == [r.out for r in done_off]
    assert [r.error for r in done_on] == [r.error for r in done_off]


def test_observer_metric_coverage():
    _, done_on, obs, eng, _ = _paired_runs()
    m = obs.metrics
    tok = sum(len(r.out) for r in done_on)
    assert m.get("repro_tokens_generated_total").value() == tok
    assert m.get("repro_requests_enqueued_total").value() == len(done_on)
    assert m.get("repro_requests_admitted_total").value() >= len(done_on)
    assert m.get("repro_requests_retired_total").value(status="ok") \
        == len(done_on)
    assert m.get("repro_engine_steps_total").value() > 0
    # every retirement with a first token contributes one TTFT sample
    assert m.get("repro_request_ttft_seconds").count() == len(done_on)
    assert m.get("repro_step_phase_seconds").count(phase="decode") > 0
    assert m.get("repro_step_phase_seconds").count(phase="prefill_chunk") > 0
    assert m.get("repro_decode_launches_total").value() > 0
    # no preemption here: one admission per request, its whole prompt
    assert m.get("repro_admitted_prompt_tokens_total").value() \
        == sum(len(r.tokens) for r in done_on)
    # paged engine: pages were grown and freed back
    assert m.get("repro_pages_total").value(op="grow") > 0
    assert m.get("repro_pages_total").value(op="free") > 0
    # the whole dump passes the format checker
    assert validate_prometheus_text(obs.prometheus_text()) > 100


def test_observer_spans_name_the_engine_work():
    """Every step is one ``engine.step`` (numbered), holding its prefill
    chunks, its decode (which holds the sync) and the retire after it;
    admissions run between steps; each span carries its arguments."""
    _, done_on, obs, _, log = _paired_runs()
    m = obs.metrics
    by = {}
    for sp in log.spans:
        assert sp["closed"], sp["name"]
        by.setdefault(sp["name"], []).append(sp)
    assert set(by) == {"engine.step", "engine.admit", "engine.prefill_chunk",
                       "engine.decode", "engine.sync", "engine.retire"}
    steps = by["engine.step"]
    assert [s["args"] for s in steps] == [{"step_num": k}
                                          for k in range(len(steps))]
    assert len(steps) == m.get("repro_engine_steps_total").value()
    assert all(s["parent"] is None for s in steps + by["engine.admit"])
    for name in ("engine.prefill_chunk", "engine.decode", "engine.retire"):
        assert all(s["parent"]["name"] == "engine.step" for s in by[name])
    assert all(s["parent"]["name"] == "engine.decode"
               for s in by["engine.sync"])
    assert len(by["engine.sync"]) == len(by["engine.decode"]) \
        == m.get("repro_decode_launches_total").value()
    admits = [s["args"] for s in by["engine.admit"]]
    assert sorted(a["rid"] for a in admits) == [r.rid for r in done_on]
    assert all(set(a) == {"rid", "slot", "n_tokens", "cached"}
               and a["slot"] in (0, 1) for a in admits)
    chunks = [s["args"] for s in by["engine.prefill_chunk"]]
    assert all(set(a) == {"slot", "rid", "start", "n"} for a in chunks)
    assert sum(a["n"] for a in chunks) \
        == m.get("repro_prefill_tokens_total").value()
    decodes = [s["args"] for s in by["engine.decode"]]
    assert sum(a["slots"] for a in decodes) \
        == m.get("repro_tokens_generated_total").value()
    assert all(a["ctx_tokens"] >= a["slots"] >= 1 for a in decodes)
    assert sum(s["args"]["n_finished"] for s in by["engine.retire"]) \
        == len(done_on)


def _profiled(observer, tmp_path):
    """Serve a few requests under a ``jax.profiler`` trace; the host
    plane's ``engine.*`` events as (name, start, end, stats)."""
    from jax.profiler import ProfileData
    cfg, _ = _cfg_params()
    eng = _engine(observer=observer, cache_kind="paged", page_size=8)
    eng.run(_requests(cfg, n=2, seed=3))           # compile outside it
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        done = eng.run(_requests(cfg, n=4, seed=4, rid0=10))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
            dict(ev.stats))
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("engine.")]
    return evs, done


def test_profiled_run_holds_the_engine_spans(tmp_path):
    evs, done = _profiled(Observer(span=profiler_span), tmp_path)
    steps = sorted((s, e, st) for n, s, e, st in evs if n == "engine.step")
    assert [st["step_num"] for _, _, st in steps] == \
        list(range(steps[0][2]["step_num"],
                   steps[0][2]["step_num"] + len(steps)))

    def parent(s, e, names):
        return [n for n, s0, e0, _ in evs
                if n in names and s0 <= s and e <= e0 and (s0, e0) != (s, e)]

    seen = set()
    for n, s, e, st in evs:
        seen.add(n)
        if n in ("engine.prefill_chunk", "engine.decode", "engine.retire"):
            assert parent(s, e, ("engine.step",)), n
        elif n == "engine.sync":
            assert parent(s, e, ("engine.decode",))
        elif n == "engine.admit":
            assert not parent(s, e, ("engine.step",))
            assert set(st) >= {"rid", "slot", "n_tokens", "cached"}
        if n == "engine.prefill_chunk":
            assert set(st) >= {"slot", "rid", "start", "n"}
        if n == "engine.decode":
            assert st["slots"] >= 1 and st["ctx_tokens"] >= st["slots"]
        if n == "engine.retire":
            assert "n_finished" in st
    assert seen == {"engine.step", "engine.admit", "engine.prefill_chunk",
                    "engine.decode", "engine.sync", "engine.retire"}
    admitted = {st["rid"] for n, _, _, st in evs if n == "engine.admit"}
    assert admitted == {r.rid for r in done}


@pytest.mark.parametrize("observer", [None, Observer()],
                         ids=["no_observer", "metrics_only"])
def test_profiled_run_without_a_span_factory_holds_no_span(observer,
                                                           tmp_path):
    evs, done = _profiled(observer, tmp_path)
    assert done and evs == []


def test_request_marks_split_ttft():
    """Queue wait (submit -> first admission) and admission -> first
    token add up to TTFT, per request and in the histograms' sums."""
    cfg, _ = _cfg_params()
    obs = Observer()
    eng = _engine(observer=obs)
    done = eng.run(_requests(cfg, n=6, seed=31))   # 6 requests, 2 slots
    for r in done:
        assert r.t_submit <= r.t_admit <= r.t_first, r.rid
        assert (r.t_admit - r.t_submit) + (r.t_first - r.t_admit) \
            == pytest.approx(r.t_first - r.t_submit, abs=1e-12)
    assert max(r.t_admit - r.t_submit for r in done) > 0   # some queued
    m = obs.metrics
    ttft = m.get("repro_request_ttft_seconds")
    wait = m.get("repro_request_queue_wait_seconds")
    a2f = m.get("repro_request_admit_to_first_seconds")
    assert ttft.count() == wait.count() == a2f.count() == len(done)
    assert wait.sum() + a2f.sum() == pytest.approx(ttft.sum(), rel=1e-9)


def test_preempted_request_keeps_its_first_admission():
    """A sequence preempted mid-generation is admitted again: ``t_admit``
    keeps the first admission, the ledger's ``admit_step`` moves to the
    latest, and every admission's tokens are counted."""
    cfg, _ = _cfg_params()
    obs = Observer()
    # 5 allocatable pages of 4: both 7-token prompts admit, decode growth
    # then forces the younger out
    eng = _engine(observer=obs, cache_kind="paged", page_size=4, n_pages=6)
    marks, sizes = {}, []
    add = eng.add_request

    def spy(req):
        sizes.append(len(req.tokens) + len(req.out))
        slot = add(req)
        marks.setdefault(req.rid, []).append((req.t_admit,
                                               eng.sched.step_count))
        return slot

    eng.add_request = spy
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, tokens=list(rng.integers(0, cfg.vocab_size, 7)),
                    max_new=8) for i in range(2)]
    done = eng.run(reqs)
    assert obs.metrics.get("repro_preemptions_total").value() >= 1
    again = [rid for rid, m in marks.items() if len(m) > 1]
    assert again
    for rid in again:
        (t_first_admit, step0), *later = marks[rid]
        assert all(t == t_first_admit for t, _ in later)
        f = eng.sched.fairness(rid)
        assert f["admit_step"] == later[-1][1] > step0
    for r in done:
        assert r.t_submit <= r.t_admit <= r.t_first
    assert obs.metrics.get("repro_admitted_prompt_tokens_total").value() \
        == sum(sizes)


def test_decode_span_holds_the_launched_slots_under_preemption():
    """Page growth runs before the decode span opens: a slot preempted
    by it is not counted in ``slots``, and there is one span (and one
    phase sample) per launch."""
    cfg, _ = _cfg_params()
    log = SpanLog()
    obs = Observer(span=log)
    eng = _engine(observer=obs, cache_kind="paged", page_size=4, n_pages=6)
    rng = np.random.default_rng(5)
    eng.run([Request(rid=i, tokens=list(rng.integers(0, cfg.vocab_size, 7)),
                     max_new=8) for i in range(2)])
    m = obs.metrics
    assert m.get("repro_preemptions_total").value() >= 1
    decodes = [s["args"] for s in log.spans if s["name"] == "engine.decode"]
    assert len(decodes) == m.get("repro_decode_launches_total").value() \
        == m.get("repro_step_phase_seconds").count(phase="decode")
    assert sum(a["slots"] for a in decodes) \
        == m.get("repro_tokens_generated_total").value()
    assert all(a["ctx_tokens"] >= a["slots"] >= 1 for a in decodes)


@pytest.mark.parametrize("metrics", [False, True],
                         ids=["no_observer", "metrics_only"])
def test_costly_span_args_wait_for_a_span_factory(metrics):
    """Without a span factory the engine computes no span argument that
    costs work (``ctx_tokens``), on the plain and the speculative path."""
    cfg, _ = _cfg_params()
    for kw in ({}, {"speculative": True, "draft_k": 2}):
        eng = _engine(observer=Observer() if metrics else None,
                      cache_kind="paged", page_size=8, **kw)
        assert eng.speculative_active == bool(kw)

        def refuse(active):
            raise AssertionError("ctx_tokens computed with spans off")

        eng._ctx_tokens = refuse
        assert eng.run(_requests(cfg, n=3, seed=6))


@functools.lru_cache(maxsize=None)
def _lowered_modules():
    """The module name each engine executable lowers to, at the
    arguments of its first call."""
    cfg, _ = _cfg_params()
    names = {}

    def spy(eng, attr):
        fn = getattr(eng, attr)

        def call(*a, **k):
            names.setdefault(attr, fn.lower(*a, **k).as_text().split()[1])
            return fn(*a, **k)

        setattr(eng, attr, call)

    plain = _engine(cache_kind="paged", page_size=8)
    spec = _engine(speculative=True, draft_k=2)
    for attr in ("_prefill_chunk_exec", "_decode", "_clear", "_sample"):
        spy(plain, attr)
    spy(spec, "_verify")
    reqs = _requests(cfg, n=2, seed=41)
    reqs[0].temperature = 1.0                   # the sampler
    reqs.append(Request(rid=9, tokens=[5], max_new=2))   # nothing to prefill
    plain.run(reqs)
    spec.run(_requests(cfg, n=2, seed=42))
    return names


@pytest.mark.parametrize("attr,module_name", [
    ("_prefill_chunk_exec", "@jit_prefill_chunk"),
    ("_decode", "@jit_decode_step"),
    ("_verify", "@jit_verify_step"),
    ("_clear", "@jit_clear_slot"),
    ("_sample", "@jit_sample_tokens"),
])
def test_engine_executables_lower_under_their_names(attr, module_name):
    assert _lowered_modules()[attr] == module_name


def test_observer_census_and_retrace_guard_sources():
    _, _, obs, eng, _ = _paired_runs()
    assert obs.census() == {k: int(v) for k, v in eng.compilations.items()}
    # retrace_guard reads the census through the Observer...
    assert census(obs) == census(eng)
    # ...and out of a flat registry snapshot
    snap = obs.snapshot()
    assert census(snap) == census(eng)
    assert snap['repro_engine_compilations{exec="decode"}'] \
        == eng.compilations["decode"]
    # a guard over a warm engine, subject = the Observer, stays quiet
    cfg, _ = _cfg_params()
    with retrace_guard(obs, label="warm rerun via observer"):
        eng.run(_requests(cfg, seed=5, rid0=100))
    # a snapshot with no census gauges is an empty census, not garbage
    assert census({"repro_tokens_generated_total": 5.0,
                   'repro_pages_total{op="grow"}': 2.0}) == {}


def test_observer_prefix_cache_hit_counters():
    cfg, _ = _cfg_params()
    obs = Observer()
    eng = _engine(observer=obs, cache_kind="paged", page_size=8,
                  prefix_cache=True)
    shared = 16   # two full pages of shared head
    eng.run(_requests(cfg, seed=11, shared_head=shared))
    hits0 = obs.metrics.get("repro_prefix_lookups_total").value(result="hit")
    eng.run(_requests(cfg, seed=12, shared_head=shared, rid0=50))
    m = obs.metrics
    assert m.get("repro_prefix_lookups_total").value(result="hit") > hits0
    assert m.get("repro_prefix_pages_saved_total").value() \
        == eng.prefix_hit_pages
    assert m.get("repro_prefix_tokens_saved_total").value() \
        == eng.prefix_hit_tokens
    assert m.get("repro_pages_total").value(op="publish") > 0


def test_observer_speculation_counters_match_engine_ledger():
    cfg, _ = _cfg_params()
    obs = Observer()
    eng = _engine(observer=obs, speculative=True, draft_k=4)
    rng = np.random.default_rng(2)
    motif = list(map(int, rng.integers(0, cfg.vocab_size, 3)))
    reqs = [Request(rid=i, tokens=(motif * 8)[:10], max_new=8)
            for i in range(4)]
    eng.run(reqs)
    m = obs.metrics
    assert m.get("repro_spec_verify_steps_total").value() == eng.spec_steps
    assert m.get("repro_decode_launches_total").value() == eng.spec_steps
    assert m.get("repro_spec_drafted_total").value() == eng.spec_drafted
    assert m.get("repro_spec_accepted_total").value() == eng.spec_accepted
    assert eng.spec_drafted > 0
    drafted = m.get("repro_spec_drafted_total").value()
    accepted = m.get("repro_spec_accepted_total").value()
    assert accepted / max(drafted, 1) == pytest.approx(eng.acceptance_rate)
    lk = m.get("repro_draft_lookups_total")
    assert lk.value(result="hit") + lk.value(result="miss") > 0
    assert m.get("repro_draft_proposed_tokens_total").value() >= drafted


def test_null_observer_is_inert_and_complete():
    # NullObserver mirrors every public hook of Observer (a new hook
    # must be added to both or engines crash with observer=None)
    hooks = [n for n in dir(Observer) if n.startswith(("on_", "phase",
                                                        "span"))]
    for n in hooks:
        assert callable(getattr(NullObserver, n, None)), n
    NULL_OBSERVER.on_step(queue_depth=1, occupied=2)
    NULL_OBSERVER.on_tokens(5)
    # off, or no span factory: every span is the one shared null span
    assert NULL_OBSERVER.phase("decode", slots=1) is NULL_SPAN
    for obs in (NULL_OBSERVER, Observer()):
        assert obs.span("engine.sync", n=1) is NULL_SPAN
        with obs.phase("decode", slots=1) as sp:
            assert sp is NULL_SPAN
            sp.set_metadata(n=1)
    assert NULL_OBSERVER.census() == {}


def test_observer_opens_spans_through_its_factory():
    log = SpanLog()
    obs = Observer(span=log)
    with obs.span("engine.step", step_num=7):
        with obs.phase("decode", slots=2) as sp:
            with obs.span("engine.sync"):
                pass
            sp.set_metadata(ctx_tokens=9)
    assert [(s["name"], s["args"]) for s in log.spans] == [
        ("engine.step", {"step_num": 7}),
        ("engine.decode", {"slots": 2, "ctx_tokens": 9}),
        ("engine.sync", {})]
    assert obs.metrics.get("repro_step_phase_seconds").count(
        phase="decode") == 1


def test_profiler_span_marks_the_step():
    assert type(profiler_span("engine.step", step_num=1)) is \
        jax.profiler.StepTraceAnnotation
    assert type(profiler_span("engine.sync")) is jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# clock unification
# ---------------------------------------------------------------------------


def test_serving_stack_has_one_clock_call_site():
    """``repro.obs.trace.now`` is the serving stack's only ``time.*``
    call site: request marks, trace timestamps, launcher and bench
    timings all read one clock."""
    root = os.path.join(os.path.dirname(__file__), "..")
    offenders = []
    scan = ["src/repro/serve", "src/repro/obs", "src/repro/launch/serve.py",
            "benchmarks/serving_bench.py", "examples/serve_lm.py"]
    for rel in scan:
        path = os.path.join(root, rel)
        files = ([os.path.join(path, f) for f in os.listdir(path)
                  if f.endswith(".py")] if os.path.isdir(path) else [path])
        for f in files:
            if f.endswith(os.path.join("obs", "trace.py")):
                continue
            src = open(f, encoding="utf-8").read()
            if "time.monotonic(" in src or "time.perf_counter(" in src \
                    or "time.time(" in src:
                offenders.append(os.path.relpath(f, root))
    assert not offenders, f"direct clock calls outside obs.trace: {offenders}"


def test_request_marks_come_from_the_shared_clock():
    cfg, _ = _cfg_params()
    eng = _engine()
    t0 = now()
    done = eng.run(_requests(cfg, n=3, seed=21))
    t1 = now()
    for r in done:
        assert t0 <= r.t_submit <= r.t_first <= r.t_done <= t1, \
            (r.rid, r.t_submit, r.t_first, r.t_done, t0, t1)
