"""The harness loop and its window accounting, rehearsed on a shrunk
engine on the CPU (``harness.run`` skips only the look for a chip)."""
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2 ** 31 + 4321


def _data(recs, steps=(), admissions=(), t0=1.0, t1=2.0):
    return harness.RunData(cell=None, t0=t0, t1=t1, recs=list(recs),
                           steps=list(steps), dims={}, peak={}, setup_s=3.5,
                           lags=[], admissions=list(admissions))


def _rec(rid, due, stamps=(), failed=False):
    stamps = list(stamps)
    return harness.Record(rid=rid, prompt_len=10, due=due,
                          first=stamps[0] if stamps else None,
                          stamps=stamps, failed=failed)


RECS = [
    _rec(0, 0.5, [0.7, 0.9, 1.1, 1.3]),       # due before the window
    _rec(1, 1.2, [1.5, 1.6, 1.8, 2.2]),       # last token after it
    _rec(2, 1.9),                             # no first token by t1
    _rec(3, 1.0, [1.3, 1.45]),
    _rec(4, 2.0, [2.1]),                      # due at t1: not in the window
]


def test_ttft_from_due_time_with_censoring():
    # in the window: rid 1 (0.3 s), rid 2 (censored at t1: 0.1 s), rid 3
    # (0.3 s)
    want = np.percentile([0.3, 0.1, 0.3], 90) * 1e3
    got = harness.metric_reader("ttft_p90_ms")(_data(RECS))
    assert got == pytest.approx(want)


def test_a_stall_cannot_hide_in_the_ttft_tail():
    stalled = [_rec(i, 1.0 + 0.1 * i) for i in range(10)]
    got = harness.metric_reader("ttft_p90_ms")(_data(stalled))
    assert got == pytest.approx(np.percentile(
        [1.0 - 0.1 * i for i in range(10)], 90) * 1e3)


def test_inter_token_gaps_inside_the_window():
    # rid 0: 1.1 -> 1.3; rid 1: 1.5 -> 1.6 -> 1.8 (2.2 is past t1);
    # rid 3: 1.3 -> 1.45
    want = np.percentile([0.2, 0.1, 0.2, 0.15], 99) * 1e3
    got = harness.metric_reader("itl_p99_ms")(_data(RECS))
    assert got == pytest.approx(want)


def test_output_rate_over_the_window():
    d = _data(RECS)
    # stamps in [1, 2]: rid 0 two, rid 1 three, rid 3 two
    assert harness.metric_reader("output_tok_s")(d) == pytest.approx(7.0)
    assert harness.metric_reader("setup_s")(d) == 3.5


def test_decode_batch_over_the_window_steps():
    steps = [harness.StepLog(1.0, [(0, 32)], []),            # no decode
             harness.StepLog(1.4, [(32, 32)], [11, 40, 7]),
             harness.StepLog(1.8, [], [12]),
             harness.StepLog(2.1, [], [13, 41])]            # ends past t1
    d = _data(RECS, steps)
    assert harness.metric_reader("decode_batch_mean")(d) == pytest.approx(2.0)


def test_prefix_hit_share_over_window_admissions():
    adm = [(0.5, 100, 100), (1.2, 2100, 2048), (1.5, 2080, 2048),
           (2.5, 50, 0)]
    got = harness.metric_reader("prefix_hit_share")(_data([], admissions=adm))
    assert got == pytest.approx(100.0 * 4096 / 4180)


def test_prompt_rate_counts_the_chunks_of_the_window_steps():
    steps = [harness.StepLog(0.9, [(0, 32)], []),            # before t0
             harness.StepLog(1.4, [(32, 32), (0, 16)], [11]),
             harness.StepLog(1.8, [(64, 8)], [12]),
             harness.StepLog(2.1, [(72, 32)], [13])]          # past t1
    d = _data([], steps, t0=1.0, t1=1.5)
    assert harness.metric_reader("prompt_tok_s")(d) == pytest.approx(96.0)


class _Req:
    def __init__(self, rid, n_out):
        self.tokens, self.out = [rid, 2, 3], list(range(n_out))


def test_the_sample_reaches_every_group_of_slots_that_served():
    # 16 slots in 4 groups; most requests decoded in slots 0-3, one in
    # slot 13: whatever the seed, that one is judged
    recs, reqs = [], {}
    for rid in range(12):
        r = _rec(rid, 0.1 * rid, [1.0])
        r.end, r.slots = 1.0, [13 if rid == 7 else rid % 4]
        recs.append(r)
        reqs[rid] = _Req(rid, 20 if rid == 0 else 5)
    for seed in (1, 2, 3, 2 ** 31 + 5):
        items = harness.sample_items(recs, reqs, seed, b_pad=4, s_pad=64,
                                     n_slots=16)
        picked = [prompt[0] for prompt, _ in items]
        assert len(picked) == len(set(picked)) == 4
        assert picked[0] == 0                            # the longest
        assert 7 in picked


def _run(mix, **kw):
    seen = {}
    t = time.perf_counter()
    res = harness.run(tiny.cell(mix), SEED, 1.5, False, t,
                      kind="TPU v5 lite", inspect=seen.update, **kw)
    return res, seen["data"], seen["reqs"]


@pytest.mark.parametrize("mix", ["open", "closed"])
def test_rehearsal_accounts_for_every_request(mix):
    res, data, reqs = _run(mix)
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True
    assert res["device"]["platform"] == "cpu"
    due = [r for r in data.recs if data.t0 <= r.due < data.t1]
    assert res["attempted"] == len(due) > 0
    assert res["failed"] == sum(r.failed for r in due) == 0
    for r in data.recs:
        req = reqs[r.rid]
        assert len(r.stamps) == len(req.out)          # one stamp a token
        assert r.stamps == sorted(r.stamps)
        if r.stamps:
            assert r.first == r.stamps[0] >= r.due
        if r.end is not None:
            assert len(req.out) == req.max_new and r.end == r.stamps[-1]
    names = {m["name"] for m in tiny.cell(mix).end_to_end}
    assert set(res["metrics"]) == names
    for name, m in res["metrics"].items():
        assert m["value"] > 0, name


def test_open_loop_times_from_the_schedule():
    res, data, _ = _run("open")
    opened = data.t0 - tiny.MIXES["open"]["ramp_s"]
    dues = sorted(r.due for r in data.recs)
    gaps = np.diff([opened] + dues)
    assert (gaps > 0).all()
    # the schedule, not the moment the loop got round to a request
    assert len(data.lags) == len(dues)
    assert all(lag >= 0 for lag in data.lags)


def test_closed_loop_keeps_every_client_busy():
    res, data, _ = _run("closed")
    clients = tiny.MIXES["closed"]["clients"]
    by_client = {}
    for r in sorted(data.recs, key=lambda r: r.due):
        by_client.setdefault(r.client, []).append(r)
    assert sorted(by_client) == list(range(clients))
    for rs in by_client.values():
        # a client sends its next request when its last one completes
        for a, b in zip(rs, rs[1:]):
            assert b.due == a.end


def test_shared_documents_hit_the_prefix_cache():
    res, data, _ = _run("docs")
    share = harness.metric_reader("prefix_hit_share")(data)
    assert share > 50.0


def test_a_compile_inside_the_window_is_refused():
    """A decode step that compiles a new program on every call: the
    engine's census does not see it, the compile listener does."""
    import jax

    def hook(eng):
        decode = eng._decode

        class Compiling:
            def __call__(self, *a, **k):
                logits, caches = decode(*a, **k)
                return jax.jit(lambda x: x + 0)(logits), caches

            def _cache_size(self):
                return decode._cache_size()

        eng._decode = Compiling()

    with pytest.raises(RuntimeError, match="compiled inside the measured"):
        harness.run(tiny.cell("closed"), SEED, 1.0, False, time.perf_counter(),
                    engine_hook=hook, kind="TPU v5 lite")
