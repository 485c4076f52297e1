"""Scheduler (pure policy) unit tests + Scheduler/Runtime integration:
token-budget math, FIFO chunk allocation, youngest-first preemption
choice, fairness accounting, decode-between-prefill-chunks interleaving,
and the O(1)-compilation guarantee the chunked runtime exists for."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import retrace_guard
from repro.configs.base import get_config, shrink
from repro.core.famous import FamousConfig
from repro.models import module, transformer
from repro.serve.engine import Request, ServingEngine
from repro.serve.scheduler import (DECODE, FREE, PREFILL, Scheduler,
                                   SchedulerConfig)

FCFG = FamousConfig(impl="xla")


def _params(cfg):
    return module.init_params(transformer.model_spec(cfg),
                              jax.random.PRNGKey(0), jnp.float32)


# ---------------------------------------------------------------------------
# pure policy (no jax, no engine)
# ---------------------------------------------------------------------------


def _req(rid, n):
    return Request(rid=rid, tokens=list(range(1, n + 1)))


def _prefilled(s, slot):
    """Prefill the slot's whole prompt in one chunk: it decodes next."""
    info = s.slots[slot]
    assert s.on_chunk(slot, info.target - info.done)


def test_bind_and_chunk_lifecycle():
    s = Scheduler(2, SchedulerConfig(chunk=8))
    assert s.bind(0, _req(0, 20), 20) == PREFILL
    assert s.slots[0].target == 19
    assert not s.on_chunk(0, 8)
    assert not s.on_chunk(0, 8)
    assert s.on_chunk(0, 3)           # 19 done -> DECODE
    assert s.slots[0].state == DECODE
    assert s.bind(1, _req(1, 1), 1) == DECODE  # nothing to prefill


def test_plan_budget_one_chunk_while_decoding():
    """Default budget (n_slots + chunk): exactly one prefill chunk per step
    while decodes are active — decode never starves behind a long prompt."""
    s = Scheduler(4, SchedulerConfig(chunk=8))
    for i in range(3):
        s.bind(i, _req(i, 2), 2)
        _prefilled(s, i)
    s.bind(3, _req(3, 65), 65)        # long prompt: 64 tokens to prefill
    plan = s.plan()
    assert plan.decode_slots == [0, 1, 2]
    assert len(plan.chunks) == 1
    assert (plan.chunks[0].slot, plan.chunks[0].start, plan.chunks[0].n) \
        == (3, 0, 8)


def test_plan_decode_width_charges_verify_cost():
    """Speculative serving sets decode_width = draft_k + 1: a decoding
    slot is charged the verify executable's full fixed width, so prefill
    chunks are granted against the step's TRUE compute — while the
    default budget widens in lockstep (one chunk per step still fits)."""
    s = Scheduler(4, SchedulerConfig(chunk=8, decode_width=4))
    for i in range(3):
        s.bind(i, _req(i, 2), 2)
        _prefilled(s, i)
    s.bind(3, _req(3, 65), 65)
    plan = s.plan()                   # default budget 4*4 + 8 = 24
    assert plan.decode_slots == [0, 1, 2]
    assert len(plan.chunks) == 1      # 24 - 3*4 = 12 -> one 8-token chunk
    # an explicit budget is consumed decode_width per decoding slot:
    # 24 - 3*4 = 12 leaves one chunk, where width-1 accounting (24 - 3)
    # would have granted two
    s2 = Scheduler(4, SchedulerConfig(chunk=8, token_budget=24,
                                      decode_width=4))
    for i in range(3):
        s2.bind(i, _req(i, 2), 2)
        _prefilled(s2, i)
    s2.bind(3, _req(3, 65), 65)
    assert len(s2.plan().chunks) == 1
    s3 = Scheduler(4, SchedulerConfig(chunk=8, token_budget=24))
    for i in range(3):
        s3.bind(i, _req(i, 2), 2)
        _prefilled(s3, i)
    s3.bind(3, _req(3, 65), 65)
    assert len(s3.plan().chunks) == 2


def test_on_draft_accounting_reaches_fairness():
    s = Scheduler(1, SchedulerConfig(chunk=8))
    s.bind(0, _req(0, 2), 2)
    _prefilled(s, 0)
    s.on_draft(0, drafted=4, accepted=2)
    s.on_draft(0, drafted=3, accepted=0)
    st = s.fairness(0)
    assert st["drafted_tokens"] == 7
    assert st["accepted_tokens"] == 2


def test_plan_idle_engine_spends_whole_budget_on_prefill():
    s = Scheduler(2, SchedulerConfig(chunk=8, token_budget=32))
    s.bind(0, _req(0, 65), 65)
    plan = s.plan()
    assert plan.decode_slots == []
    assert [c.start for c in plan.chunks] == [0, 8, 16, 24]
    assert all(c.n == 8 for c in plan.chunks)


def test_plan_grants_minimum_one_chunk():
    """Forward progress even when decodes alone exceed the budget."""
    s = Scheduler(4, SchedulerConfig(chunk=8, token_budget=2))
    for i in range(3):
        s.bind(i, _req(i, 2), 2)
        _prefilled(s, i)
    s.bind(3, _req(3, 30), 30)
    plan = s.plan()
    assert len(plan.chunks) == 1


def test_plan_fifo_oldest_prefill_first():
    s = Scheduler(2, SchedulerConfig(chunk=8, token_budget=16))
    s.bind(1, _req(0, 33), 33)        # admitted first (into slot 1)
    s.bind(0, _req(1, 33), 33)
    plan = s.plan()
    assert [c.slot for c in plan.chunks] == [1, 1]  # finish the elder first


def test_final_chunk_is_partial():
    s = Scheduler(1, SchedulerConfig(chunk=8, token_budget=64))
    s.bind(0, _req(0, 12), 12)        # target 11 -> chunks of 8 and 3
    plan = s.plan()
    assert [(c.start, c.n) for c in plan.chunks] == [(0, 8), (8, 3)]


def test_preempt_victim_youngest_including_prefilling():
    s = Scheduler(3, SchedulerConfig(chunk=8))
    s.bind(0, _req(0, 5), 5)
    _prefilled(s, 0)
    s.bind(1, _req(1, 5), 5)
    _prefilled(s, 1)
    s.bind(2, _req(2, 30), 30)        # youngest, still prefilling
    assert s.preempt_victim() == 2
    assert s.preempt_victim(exclude=(2,)) == 1
    req = s.preempt(2)
    assert req.rid == 2 and s.slots[2].state == FREE
    assert s.stats[2]["preemptions"] == 1


def test_bind_cached_prefix_starts_prefill_at_first_uncached_token():
    """Prefix-cache admission: bind(cached=) skips the cached head — the
    first chunk starts there, and a fully-cached target goes straight to
    DECODE with the saving on the fairness ledger."""
    s = Scheduler(2, SchedulerConfig(chunk=8, token_budget=64))
    assert s.bind(0, _req(0, 21), 21, cached=16) == PREFILL   # target 20
    plan = s.plan()
    assert [(c.start, c.n) for c in plan.chunks if c.slot == 0] == [(16, 4)]
    assert s.fairness(0)["cached_tokens"] == 16
    # cached >= target: nothing to prefill at all
    assert s.bind(1, _req(1, 17), 17, cached=16) == DECODE
    assert s.slots[1].done == s.slots[1].target == 16
    assert s.fairness(1)["cached_tokens"] == 16


def test_fairness_accounting():
    s = Scheduler(1, SchedulerConfig(chunk=4))
    r = _req(7, 9)
    s.enqueue(r)
    s.tick(); s.tick()                # queued for 2 steps
    s.bind(0, s.pop_queued(), 9)
    s.on_chunk(0, 4); s.on_chunk(0, 4)
    s.on_decode_token(0)
    f = s.fairness(7)
    assert f["enqueue_step"] == 0 and f["admit_step"] == 2
    assert f["prefill_tokens"] == 8 and f["decode_tokens"] == 1
    assert f["ttft_steps"] == 2


# ---------------------------------------------------------------------------
# integration: the properties the split exists for
# ---------------------------------------------------------------------------


def test_decode_proceeds_between_prefill_chunks():
    """The acceptance check: while a long prompt prefills chunk by chunk,
    an already-decoding request keeps emitting tokens every step."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(0)
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=128,
                           chunk=16)
    short = Request(rid=0, tokens=list(rng.integers(0, cfg.vocab_size, 5)),
                    max_new=30)
    engine.add_request(short)
    engine.step()
    long = Request(rid=1, tokens=list(rng.integers(0, cfg.vocab_size, 100)),
                   max_new=4)
    engine.add_request(long)
    interleaved = 0
    prefill_steps = 0
    while engine.sched.slots[1].state == PREFILL:
        before = len(short.out)
        engine.step()
        prefill_steps += 1
        if len(short.out) > before:
            interleaved += 1
    assert prefill_steps >= 6          # 99 tokens / 16-chunk -> 7 steps
    assert interleaved >= prefill_steps - 1  # decode ran alongside chunks
    # and the long prompt still completes correctly afterwards
    done = engine.run([])
    assert {r.rid for r in done} == {0, 1}


def test_prefill_compilations_o1_mixed_lengths():
    """Regression for the unbounded ``_prefill_exec`` growth on
    exact-length (recurrent) prefill: 20 requests of 16 distinct lengths
    through a recurrent arch compile exactly ONE prefill executable."""
    cfg = shrink(get_config("rwkv6-1.6b"))
    params = _params(cfg)
    rng = np.random.default_rng(1)
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64,
                           chunk=16)
    lens = list(range(2, 61, 3))          # 20 distinct prompt lengths
    reqs = [Request(rid=i, tokens=list(rng.integers(0, cfg.vocab_size, n)),
                    max_new=2) for i, n in enumerate(lens)]
    done = engine.run(reqs)
    assert len(done) == len(lens) == 20
    assert engine.prefill_compilations == 1


def test_total_compilations_bounded():
    """O(1) executables for any prompt-length mix: the first batch pays
    the warmup compiles (chunk, decode, and the clear used by
    single-token admissions); a second, differently-mixed batch through
    the warm engine must compile nothing at all (retrace_guard)."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(2)
    engine = ServingEngine(params, cfg, FCFG, n_slots=4, max_seq=128,
                           chunk=16)
    lens = [1, 3, 9, 17, 33, 64, 100, 5, 27, 2]
    reqs = [Request(rid=i, tokens=list(rng.integers(0, cfg.vocab_size, n)),
                    max_new=3) for i, n in enumerate(lens)]
    done = engine.run(reqs)
    assert len(done) == len(lens)
    lens2 = [4, 1, 50, 8, 31]
    reqs2 = [Request(rid=100 + i,
                     tokens=list(rng.integers(0, cfg.vocab_size, n)),
                     max_new=3) for i, n in enumerate(lens2)]
    with retrace_guard(engine, label="steady-state mixed batch"):
        done2 = engine.run(reqs2)
    assert len(done2) == len(lens2)


def test_scheduler_stats_reach_engine_requests():
    """TTFT/TPOT raw material: wall-clock marks land on the requests and
    the scheduler ledger sees every served token."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(3)
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64, chunk=8)
    reqs = [Request(rid=i, tokens=list(rng.integers(0, cfg.vocab_size, 9)),
                    max_new=4) for i in range(3)]
    done = engine.run(reqs)
    for r in done:
        assert r.t_submit is not None and r.t_first is not None
        assert r.t_done is not None and r.t_done >= r.t_first >= r.t_submit
        f = engine.sched.fairness(r.rid)
        assert f["decode_tokens"] == 4 and f["prefill_tokens"] == 8
