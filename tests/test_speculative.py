"""Speculative decoding: prompt-lookup drafting + batched verification.

The gate is a randomized parity/property harness: ~50 seeded mixes of
(architecture, page size and pool, prefix cache, kernel impl, per-request
sampling params, preemption-inducing tiny page pools) must produce token
streams *identical* to ``speculative=False`` — acceptance/rollback may
only change *when* tokens appear, never *which* tokens.  Around it:
drafter unit tests, logits-level verify-vs-sequential-decode parity,
rollback edge cases (page-boundary rejection, preempt-mid-verify,
fully-rejected drafts, ``max_new`` reached mid-accept), draft-failure
isolation, the executable census under ``retrace_guard``, and the
explicit plain-decode fallback for recurrent/hybrid stacks.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.retrace_guard import retrace_guard
from repro.configs.base import get_config, shrink
from repro.core.famous import FamousConfig
from repro.models import module, transformer
from repro.serve.draft import PromptLookupDrafter
from repro.serve.engine import Request, ServingEngine
from repro.serve.paged import PagedCacheConfig

MAX_SEQ = 32
CHUNK = 8
ARCHS = ("qwen2-7b", "recurrentgemma-2b", "rwkv6-1.6b")


@functools.lru_cache(maxsize=None)
def _cfg_params(arch):
    cfg = shrink(get_config(arch))
    params = module.init_params(transformer.model_spec(cfg),
                                jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _serve(params, cfg, reqs, **kw):
    eng = ServingEngine(params, cfg, kw.pop("fcfg", FamousConfig(impl="xla")),
                        n_slots=kw.pop("n_slots", 2), max_seq=MAX_SEQ,
                        chunk=CHUNK, **kw)
    done = sorted(eng.run(reqs), key=lambda r: r.rid)
    return done, eng


# ---------------------------------------------------------------------------
# drafter unit tests (pure host policy)
# ---------------------------------------------------------------------------


def test_drafter_empty_cases():
    d = PromptLookupDrafter()
    assert d.draft([1, 2, 3], 0) == []
    assert d.draft([1], 4) == []          # too short for any n-gram + match
    assert d.draft([], 4) == []
    assert d.draft([1, 2, 3, 4], 4) == []  # no repeated n-gram anywhere


def test_drafter_finds_longest_ngram():
    # trailing 3-gram (1,2,3) recurs at the head: the continuation there
    # (9, 1) is the draft
    d = PromptLookupDrafter(max_ngram=3)
    assert d.draft([1, 2, 3, 9, 1, 2, 3], 2) == [9, 1]


def test_drafter_prefers_most_recent_match():
    # trailing (1,2) occurs twice; the LATER occurrence (continuation 8)
    # wins — recency tracks the generation's current phrasing
    d = PromptLookupDrafter(max_ngram=2)
    out = d.draft([5, 1, 2, 7, 1, 2, 8, 1, 2], 3)
    assert out == [8, 1, 2]


def test_drafter_falls_back_to_shorter_ngram():
    # no 2/3-gram repeats, but the trailing 1-gram (4,) recurs; its most
    # recent earlier occurrence (index 2) continues with 7
    d = PromptLookupDrafter(max_ngram=3, min_ngram=1)
    assert d.draft([4, 6, 4, 7, 9, 4], 1) == [7]


def test_drafter_truncates_at_sequence_end():
    # the match sits near the tail: fewer than k continuation tokens exist
    d = PromptLookupDrafter(max_ngram=2)
    assert d.draft([1, 2, 9, 1, 2], 4) == [9, 1, 2]


# ---------------------------------------------------------------------------
# logits-level parity: one verify call == W sequential decode steps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("cache", ["paged", "paged_int8", "paged_ps4"])
def test_verify_step_matches_sequential_decode(impl, cache):
    """verify_step's row j must equal the logits of the j+1-th sequential
    decode_step over the same tokens (causality makes the parallel and
    sequential activations identical) — the foundation the engine's
    accept rule stands on.  The verified draft crosses a page boundary,
    at page size 8 and 4.  The int8 axis holds because BOTH paths write
    the same quantized values before attending: quantization is lossy vs
    fp, but deterministic, so verify-vs-sequential stays exact."""
    cfg, params = _cfg_params("qwen2-7b")
    fcfg = FamousConfig(impl=impl)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=9)
    W = 4
    ps = 4 if cache == "paged_ps4" else 8
    n_p = MAX_SEQ // ps
    caches = transformer.make_caches(
        cfg, 1, MAX_SEQ, jnp.float32, page_size=ps, n_pages=n_p + 1,
        kv_dtype="int8" if cache == "paged_int8" else "fp")
    # pages 1..n_p back the single slot (page 0 is the null page)
    kw = {"page_table": jnp.arange(1, n_p + 1, dtype=jnp.int32)[None]}
    seq_caches = caches
    seq_logits = []
    for j, t in enumerate(toks):
        lg, seq_caches = transformer.decode_step(
            params, jnp.asarray([t], jnp.int32), seq_caches,
            jnp.asarray([j], jnp.int32), cfg, fcfg,
            active=jnp.asarray([True]), **kw)
        seq_logits.append(np.asarray(lg[0]))
    # verify the last W tokens in one shot, on top of the first 9 - W
    ver_caches = caches
    L = len(toks) - W
    assert L // ps != (L + W - 1) // ps      # the draft crosses a page
    for j, t in enumerate(toks[:L]):
        _, ver_caches = transformer.decode_step(
            params, jnp.asarray([t], jnp.int32), ver_caches,
            jnp.asarray([j], jnp.int32), cfg, fcfg,
            active=jnp.asarray([True]), **kw)
    vlg, _ = transformer.verify_step(
        params, jnp.asarray(toks[None, L:], jnp.int32), ver_caches,
        jnp.asarray([L], jnp.int32), cfg, fcfg, **kw)
    for j in range(W):
        np.testing.assert_allclose(np.asarray(vlg[0, j]), seq_logits[L + j],
                                   atol=3e-5, rtol=1e-5)


def test_verify_step_rejects_non_attention_stacks():
    cfg, params = _cfg_params("recurrentgemma-2b")
    caches = transformer.make_caches(cfg, 1, MAX_SEQ, jnp.float32,
                                     page_size=8, n_pages=1 + MAX_SEQ // 8)
    with pytest.raises(ValueError, match="global-attention"):
        transformer.verify_step(params, jnp.zeros((1, 3), jnp.int32), caches,
                                jnp.zeros((1,), jnp.int32), cfg,
                                FamousConfig(impl="xla"),
                                page_table=jnp.zeros((1, MAX_SEQ // 8),
                                                     jnp.int32))


# ---------------------------------------------------------------------------
# the randomized parity/property harness
# ---------------------------------------------------------------------------


def _random_mix(mix_seed):
    """One randomized serving scenario: engine kwargs + request list."""
    rng = np.random.default_rng(10_000 + mix_seed)
    arch = ARCHS[rng.choice(3, p=[0.7, 0.15, 0.15])]
    cfg, params = _cfg_params(arch)
    impl = "pallas" if rng.random() < 0.2 else "xla"
    kw = {"fcfg": FamousConfig(impl=impl),
          "n_slots": int(rng.integers(2, 4)),
          "draft_k": int(rng.integers(1, 6))}
    if rng.random() < 0.5:
        ps = int(rng.choice([4, 8]))
        kw.update(page_size=ps)
        if rng.random() < 0.4:
            # tiny pool: big enough to back any single request, small
            # enough that concurrent slots fight over pages (preemption)
            kw["n_pages"] = (PagedCacheConfig(page_size=ps, n_pages=2)
                             .pages_for(MAX_SEQ) + 1 + int(rng.integers(0, 3)))
        if rng.random() < 0.5:
            kw["prefix_cache"] = True
        if rng.random() < 0.3:
            # quantized KV: spec-vs-plain parity must survive lossy caches
            # (both sides read the same int8 pages)
            kw["kv_dtype"] = "int8"
    reqs = []
    shared = list(map(int, rng.integers(0, cfg.vocab_size, 11)))
    for i in range(int(rng.integers(3, 7))):
        max_new = int(rng.integers(3, 9))
        n = int(rng.integers(1, MAX_SEQ - max_new + 1))
        if rng.random() < 0.5:
            # periodic prompt: the n-gram drafter actually fires on these
            motif = list(map(int, rng.integers(0, cfg.vocab_size, 3)))
            prompt = (motif * MAX_SEQ)[:n]
        elif rng.random() < 0.5:
            prompt = (shared + list(
                map(int, rng.integers(0, cfg.vocab_size, MAX_SEQ))))[:n]
        else:
            prompt = list(map(int, rng.integers(0, cfg.vocab_size, n)))
        greedy = rng.random() < 0.6
        reqs.append(dict(rid=i, tokens=prompt, max_new=max_new,
                         temperature=0.0 if greedy else
                         float(rng.uniform(0.5, 1.0)),
                         top_k=int(rng.choice([0, 4, 8])),
                         seed=int(rng.integers(0, 2**31))))
    return arch, cfg, params, kw, reqs


@pytest.mark.parametrize("mix_seed", range(50))
def test_speculative_parity_random_mix(mix_seed):
    """Speculative serving must be token-identical to plain serving for
    every randomized mix, with no request dropped or errored and the
    allocator invariants intact."""
    arch, cfg, params, kw, req_specs = _random_mix(mix_seed)
    ref, _ = _serve(params, cfg,
                    [Request(**s) for s in req_specs], **dict(kw))
    spec, eng = _serve(params, cfg, [Request(**s) for s in req_specs],
                       speculative=True, **dict(kw))
    assert len(spec) == len(req_specs)
    assert all(r.error is None and r.done for r in ref + spec), \
        [(r.rid, r.error) for r in ref + spec]
    assert [r.out for r in spec] == [r.out for r in ref], (arch, kw)
    eng.alloc.assert_invariants()
    if arch == "qwen2-7b":
        assert eng.speculative_active
        # verify REPLACED decode: the decode executable never compiled
        assert eng.compilations["decode"] == 0
    else:
        assert not eng.speculative_active   # recurrent/hybrid fallback


# ---------------------------------------------------------------------------
# rollback edge cases (scripted drafters make the accept length exact)
# ---------------------------------------------------------------------------


class ScriptedDrafter:
    """Drafts ``(ref_out[pos + j] + delta) % vocab``: delta=0 is an
    oracle (every draft token accepted), any other delta guarantees the
    first draft token is rejected (fully-rejected drafts)."""

    def __init__(self, prompt_len, ref_out, vocab, delta=0):
        self.prompt_len, self.ref, self.vocab, self.delta = \
            prompt_len, list(ref_out), vocab, delta

    def draft(self, seq, k):
        pos = len(seq) - self.prompt_len
        return [(t + self.delta) % self.vocab
                for t in self.ref[pos:pos + k]]


class PoisonDrafter(PromptLookupDrafter):
    """Raises for one specific prompt; drafts normally for everyone else."""

    def __init__(self, poison_prefix):
        super().__init__()
        self.poison = list(poison_prefix)

    def draft(self, seq, k):
        if seq[:len(self.poison)] == self.poison:
            raise RuntimeError("poisoned request")
        return super().draft(seq, k)


def _ref_out(params, cfg, prompt, max_new, **kw):
    done, _ = _serve(params, cfg,
                     [Request(rid=0, tokens=list(prompt), max_new=max_new)],
                     **kw)
    assert done[0].error is None
    return done[0].out


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_rejected_draft_at_page_boundary_frees_pages(kv_dtype):
    """A draft that grows the slot across a page boundary and is then
    fully rejected must give the boundary page back — held pages track
    ``cache_len`` exactly after every step (no leak), and the pool is
    clean after retirement.  The int8 axis checks the scale rows shrink
    in lockstep: they share the freed page ids, so a leak would trip
    ``assert_invariants`` or the held-pages accounting."""
    cfg, params = _cfg_params("qwen2-7b")
    rng = np.random.default_rng(3)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 6)))
    # the reference comes from a plain engine with the SAME cache dtype:
    # int8 greedy may lawfully diverge from fp greedy, rejection must not
    ref = _ref_out(params, cfg, prompt, 12, page_size=4,
                   kv_dtype=kv_dtype)
    drafter = ScriptedDrafter(len(prompt), ref, cfg.vocab_size, delta=1)
    eng = ServingEngine(params, cfg, FamousConfig(impl="xla"), n_slots=2,
                        max_seq=MAX_SEQ, chunk=CHUNK,
                        page_size=4, speculative=True, draft_k=5,
                        drafter=drafter, kv_dtype=kv_dtype)
    req = Request(rid=0, tokens=list(prompt), max_new=12)
    eng.sched.enqueue(req)
    eng.add_request(eng.sched.pop_queued())
    while not req.done:
        eng.step()
        eng.alloc.assert_invariants()
        if not req.done:   # slot 0 still live: no draft page survives
            assert eng.alloc.pages_held(0) == \
                eng.pcfg.pages_for(int(eng.cache_len[0]))
    assert req.error is None and req.out == ref
    assert eng.spec_accepted == 0          # every draft token rejected
    assert eng.alloc.free_pages == eng.pcfg.n_pages - 1   # all returned


def test_fully_rejected_drafts_emit_exactly_one_token_per_step():
    cfg, params = _cfg_params("qwen2-7b")
    rng = np.random.default_rng(4)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 5)))
    ref = _ref_out(params, cfg, prompt, 8)
    drafter = ScriptedDrafter(len(prompt), ref, cfg.vocab_size, delta=7)
    done, eng = _serve(params, cfg,
                       [Request(rid=0, tokens=list(prompt), max_new=8)],
                       speculative=True, draft_k=3, drafter=drafter)
    assert done[0].out == ref
    assert eng.spec_accepted == 0 and eng.spec_drafted > 0
    assert eng.spec_steps == len(ref)      # one bonus token per verify step
    assert eng.acceptance_rate == 0.0 and eng.accepted_per_step == 1.0


def test_oracle_drafter_hits_max_new_exactly():
    """``max_new`` reached mid-accept: the draft cap trims the last step's
    width so the request finishes with EXACTLY max_new tokens (no
    overshoot), in fewer verify steps than tokens."""
    cfg, params = _cfg_params("qwen2-7b")
    rng = np.random.default_rng(5)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 5)))
    max_new = 7                            # not a multiple of draft_k + 1
    ref = _ref_out(params, cfg, prompt, max_new)
    drafter = ScriptedDrafter(len(prompt), ref, cfg.vocab_size, delta=0)
    done, eng = _serve(params, cfg,
                       [Request(rid=0, tokens=list(prompt), max_new=max_new)],
                       speculative=True, draft_k=3, drafter=drafter)
    assert done[0].out == ref and len(done[0].out) == max_new
    assert eng.spec_steps == 2             # 4 + 3 tokens, width-capped
    assert eng.spec_accepted == max_new - eng.spec_steps


def test_preemption_mid_speculation_stays_token_identical():
    """A pool too small for all slots forces preemption while drafts are
    in flight; resumed requests must still match plain decode exactly."""
    cfg, params = _cfg_params("qwen2-7b")
    rng = np.random.default_rng(6)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 14 + 5 * i)))
               for i in range(3)]

    def reqs():
        return [Request(rid=i, tokens=list(p), max_new=8)
                for i, p in enumerate(prompts)]

    kw = dict(page_size=4,
              n_pages=PagedCacheConfig(page_size=4, n_pages=2)
              .pages_for(MAX_SEQ) + 2)
    ref, _ = _serve(params, cfg, reqs(), **dict(kw))
    spec, eng = _serve(params, cfg, reqs(), speculative=True, draft_k=4,
                       **dict(kw))
    assert all(r.error is None for r in ref + spec)
    assert [r.out for r in spec] == [r.out for r in ref]
    assert sum(st.get("preemptions", 0)
               for st in eng.sched.stats.values()) >= 1
    eng.alloc.assert_invariants()


def test_poisoned_drafter_fails_alone():
    """One request whose drafting raises comes back with ``req.error``
    set; co-scheduled requests finish normally and token-identically."""
    cfg, params = _cfg_params("qwen2-7b")
    rng = np.random.default_rng(8)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 6 + 2 * i)))
               for i in range(3)]
    ref, _ = _serve(params, cfg, [Request(rid=i, tokens=list(p), max_new=6)
                                  for i, p in enumerate(prompts)])
    spec, eng = _serve(params, cfg,
                       [Request(rid=i, tokens=list(p), max_new=6)
                        for i, p in enumerate(prompts)],
                       speculative=True, drafter=PoisonDrafter(prompts[1]))
    assert spec[1].error is not None and "poisoned" in spec[1].error
    for i in (0, 2):
        assert spec[i].error is None
        assert spec[i].out == ref[i].out


# ---------------------------------------------------------------------------
# executable census / fallback
# ---------------------------------------------------------------------------


def test_speculative_census_and_retrace():
    """Warmed speculative engine: at most three hot executables (prefill,
    verify, clear), decode never compiled, and a fresh mixed workload
    triggers zero new compilations."""
    cfg, params = _cfg_params("qwen2-7b")
    rng = np.random.default_rng(9)

    def reqs(rid0):
        return [Request(rid=rid0 + i, max_new=4,
                        tokens=list(map(int, rng.integers(
                            0, cfg.vocab_size, 1 + 4 * i))),
                        temperature=0.7 if i == 2 else 0.0, top_k=4)
                for i in range(3)]

    eng = ServingEngine(params, cfg, FamousConfig(impl="xla"), n_slots=2,
                        max_seq=MAX_SEQ, chunk=CHUNK,
                        page_size=8, prefix_cache=True, speculative=True,
                        draft_k=3)
    eng.run(reqs(0))
    census = eng.compilations
    assert census["decode"] == 0
    assert census["prefill"] + census["verify"] + census["clear"] <= 3
    with retrace_guard(eng, label="warm speculative loop"):
        eng.run(reqs(10))


def test_recurrent_arch_falls_back_to_plain_decode():
    """``speculative=True`` on a recurrent stack must not break serving:
    the engine degrades to plain decode explicitly (no verify compile,
    no speculative accounting) and stays token-identical."""
    for arch in ("rwkv6-1.6b", "recurrentgemma-2b"):
        cfg, params = _cfg_params(arch)
        rng = np.random.default_rng(11)
        prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 5 + 3 * i)))
                   for i in range(2)]
        ref, _ = _serve(params, cfg,
                        [Request(rid=i, tokens=list(p), max_new=5)
                         for i, p in enumerate(prompts)])
        spec, eng = _serve(params, cfg,
                           [Request(rid=i, tokens=list(p), max_new=5)
                            for i, p in enumerate(prompts)],
                           speculative=True, draft_k=4)
        assert not eng.speculative_active
        assert eng.spec_steps == 0
        assert eng.compilations["verify"] == 0
        assert eng.compilations["decode"] >= 1
        assert [r.out for r in spec] == [r.out for r in ref]
