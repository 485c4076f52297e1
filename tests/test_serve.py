"""Serving-engine integration: continuous batching with chunked prefill
into the paged pool produces exactly the greedy tokens of the cache-free
full-sequence forward (xla + pallas, attention/hybrid/recurrent archs,
prompts of partial, exact and multi-chunk lengths), one prefill executable
serves every prompt-length mix, long prompts prefill across many chunks,
and per-request sampling is reproducible."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, shrink
from repro.core.famous import FamousConfig
from repro.models import module, transformer
from repro.serve.engine import Request, ServingEngine

FCFG = FamousConfig(impl="xla")


def _params(cfg):
    return module.init_params(transformer.model_spec(cfg),
                              jax.random.PRNGKey(0), jnp.float32)


def _serve(params, cfg, prompts, max_new, **kw):
    engine = ServingEngine(params, cfg, kw.pop("fcfg", FCFG), **kw)
    reqs = [Request(rid=i, tokens=list(p), max_new=max_new)
            for i, p in enumerate(prompts)]
    done = sorted(engine.run(reqs), key=lambda r: r.rid)
    assert len(done) == len(prompts)
    assert all(r.error is None for r in done)
    return [r.out for r in done], engine


@pytest.mark.parametrize("arch", ["qwen2-7b", "recurrentgemma-2b",
                                  "rwkv6-1.6b"])
def test_engine_matches_sequential_reference(arch, greedy_reference):
    cfg = shrink(get_config(arch))
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (5, 9, 17, 3)]
    refs = [greedy_reference(params, cfg, p, 6) for p in prompts]
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=128)
    reqs = [Request(rid=i, tokens=p, max_new=6) for i, p in enumerate(prompts)]
    done = sorted(engine.run(reqs), key=lambda r: r.rid)
    for req, ref in zip(done, refs):
        assert req.out == ref, (arch, req.rid, req.out, ref)


# ---------------------------------------------------------------------------
# chunked prefill against the forward oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen2-7b", "recurrentgemma-2b",
                                  "rwkv6-1.6b"])
def test_chunked_matches_monolithic(arch, greedy_reference):
    """The forward's greedy tokens whether a prompt fits one chunk or
    spans several, between other requests' decode steps — global-attention,
    hybrid recurrent/local-attention and pure-recurrent stacks, with
    prompts spanning partial, exact and multi-chunk lengths."""
    cfg = shrink(get_config(arch))
    params = _params(cfg)
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (3, 9, 17, 33, 1)]
    refs = [greedy_reference(params, cfg, p, 5) for p in prompts]
    chunked, engine = _serve(params, cfg, prompts, 5, n_slots=2, max_seq=64,
                             chunk=8)
    assert chunked == refs, arch
    assert engine.prefill_compilations == 1


def test_chunked_matches_monolithic_paged(greedy_reference):
    """Chunks of 16 over pages of 8: a chunk fills two pages at once."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (5, 21, 12)]
    refs = [greedy_reference(params, cfg, p, 4) for p in prompts]
    chunked, _ = _serve(params, cfg, prompts, 4, n_slots=2, max_seq=64,
                        chunk=16, page_size=8)
    assert chunked == refs


def test_chunked_pallas_matches_xla():
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (6, 19)]
    xla, _ = _serve(params, cfg, prompts, 4, n_slots=2, max_seq=32, chunk=8)
    pallas, _ = _serve(params, cfg, prompts, 4, n_slots=2, max_seq=32,
                       chunk=8, fcfg=FamousConfig(impl="pallas"))
    assert xla == pallas


def test_long_prompt_spans_many_chunks(greedy_reference):
    """A prompt far beyond any single prefill call prefills as a sequence
    of fixed chunks and still gives the forward's tokens, token for
    token."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=100))]
    refs = [greedy_reference(params, cfg, p, 4) for p in prompts]
    chunked, engine = _serve(params, cfg, prompts, 4, n_slots=2, max_seq=128,
                             chunk=16)
    assert chunked == refs
    assert engine.prefill_compilations == 1  # 7 chunk calls, one executable


@pytest.mark.parametrize("arch", ["qwen2-7b", "recurrentgemma-2b",
                                  "rwkv6-1.6b"])
def test_one_prefill_executable_for_any_prompt_mix(arch):
    """20 prompts of 20 distinct lengths — below, at and across chunk and
    page boundaries — compile exactly one prefill executable, on the
    attention, hybrid and pure-recurrent stacks."""
    cfg = shrink(get_config(arch))
    rng = np.random.default_rng(10)
    lens = [1, 2, 7, 8, 9, 15, 16, 17, 23, 24, 25, 31, 32, 33, 40, 47, 48,
            49, 55, 60]
    reqs = [Request(rid=i, tokens=list(rng.integers(0, cfg.vocab_size, n)),
                    max_new=2) for i, n in enumerate(lens)]
    engine = ServingEngine(_params(cfg), cfg, FCFG, n_slots=4, max_seq=64,
                           chunk=8)
    done = engine.run(reqs)
    assert len(done) == 20 and all(r.error is None for r in done)
    assert engine.prefill_compilations == 1


def test_engine_refuses_a_contiguous_cache():
    """The paged pool is the only cache layout: no ``cache_kind`` builds
    it, and any other value is refused."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=32)
    assert engine.alloc.pages_per_slot == 2       # page 16 by default
    with pytest.raises(ValueError, match="cache_kind='contiguous'"):
        ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=32,
                      cache_kind="contiguous")


# ---------------------------------------------------------------------------
# per-request sampling
# ---------------------------------------------------------------------------


def test_greedy_default_unchanged(greedy_reference):
    """temperature=0 (the default) is plain argmax — identical to the
    sequential greedy reference."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(0, cfg.vocab_size, size=9))
    ref = greedy_reference(params, cfg, prompt, 5)
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=128)
    done = engine.run([Request(rid=0, tokens=prompt, max_new=5)])
    assert done[0].out == ref


def test_seeded_sampling_reproducible():
    """A seeded request samples the same tokens regardless of batch
    composition or slot placement (key = f(seed, token index) only)."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(6)
    prompt = list(rng.integers(0, cfg.vocab_size, size=9))

    def run(extra_prompts, n_slots):
        reqs = [Request(rid=0, tokens=list(prompt), max_new=6,
                        temperature=0.8, top_k=5, seed=42)]
        reqs += [Request(rid=i + 1, tokens=list(p), max_new=6)
                 for i, p in enumerate(extra_prompts)]
        engine = ServingEngine(params, cfg, FCFG, n_slots=n_slots, max_seq=64,
                               chunk=8)
        done = sorted(engine.run(reqs), key=lambda r: r.rid)
        return done[0].out

    alone = run([], 2)
    extras = [list(rng.integers(0, cfg.vocab_size, size=7)) for _ in range(3)]
    crowded = run(extras, 3)
    assert alone == crowded
    # unseeded (seed=None) requests fall back to their rid: two sampling
    # requests with the same prompt draw different noise, not N copies
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64)
    pair = engine.run([Request(rid=i, tokens=list(prompt), max_new=8,
                               temperature=2.0) for i in (0, 1)])
    pair = sorted(pair, key=lambda r: r.rid)
    assert pair[0].out != pair[1].out
    # and a seeded run is actually sampling (top_k > 1, warm temperature):
    # it may coincide with greedy on some steps but the machinery is live —
    # top_k=1 must collapse back to greedy exactly.
    greedy = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64).run(
        [Request(rid=0, tokens=list(prompt), max_new=6)])[0].out
    k1 = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64).run(
        [Request(rid=0, tokens=list(prompt), max_new=6, temperature=0.7,
                 top_k=1, seed=9)])[0].out
    assert k1 == greedy


def test_sample_tokens_topk_matches_full_sort_reference():
    """The lax.top_k thresholding path must be token-identical to the old
    full-vocab-sort sampler for every (temperature, top_k) mix."""
    from repro.serve import sampling

    def reference(logits, temperature, top_k, seed, index):
        def one(lg, t, k, s, idx):
            greedy = jnp.argmax(lg).astype(jnp.int32)
            v = lg.shape[-1]
            kth = jnp.sort(lg)[::-1][jnp.clip(k, 1, v) - 1]
            masked = jnp.where((k > 0) & (lg < kth), -jnp.inf, lg)
            key = jax.random.fold_in(jax.random.PRNGKey(s), idx)
            g = jax.random.gumbel(key, lg.shape, lg.dtype)
            sampled = jnp.argmax(masked / jnp.maximum(t, 1e-6) + g)
            return jnp.where(t > 0, sampled.astype(jnp.int32), greedy)
        return jax.vmap(one)(logits, temperature, top_k, seed, index)

    rng = np.random.default_rng(7)
    B, V = 6, 91
    logits = jnp.asarray(rng.normal(size=(B, V)).astype(np.float32))
    temps = jnp.asarray([0.0, 0.7, 1.3, 0.5, 2.0, 1.0], jnp.float32)
    topks = jnp.asarray([0, 5, 1, 20, 0, 64], jnp.int32)
    seeds = np.asarray([0, 42, 7, 9, 11, 13], np.uint32)
    idxs = jnp.asarray(rng.integers(0, 9, size=B), jnp.int32)
    want = reference(logits, temps, topks, jnp.asarray(seeds), idxs)
    for k_cap in (0, 64, 128):     # cap >= max(top_k): identical thresholds
        got = sampling.sample_tokens(logits, temps, topks,
                                     jnp.asarray(seeds), idxs, k_cap=k_cap)
        assert (np.asarray(want) == np.asarray(got)).all(), k_cap


def test_huge_rid_seed_fallback():
    """seed=None falls back to the request id; rids >= 2^31 must neither
    overflow the seed operand nor collide after uint32 folding."""
    from repro.serve.sampling import fold_seed
    assert fold_seed(42) == 42                       # identity below 2^32
    assert fold_seed(2**40 + 3) != fold_seed(2**41 + 3)
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(8)
    prompt = list(rng.integers(0, cfg.vocab_size, size=6))
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64)
    done = sorted(engine.run(
        [Request(rid=2**40 + i, tokens=list(prompt), max_new=8,
                 temperature=2.0) for i in (0, 1)]), key=lambda r: r.rid)
    assert all(r.error is None and len(r.out) == 8 for r in done)
    assert done[0].out != done[1].out    # distinct rids -> distinct noise


def test_negative_seed_folds_into_uint32():
    """A negative request id or seed folds to its 32-bit two's complement
    (down to -2^32) or, beyond that, into range; seeds >= 0 keep their
    exact streams."""
    from repro.serve.sampling import fold_seed
    for s in (0, 1, 2**31, 2**32 - 1):
        assert fold_seed(s) == s
    assert fold_seed(-1) == 2**32 - 1 and fold_seed(-5) == 2**32 - 5
    assert fold_seed(-2**32) == 0
    for s in (-2**32 - 1, -2**40 - 3, -2**70):
        assert 0 <= fold_seed(s) < 2**32
    assert fold_seed(-2**40 - 3) != fold_seed(-2**41 - 3)
    cfg = shrink(get_config("qwen2-7b"))
    engine = ServingEngine(_params(cfg), cfg, FCFG, n_slots=2, max_seq=64)
    done = sorted(engine.run(
        [Request(rid=-1 - i, tokens=[3, 1, 4, 1, 5], max_new=8,
                 temperature=2.0) for i in (0, 1)]), key=lambda r: r.rid)
    assert all(r.error is None and len(r.out) == 8 for r in done)
    assert done[0].out != done[1].out    # distinct rids -> distinct noise


def test_run_max_steps_surfaces_every_request():
    """Exhausting max_steps must return EVERY request — slot-bound
    mid-flight, preempted, and never-admitted alike — with req.error set
    instead of silently dropping them."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(9)
    reqs = [Request(rid=i, tokens=list(rng.integers(0, cfg.vocab_size, 9)),
                    max_new=4) for i in range(5)]
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64, chunk=8)
    done = engine.run(reqs, max_steps=1)
    assert sorted(r.rid for r in done) == list(range(5))
    assert all(r.error is not None and not r.done for r in done)
    assert all(r.t_done is not None for r in done)   # terminal timestamp
    assert any("mid-flight" in r.error for r in done)       # the 2 slot-bound
    assert any("never admitted" in r.error for r in done)   # the 3 queued
    # the engine is reusable afterwards: slots and queues were cleaned up
    ok = engine.run([Request(rid=9, tokens=[1, 2, 3], max_new=2)])
    assert len(ok) == 1 and ok[0].error is None and len(ok[0].out) == 2
