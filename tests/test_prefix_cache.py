"""Prefix caching: refcounted copy-on-write page sharing across requests.

Covers the allocator primitives (lookup/map_prefix/publish, refcounts,
cached-free LRU reclaim), warm-vs-cold token parity through the engine
(xla + pallas, greedy + seeded), refcount invariants under
retire/preempt/re-admit, reclaim under an oversubscribed pool, and the
explicit cold-prefill fallback for architectures with per-slot state."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import retrace_guard
from repro.configs.base import get_config, shrink
from repro.core.famous import FamousConfig
from repro.models import module, transformer
from repro.serve.engine import Request, ServingEngine
from repro.serve.paged import (PageAllocator, PagedCacheConfig,
                               PagePoolExhausted, block_hashes)

FCFG = FamousConfig(impl="xla")


def _params(cfg):
    return module.init_params(transformer.model_spec(cfg),
                              jax.random.PRNGKey(0), jnp.float32)


def _run(engine, prompts, rid0=0, max_new=4, **req_kw):
    reqs = [Request(rid=rid0 + i, tokens=list(p), max_new=max_new, **req_kw)
            for i, p in enumerate(prompts)]
    done = sorted(engine.run(reqs), key=lambda r: r.rid)
    assert len(done) == len(prompts)
    assert all(r.error is None for r in done), [r.error for r in done]
    return [r.out for r in done]


# ---------------------------------------------------------------------------
# allocator: refcounts, index, LRU
# ---------------------------------------------------------------------------


def test_block_hashes_are_chained():
    """Equal blocks under different prefixes must NOT collide: block j's
    hash covers blocks 0..j."""
    ps = 4
    a = block_hashes([1, 2, 3, 4, 9, 9, 9, 9], ps)
    b = block_hashes([5, 6, 7, 8, 9, 9, 9, 9], ps)
    c = block_hashes([1, 2, 3, 4, 9, 9, 9, 9, 1], ps)  # partial tail ignored
    assert len(a) == len(b) == len(c) == 2
    assert a[0] != b[0] and a[1] != b[1]       # same 2nd block, diff prefix
    assert a == c


def test_refcounts_share_and_release():
    cfg = PagedCacheConfig(page_size=4, n_pages=9)
    alloc = PageAllocator(cfg, n_slots=3, max_seq=16)
    hashes = block_hashes(list(range(8)), 4)
    alloc.grow(0, 8)                     # 2 private pages
    alloc.publish(0, hashes)
    pages = [int(p) for p in alloc.page_table[0, :2]]
    # a second slot aliases the published pages
    assert alloc.lookup(hashes) == pages
    alloc.map_prefix(1, pages)
    assert [alloc.refcount(p) for p in pages] == [2, 2]
    assert alloc.pages_shared(1) == 2 and alloc.pages_shared(0) == 0
    alloc.assert_invariants()
    # owner retires: refcount drops to 1, pages stay live for slot 1
    alloc.free(0)
    assert [alloc.refcount(p) for p in pages] == [1, 1]
    assert alloc.cached_free_pages == 0
    # last holder retires: refcount 0 -> cached-free LRU, still indexed
    alloc.free(1)
    assert [alloc.refcount(p) for p in pages] == [0, 0]
    assert alloc.cached_free_pages == 2
    assert alloc.lookup(hashes) == pages       # warm
    alloc.assert_invariants()


def test_lru_reclaim_evicts_oldest_and_unindexes():
    cfg = PagedCacheConfig(page_size=4, n_pages=5)   # 4 allocatable
    alloc = PageAllocator(cfg, n_slots=2, max_seq=16)
    h_a = block_hashes([1] * 8, 4)
    h_b = block_hashes([2] * 8, 4)
    alloc.grow(0, 8); alloc.publish(0, h_a); alloc.free(0)
    alloc.grow(0, 8); alloc.publish(0, h_b); alloc.free(0)
    assert alloc.cached_free_pages == 4 and alloc.free_pages == 4
    # allocating 3 fresh pages must reclaim from the LRU oldest-first:
    # both of A's pages (older) and one of B's go, evicting their hashes
    alloc.grow(1, 12)
    alloc.assert_invariants()
    assert alloc.lookup(h_a) == []
    assert len(alloc.lookup(h_b)) <= 1
    # and a warm cache never blocks: the pool is still fully allocatable
    alloc.free(1)
    assert alloc.free_pages == 4


def test_map_prefix_pins_pages_against_reclaim():
    cfg = PagedCacheConfig(page_size=4, n_pages=4)   # 3 allocatable
    alloc = PageAllocator(cfg, n_slots=2, max_seq=12)
    h = block_hashes([3] * 8, 4)
    alloc.grow(0, 8); alloc.publish(0, h); alloc.free(0)
    pages = alloc.lookup(h)
    alloc.map_prefix(1, pages)           # pinned: refcount 1, off the LRU
    with pytest.raises(PagePoolExhausted):
        alloc.grow(0, 8)                 # only 1 page left, needs 2
    alloc.assert_invariants()
    assert alloc.lookup(h) == pages      # the hit survived the failed grow


def test_can_admit_discounts_lru_hits():
    """Cached-free hit pages are about to be pinned by the admission — they
    cannot double as the fresh capacity the same admission needs."""
    cfg = PagedCacheConfig(page_size=4, n_pages=5)   # 4 allocatable
    alloc = PageAllocator(cfg, n_slots=2, max_seq=16)
    h = block_hashes([4] * 8, 4)
    alloc.grow(0, 8); alloc.publish(0, h); alloc.free(0)   # 2 pages -> LRU
    alloc.grow(1, 8)                                       # 2 pages live
    hits = alloc.lookup(h)
    assert len(hits) == 2 and alloc.free_pages == 2        # both on the LRU
    # 16 tokens = 4 pages: 2 hits + 2 fresh, but the only reclaimable pages
    # ARE the hits — naively `need - hits <= free_pages` would wrongly pass
    assert not alloc.can_admit(16, hits=hits)
    assert alloc.can_admit(8, hits=hits)                   # 2 hits + 0 fresh


# ---------------------------------------------------------------------------
# engine: warm == cold parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_warm_hits_token_identical_greedy(impl):
    """Shared-prefix workload served cold, then warm through the same
    engine: outputs token-identical to the uncached engine, pages
    actually aliased, executables still O(1).  Prompt lengths straddle
    page boundaries (partial last block stays private: the COW rule)."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    fcfg = FamousConfig(impl=impl)
    rng = np.random.default_rng(0)
    shared = list(rng.integers(0, cfg.vocab_size, size=19))
    prompts = [shared + list(rng.integers(0, cfg.vocab_size, size=k))
               for k in (1, 5, 13)]     # lens 20, 24, 32 over pages of 8
    cold_eng = ServingEngine(params, cfg, fcfg, n_slots=2, max_seq=64,
                             page_size=8)
    cold = _run(cold_eng, prompts)
    eng = ServingEngine(params, cfg, fcfg, n_slots=2, max_seq=64,
                        page_size=8, prefix_cache=True)
    first = _run(eng, prompts)
    hits_first = eng.prefix_hit_pages
    with retrace_guard(eng, label="warm prefix-cache run"):
        warm = _run(eng, prompts, rid0=10)
    assert cold == first == warm
    assert eng.prefix_hit_pages - hits_first >= 3 * 2  # >= 2 shared pages each
    eng.alloc.assert_invariants()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_warm_hits_token_identical_greedy_int8(impl):
    """Int8 pages republish and alias exactly: block hashes cover token
    ids (not pool bytes), and a warm hit re-reads the very int8 payload +
    scale rows the cold run wrote — so warm == cold holds token-for-token
    even though quantization is lossy vs fp.  Scale rows share the
    payload's page ids, so refcounts/reclaim need no extra bookkeeping
    (assert_invariants covers both)."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    fcfg = FamousConfig(impl=impl)
    rng = np.random.default_rng(6)
    shared = list(rng.integers(0, cfg.vocab_size, size=19))
    prompts = [shared + list(rng.integers(0, cfg.vocab_size, size=k))
               for k in (1, 5, 13)]
    cold_eng = ServingEngine(params, cfg, fcfg, n_slots=2, max_seq=64,
                             page_size=8,
                             kv_dtype="int8")
    cold = _run(cold_eng, prompts)
    eng = ServingEngine(params, cfg, fcfg, n_slots=2, max_seq=64,
                        page_size=8, prefix_cache=True,
                        kv_dtype="int8")
    first = _run(eng, prompts)
    hits_first = eng.prefix_hit_pages
    with retrace_guard(eng, label="warm int8 prefix-cache run"):
        warm = _run(eng, prompts, rid0=10)
    assert cold == first == warm
    assert eng.prefix_hit_pages - hits_first >= 3 * 2
    eng.alloc.assert_invariants()
    # the quantized caches really are quantized: int8 payload pools live
    # in the tree (scale pools ride alongside them)
    assert any(l.dtype == jnp.int8
               for l in jax.tree_util.tree_leaves(eng.caches))


def test_warm_hits_token_identical_seeded_sampling():
    """Seeded sampling is keyed by (seed, token index) only — a warm hit
    must reproduce the cold run's sampled tokens exactly."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(1)
    shared = list(rng.integers(0, cfg.vocab_size, size=17))
    prompts = [shared + list(rng.integers(0, cfg.vocab_size, size=k))
               for k in (2, 9)]
    kw = dict(temperature=0.8, top_k=5, seed=42)
    cold_eng = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64,
                             page_size=8)
    cold = _run(cold_eng, prompts, max_new=6, **kw)
    eng = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64,
                        page_size=8, prefix_cache=True)
    first = _run(eng, prompts, max_new=6, **kw)
    warm = _run(eng, prompts, rid0=10, max_new=6, **kw)
    assert cold == first == warm
    assert eng.prefix_hit_pages > 0


def test_fully_cached_prompt_skips_prefill(greedy_reference):
    """A repeated prompt whose cacheable head covers everything but the
    last token admits straight to DECODE — and the page holding position
    n-1 is still private (decode writes it)."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(2)
    prompt = [list(rng.integers(0, cfg.vocab_size, size=17))]  # target 16 = 2*8
    base = [greedy_reference(params, cfg, p, 4) for p in prompt]
    eng = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64,
                        page_size=8, prefix_cache=True)
    a = _run(eng, prompt)
    b = _run(eng, prompt, rid0=1)
    assert base == a == b
    assert eng.prefix_hit_tokens == 16    # both full blocks of the head
    f = eng.sched.fairness(1)
    assert f["cached_tokens"] == 16 and f.get("prefill_tokens", 0) == 0
    eng.alloc.assert_invariants()


# ---------------------------------------------------------------------------
# engine: refcounts under retire / preempt / re-admit, LRU under pressure
# ---------------------------------------------------------------------------


def test_refcounts_under_preempt_and_readmit(greedy_reference):
    """Decode-time growth on a tiny pool forces preemption of slots that
    hold aliased prefix pages; resume re-maps the (still-indexed) prefix
    and the whole run stays token-identical to the forward's tokens."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(3)
    shared = list(rng.integers(0, cfg.vocab_size, size=4))
    prompts = [shared + list(rng.integers(0, cfg.vocab_size, size=3))
               for _ in range(2)]
    base = [greedy_reference(params, cfg, p, 8) for p in prompts]
    # 5 allocatable pages of 4: both admit (2 pages each), growth collides
    eng = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=32,
                        page_size=4, n_pages=6, prefix_cache=True)
    w1 = _run(eng, prompts, max_new=8)
    w2 = _run(eng, prompts, rid0=10, max_new=8)
    assert base == w1 == w2
    eng.alloc.assert_invariants()
    # drained: nothing live, every allocatable page free or warm
    assert eng.alloc.free_pages == 5


def test_lru_reclaim_engine_oversubscribed(greedy_reference):
    """More distinct prefixes than the pool can keep warm: old index
    entries are reclaimed on demand and every request still completes,
    token-identically to the forward's tokens."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (9, 17, 12, 21, 8, 15)]
    base = [greedy_reference(params, cfg, p, 4) for p in prompts]
    # pool of 7 allocatable pages of 8 — fewer than the 8 block hashes the
    # six prompts publish plus live growth; the LRU must cycle
    eng = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64,
                        page_size=8, n_pages=8, prefix_cache=True)
    w1 = _run(eng, prompts)
    w2 = _run(eng, prompts, rid0=10)
    assert base == w1 == w2
    eng.alloc.assert_invariants()


def test_hybrid_arch_falls_back_to_cold_prefill(greedy_reference):
    """Per-slot recurrent/ring state is not prefix-shareable: the engine
    explicitly disables sharing (prefix_cache_active False) and serves
    every request cold — the forward's tokens, zero hits."""
    cfg = shrink(get_config("recurrentgemma-2b"))
    params = _params(cfg)
    rng = np.random.default_rng(5)
    shared = list(rng.integers(0, cfg.vocab_size, size=16))
    prompts = [shared + list(rng.integers(0, cfg.vocab_size, size=k))
               for k in (3, 7)]
    base = [greedy_reference(params, cfg, p, 4) for p in prompts]
    eng = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=64,
                        page_size=16, prefix_cache=True)
    assert not eng.prefix_cache_active and eng.prefix_shareable is False
    w1 = _run(eng, prompts)
    w2 = _run(eng, prompts, rid0=10)
    assert base == w1 == w2
    assert eng.prefix_hit_pages == 0 and eng.prefix_lookups == 0
