"""Paged KV cache: allocator invariants, page-table kernel parity against
the gather-based reference, and token-identical paged-vs-contiguous serving
on mixed-length request batches (including slot reuse and pool exhaustion)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, shrink
from repro.core import famous
from repro.core.famous import FamousConfig
from repro.kernels.decode import decode_attn, ref as dec_ref
from repro.models import module, transformer
from repro.serve.engine import Request, ServingEngine
from repro.serve.paged import (NULL_PAGE, PageAllocator, PagedCacheConfig,
                               PagePoolExhausted)

FCFG = FamousConfig(impl="xla")


def _params(cfg):
    return module.init_params(transformer.model_spec(cfg),
                              jax.random.PRNGKey(0), jnp.float32)


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_allocator_invariants():
    cfg = PagedCacheConfig(page_size=4, n_pages=9)  # 8 allocatable
    alloc = PageAllocator(cfg, n_slots=3, max_seq=16)
    alloc.grow(0, 5)   # 2 pages
    alloc.grow(1, 9)   # 3 pages
    alloc.grow(0, 7)   # still 2 pages — idempotent
    assert alloc.pages_held(0) == 2 and alloc.pages_held(1) == 3
    assert alloc.free_pages == 3
    live = [int(p) for s in (0, 1) for p in
            alloc.page_table[s, :alloc.pages_held(s)]]
    assert NULL_PAGE not in live            # null page never handed out
    assert len(set(live)) == len(live)      # no page aliased across slots
    alloc.free(0)
    assert alloc.free_pages == 5
    assert (alloc.page_table[0] == NULL_PAGE).all()


def test_allocator_exhaustion_leaves_state_untouched():
    cfg = PagedCacheConfig(page_size=4, n_pages=4)  # 3 allocatable
    alloc = PageAllocator(cfg, n_slots=2, max_seq=16)
    alloc.grow(0, 8)  # 2 pages
    table_before = alloc.page_table.copy()
    with pytest.raises(PagePoolExhausted):
        alloc.grow(1, 12)  # needs 3, only 1 free
    assert alloc.free_pages == 1
    assert (alloc.page_table == table_before).all()
    alloc.grow(1, 4)  # the last page is still allocatable


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------


# The kernels read layer LAYER of a stack of pools whose other layers hold
# NaN (int8 pools: NaN scale rows), so a kernel that reads the wrong layer
# returns NaN.
LAYER = 1


def _stack(pool, fill=jnp.nan):
    """``pool`` as layer LAYER of a 3-layer stack, the others ``fill``."""
    other = jnp.full_like(pool, fill)
    return jnp.stack([other, pool, other])


# Block-walk shapes: 16-page (512-token) blocks over a 35-page table, two
# whole blocks and a ragged third.  Lengths: one token, one page, one
# block, one past it, the whole table; then a slot aliasing the first 18
# pages of slot 4 (a shared prefix) and a dead slot (length 1, null page).
PS, DH = 32, 16
PPB = decode_attn.pages_per_block(10 ** 6, PS, 1, DH, jnp.float32)
BT = PPB * PS
N_P = 2 * PPB + 3
LENS = (1, PS, BT, BT + 1, N_P * PS, BT + PS + 5, 1)


def _block_walk_table():
    """(page table (7, N_P), pool size): slots 0-4 own distinct pages,
    slot 5 aliases slot 4's first pages, slot 6 is dead (all null)."""
    pt = np.zeros((len(LENS), N_P), np.int32)
    pt[:5] = np.random.default_rng(11).permutation(
        np.arange(1, 1 + 5 * N_P)).reshape(5, N_P)
    shared = -(-LENS[5] // PS)
    pt[5, :shared] = pt[4, :shared]
    return pt, 1 + 5 * N_P


def _decode_case(case, seed):
    """(q, page table, lengths, pool shape) of one parity case."""
    if case == "mixed":                     # one block; repeated pages
        B, KV, group, dh, ps, n_pages, n_p = 3, 2, 4, 16, 8, 17, 4
        pt = np.random.default_rng(seed).integers(1, n_pages, size=(B, n_p))
        lens = (5, 23, 32)
    else:
        KV, group = {"gqa_blocks": (2, 7), "mha_blocks": (3, 1)}[case]
        dh, ps = DH, PS
        pt, n_pages = _block_walk_table()
        lens = LENS
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (len(lens), KV, group, dh), jnp.float32)
    return (q, jnp.asarray(pt, jnp.int32), jnp.asarray(lens, jnp.int32),
            (KV, n_pages, ps, dh))


@pytest.mark.parametrize("n_kv", [2, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_block_walk_shapes_cover_a_ragged_multi_block_table(n_kv, dtype):
    """The parity cases' kernels walk 16-page blocks, two whole and a
    ragged third, whatever their kv heads and pool dtype."""
    assert decode_attn.pages_per_block(N_P, PS, n_kv, DH, dtype) == PPB
    assert PPB == 16 and N_P % PPB and N_P // PPB == 2


@pytest.mark.parametrize("case", ["mixed", "gqa_blocks", "mha_blocks"])
def test_paged_kernel_matches_gather_reference(case):
    q, pt, lens, pool = _decode_case(case, 0)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    kp = _stack(jax.random.normal(ks[0], pool, jnp.float32))
    vp = _stack(jax.random.normal(ks[1], pool, jnp.float32))
    out = decode_attn.paged_decode_attention(q, kp, vp, pt, lens, LAYER,
                                             interpret=True)
    want = dec_ref.paged_decode_reference(q, kp, vp, pt, lens, LAYER)
    np.testing.assert_allclose(out, want, atol=1e-5)


@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_paged_kernel_never_reads_skipped_pages(kv):
    """Every pool page outside the slots' live pages, and the null page,
    holds NaN (int8: NaN scales): the output still equals the reference
    on the same pools with finite pages there, and is finite, so no page
    past a slot's length reaches the softmax or the PV matmul.  A kernel
    that walks every page of the table and only masks the scores fails
    this: a zero weight times a NaN value is NaN."""
    q, pt, lens, pool = _decode_case("gqa_blocks", 2)
    q, pt, lens = q[:-1], pt[:-1], lens[:-1]          # no dead slot
    live = np.zeros(pool[1], bool)
    for row, n in zip(np.asarray(pt), np.asarray(lens)):
        live[row[:-(-n // PS)]] = True
    assert not live[NULL_PAGE] and live.sum() < pool[1] - 1
    dead = jnp.asarray(~live)[None, :, None, None]
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    if kv == "fp":
        kp, vp = (_stack(jax.random.normal(k, pool)) for k in ks)
        want = dec_ref.paged_decode_reference(q, kp, vp, pt, lens, LAYER)
        kp, vp = (jnp.where(dead, jnp.nan, x) for x in (kp, vp))
        out = decode_attn.paged_decode_attention(q, kp, vp, pt, lens, LAYER,
                                                 interpret=True)
    else:
        (_, kq, kscale), (_, vq, vscale) = (
            _quantized_pools(k, pool[1], PS, pool[0], DH) for k in ks)
        kq, vq = _stack(kq, 0), _stack(vq, 0)
        kscale, vscale = _stack(kscale), _stack(vscale)
        want = dec_ref.paged_decode_reference_int8(q, kq, vq, kscale, vscale,
                                                   pt, lens, LAYER)
        kscale, vscale = (jnp.where(dead, jnp.nan, x)
                          for x in (kscale, vscale))
        out = decode_attn.paged_decode_attention_int8(
            q, kq, vq, kscale, vscale, pt, lens, LAYER, interpret=True)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(out, want, atol=1e-5)


def test_paged_verify_kernel_matches_reference():
    """Speculative verify (W = 3) on the block walk, near ``max_seq``:
    rows of length ``cache_len + j + 1``, clipped at the table's end."""
    KV, group, W = 2, 7, 3
    pt, n_pages = _block_walk_table()
    pt = jnp.asarray(pt[:4], jnp.int32)
    cache_len = jnp.asarray([N_P * PS - 3, N_P * PS - 2, BT - 1, 0],
                            jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (4, W, KV * group, DH), jnp.float32)
    kp = _stack(jax.random.normal(ks[1], (KV, n_pages, PS, DH), jnp.float32))
    vp = _stack(jax.random.normal(ks[2], (KV, n_pages, PS, DH), jnp.float32))
    from repro.kernels.decode import ops as dec_ops
    out = dec_ops.paged_verify_attention(q, kp, vp, pt, cache_len, LAYER,
                                         interpret=True)
    want = famous.paged_verify_attention(q, kp, vp, pt, cache_len, LAYER,
                                         cfg=FCFG)
    np.testing.assert_allclose(out, want, atol=1e-5)


def test_chunk_prefill_kernel_matches_reference():
    """Contiguous chunked-prefill kernel vs the dense oracle, across
    offsets (first / middle / last chunk of a prompt)."""
    B, C, H, KV, dh = 2, 8, 4, 2, 16
    Skv = 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, C, H, dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Skv, KV, dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Skv, KV, dh), jnp.float32)
    from repro.kernels.decode import ops as dec_ops
    for off in (0, 8, 24):
        out = dec_ops.chunk_prefill_attention(q, kc, vc, jnp.int32(off),
                                              block_k=16, interpret=True)
        want = dec_ref.chunk_prefill_reference(q, kc, vc, jnp.int32(off))
        np.testing.assert_allclose(out, want, atol=1e-5)


def test_paged_chunk_prefill_kernel_matches_reference():
    """Scalar-prefetched page-table chunked-prefill kernel vs the
    gather-based oracle."""
    B, C, H, KV, dh = 2, 8, 4, 2, 16
    ps, n_p = 8, 8
    n_pages = 1 + B * n_p
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, C, H, dh), jnp.float32)
    kp = _stack(jax.random.normal(ks[1], (KV, n_pages, ps, dh), jnp.float32))
    vp = _stack(jax.random.normal(ks[2], (KV, n_pages, ps, dh), jnp.float32))
    rng = np.random.default_rng(4)
    pt = jnp.asarray(
        rng.permutation(np.arange(1, n_pages)).reshape(B, n_p), jnp.int32)
    from repro.kernels.decode import ops as dec_ops
    for off in (0, 8, 21):
        out = dec_ops.paged_chunk_prefill_attention(q, kp, vp, pt,
                                                    jnp.int32(off), LAYER,
                                                    interpret=True)
        want = dec_ref.paged_chunk_prefill_reference(q, kp, vp, pt,
                                                     jnp.int32(off), LAYER)
        np.testing.assert_allclose(out, want, atol=1e-5)


def _quantized_pools(key, n_pages, ps, KV, dh):
    """An fp pool plus its symmetric per-token-per-head int8 quantization."""
    from repro.core import quant as quant_lib
    pool = jax.random.normal(key, (KV, n_pages, ps, dh), jnp.float32)
    q, s = quant_lib.quantize(pool, axis=-1)
    return pool, q, s.swapaxes(-1, -2).astype(jnp.float32)


def _stacked_quantized_pools(key, n_pages, ps, KV, dh):
    """:func:`_quantized_pools`, each as layer LAYER of a 3-layer stack."""
    pool, q, s = _quantized_pools(key, n_pages, ps, KV, dh)
    return _stack(pool), _stack(q, 0), _stack(s)


@pytest.mark.parametrize("case", ["mixed", "gqa_blocks", "mha_blocks"])
def test_paged_int8_decode_kernel_matches_oracle(case):
    """Int8 paged decode: Pallas in-kernel dequant vs the XLA
    dequantizing-gather oracle (tight), and both vs the fp kernel on the
    pre-quantization pool (lossy but bounded drift)."""
    q, pt, lens, (KV, n_pages, ps, dh) = _decode_case(case, 7)
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    kf, kq, kscale = _stacked_quantized_pools(ks[0], n_pages, ps, KV, dh)
    vf, vq, vscale = _stacked_quantized_pools(ks[1], n_pages, ps, KV, dh)
    out = decode_attn.paged_decode_attention_int8(q, kq, vq, kscale, vscale,
                                                  pt, lens, LAYER,
                                                  interpret=True)
    want = dec_ref.paged_decode_reference_int8(q, kq, vq, kscale, vscale,
                                               pt, lens, LAYER)
    np.testing.assert_allclose(out, want, atol=1e-5)
    fp = decode_attn.paged_decode_attention(q, kf, vf, pt, lens, LAYER,
                                            interpret=True)
    drift = float(jnp.abs(out - fp).max())
    assert 0 < drift < 0.05, drift


def test_paged_int8_chunk_prefill_kernel_matches_oracle():
    B, C, H, KV, dh = 2, 8, 4, 2, 16
    ps, n_p = 8, 8
    n_pages = 1 + B * n_p
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, C, H, dh), jnp.float32)
    _, kq, kscale = _stacked_quantized_pools(ks[1], n_pages, ps, KV, dh)
    _, vq, vscale = _stacked_quantized_pools(ks[2], n_pages, ps, KV, dh)
    rng = np.random.default_rng(8)
    pt = jnp.asarray(
        rng.permutation(np.arange(1, n_pages)).reshape(B, n_p), jnp.int32)
    from repro.kernels.decode import ops as dec_ops
    for off in (0, 8, 21):
        out = dec_ops.paged_chunk_prefill_attention_int8(
            q, kq, vq, kscale, vscale, pt, jnp.int32(off), LAYER,
            interpret=True)
        want = dec_ref.paged_chunk_prefill_reference_int8(
            q, kq, vq, kscale, vscale, pt, jnp.int32(off), LAYER)
        np.testing.assert_allclose(out, want, atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_matches_contiguous_decode(impl):
    """Scattering a contiguous cache into pages and reading it back through
    the page table reproduces dense decode attention exactly."""
    B, KV, H, dh = 2, 2, 4, 16
    ps, n_p = 8, 4
    Smax = ps * n_p
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Smax, KV, dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Smax, KV, dh), jnp.float32)
    lens = jnp.asarray([7, 29], jnp.int32)
    # lay each sequence's pages out in a shuffled shared pool
    rng = np.random.default_rng(1)
    ids = rng.permutation(np.arange(1, 1 + B * n_p)).reshape(B, n_p)
    n_pages = 1 + B * n_p
    kp = jnp.zeros((KV, n_pages, ps, dh), jnp.float32)
    vp = jnp.zeros((KV, n_pages, ps, dh), jnp.float32)
    kp = kp.at[:, ids].set(kc.reshape(B, n_p, ps, KV, dh)
                           .transpose(3, 0, 1, 2, 4))
    vp = vp.at[:, ids].set(vc.reshape(B, n_p, ps, KV, dh)
                           .transpose(3, 0, 1, 2, 4))
    pt = jnp.asarray(ids, jnp.int32)
    fcfg = FamousConfig(impl=impl)
    paged = famous.paged_decode_attention(q, _stack(kp), _stack(vp), pt, lens,
                                          LAYER, cfg=fcfg)
    dense = famous.decode_attention(q, kc, vc, lens, cfg=fcfg)
    np.testing.assert_allclose(paged, dense, atol=1e-5)


# ---------------------------------------------------------------------------
# the layer scan's in-place writes into the stacked pools
# ---------------------------------------------------------------------------


def _filled(tree, key):
    """Every pool of a cache tree filled at random: fp rows normal, int8
    rows uniform over [-127, 127], scale rows in [0.01, 0.05]."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))

    def fill(a, k):
        if a.dtype == jnp.int8:
            return jax.random.randint(k, a.shape, -127, 128).astype(jnp.int8)
        if a.ndim == 5 and a.shape[-2] == 1:          # scale rows
            return jax.random.uniform(k, a.shape, a.dtype, 0.01, 0.05)
        return jax.random.normal(k, a.shape, a.dtype)

    return treedef.unflatten([fill(a, k) for a, k in zip(leaves, keys)])


def _contiguous_history(pools, pt):
    """The contiguous caches (layers, B, n_p * ps, KV, dh) holding each
    slot's pages of each layer of ``pools`` in table order, dequantized."""
    n = pools["k"].shape[0]
    if "k_scale" in pools:
        return {name: jnp.stack([dec_ref.gather_pages_int8(
            pools[name], pools[name + "_scale"], pt, l) for l in range(n)])
            for name in ("k", "v")}
    return {name: jnp.stack([dec_ref.gather_pages(pools[name], pt, l)
                             for l in range(n)]) for name in ("k", "v")}


@pytest.mark.parametrize("step", ["decode", "chunk"])
@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_paged_step_writes_only_its_rows_into_each_layer(kv, step):
    """One decode step, or one prefill chunk, on a 3-layer stack of pools
    (Pallas kernels, interpreted): every (layer, kv head, page, offset) it
    does not write is bit-identical after it, and the rows it writes are
    the K/V the same step computes layer by layer through contiguous
    per-slot caches holding the same history (int8: within a quantization
    step), so a row written into the wrong layer, or a layer that reads
    another's pool, fails."""
    cfg = shrink(get_config("qwen2-7b"), num_layers=3)
    params = _params(cfg)
    B, ps, n_p = 2, 4, 4
    n_pages = 1 + B * n_p
    pt = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, n_pages)).reshape(B, n_p), jnp.int32)
    paged = _filled(transformer.make_caches(
        cfg, B, n_p * ps, jnp.float32, cache_kind="paged", page_size=ps,
        n_pages=n_pages, kv_dtype=kv), jax.random.PRNGKey(1))
    contig = {"blocks": {"pos0": _contiguous_history(
        paged["blocks"]["pos0"], pt)}}
    pcfg = FamousConfig(impl="pallas")
    if step == "decode":
        toks = jnp.asarray([3, 7], jnp.int32)
        clen = jnp.asarray([5, 10], jnp.int32)
        act = jnp.ones((B,), bool)
        _, new = transformer.decode_step(params, toks, paged, clen, cfg, pcfg,
                                         page_table=pt, active=act)
        _, ref = transformer.decode_step(params, toks, contig, clen, cfg,
                                         FCFG, active=act)
        rows = [(b, int(n)) for b, n in enumerate(clen)]
    else:
        slot, offset, C = 1, 4, 8
        toks = jnp.asarray([[5, 9, 2, 11, 4, 8, 0, 0]], jnp.int32)
        args = (jnp.int32(slot), jnp.int32(offset), jnp.int32(6))
        new = transformer.prefill_chunk(params, toks, paged, *args, cfg, pcfg,
                                        page_table=pt)
        ref = transformer.prefill_chunk(params, toks, contig, *args, cfg,
                                        FCFG)
        rows = [(slot, p) for p in range(offset, offset + C)]
    old, new, ref = (c["blocks"]["pos0"] for c in (paged, new, ref))
    written = np.zeros(old["k"].shape[:4], bool)       # (L, KV, n_pages, ps)
    for b, p in rows:
        written[:, :, int(pt[b, p // ps]), p % ps] = True
    for name, pool in old.items():
        mask = written[..., None, :] if name.endswith("scale") else written
        np.testing.assert_array_equal(np.asarray(new[name])[~mask],
                                      np.asarray(pool)[~mask])
    for b, p in rows:
        at = (slice(None), slice(None), int(pt[b, p // ps]), p % ps)
        for name in ("k", "v"):
            got, want = new[name][at], ref[name][:, b, p]   # (L, KV, dh)
            if kv == "fp":
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            else:
                # in quantization steps: layer 0's input is the same on
                # both paths, so its rows round within half a step; later
                # layers' inputs drift, as the paged step attends over the
                # rows it wrote quantized and the contiguous one over them
                # exact, by less than another half
                scale = new[name + "_scale"][at[:3] + (0, p % ps)]
                steps = jnp.abs(got * scale[..., None] - want) / scale[..., None]
                assert float(steps[0].max()) <= 0.5 + 1e-4
                assert float(steps.max()) <= 1.0


# ---------------------------------------------------------------------------
# engine parity
# ---------------------------------------------------------------------------


def _engine_outputs(params, cfg, prompts, max_new, **engine_kw):
    engine = ServingEngine(params, cfg, engine_kw.pop("fcfg", FCFG),
                           **engine_kw)
    reqs = [Request(rid=i, tokens=list(p), max_new=max_new)
            for i, p in enumerate(prompts)]
    done = sorted(engine.run(reqs), key=lambda r: r.rid)
    assert len(done) == len(prompts)
    return [r.out for r in done]


def test_paged_engine_token_identical_mixed_lengths():
    """6 mixed-length requests through 2 slots: slot reuse after retirement,
    decode-time page growth across page boundaries, length-1 admission."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (5, 9, 17, 3, 33, 1)]
    base = _engine_outputs(params, cfg, prompts, 6, n_slots=2, max_seq=64)
    paged = _engine_outputs(params, cfg, prompts, 6, n_slots=2, max_seq=64,
                            cache_kind="paged", page_size=8)
    assert base == paged


def test_paged_engine_pallas_kernel_path():
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 12)]
    xla = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=32,
                          cache_kind="paged", page_size=8)
    pallas = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=32,
                             cache_kind="paged", page_size=8,
                             fcfg=FamousConfig(impl="pallas"))
    assert xla == pallas


def test_paged_engine_int8_kernel_path_matches_xla():
    """Both impls read the SAME quantized pages, so int8 pallas vs int8
    xla is ordinary kernel parity — greedy outputs identical."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 12)]
    xla = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=32,
                          cache_kind="paged", page_size=8, kv_dtype="int8")
    pallas = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=32,
                             cache_kind="paged", page_size=8,
                             kv_dtype="int8",
                             fcfg=FamousConfig(impl="pallas"))
    assert xla == pallas


def test_kv_int8_requires_paged_cache():
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    with pytest.raises(AssertionError):
        ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=32,
                      kv_dtype="int8")          # contiguous cache


def test_int8_preemption_keeps_scales_in_lockstep():
    """Preempt/resume on a tiny int8 pool: scale rows ride the same page
    ids as their payload, so a preempted-and-resumed request reproduces
    the un-contended int8 engine's tokens exactly and the drained pool
    holds no stale scale state."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(9)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=7)) for _ in range(2)]
    base = _engine_outputs(params, cfg, prompts, 8, n_slots=2, max_seq=32,
                           cache_kind="paged", page_size=4,
                           kv_dtype="int8")
    paged = _engine_outputs(params, cfg, prompts, 8, n_slots=2, max_seq=32,
                            cache_kind="paged", page_size=4, n_pages=6,
                            kv_dtype="int8")
    assert base == paged


def test_paged_engine_hybrid_arch():
    """Hybrid recurrent/local arch under cache_kind="paged": recurrent state
    and ring buffers keep their per-slot buffers, outputs unchanged."""
    cfg = shrink(get_config("recurrentgemma-2b"))
    params = _params(cfg)
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (7, 3, 11)]
    base = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=64)
    paged = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=64,
                            cache_kind="paged", page_size=16)
    assert base == paged


def test_engine_admission_control():
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    # 3-page pool (n_pages=4 incl. null): a 20-token prompt needs 3 pages of
    # 8 -> admissible; a second request then cannot be admitted.
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=32,
                           cache_kind="paged", page_size=8, n_pages=4)
    engine.add_request(Request(rid=0, tokens=list(range(1, 21)), max_new=2))
    with pytest.raises(PagePoolExhausted):
        engine.add_request(Request(rid=1, tokens=list(range(1, 10)), max_new=2))
    # engine state untouched by the failed admission: slot 1 still free,
    # and the first request decodes to completion.
    assert engine.slot_req[1] is None
    done = engine.run([])
    assert len(done) == 1 and len(done[0].out) == 2
    assert engine.alloc.free_pages == 3  # retirement returned every page


def test_engine_preemption_resumes_token_identically():
    """Two sequences whose decode-time growth collides on the last free
    page: the younger is preempted mid-generation, resumed after the elder
    retires, and still produces exactly the contiguous-engine tokens."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=7)) for _ in range(2)]
    base = _engine_outputs(params, cfg, prompts, 8, n_slots=2, max_seq=32)
    # 5 allocatable pages of 4: both prompts admit (2 pages each), the first
    # boundary crossing takes the last page, the second forces a preemption
    paged = _engine_outputs(params, cfg, prompts, 8, n_slots=2, max_seq=32,
                            cache_kind="paged", page_size=4, n_pages=6)
    assert base == paged


def test_engine_impossible_request_fails_cleanly():
    """run() returns impossible requests with req.error set instead of
    discarding completed work."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(6)
    ok = Request(rid=0, tokens=list(rng.integers(0, cfg.vocab_size, size=5)),
                 max_new=3)
    huge = Request(rid=1, tokens=list(rng.integers(0, cfg.vocab_size, size=30)),
                   max_new=3)  # needs 4 pages, pool only has 3
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=32,
                           cache_kind="paged", page_size=8, n_pages=4)
    done = sorted(engine.run([ok, huge]), key=lambda r: r.rid)
    assert len(done) == 2
    assert done[0].error is None and len(done[0].out) == 3
    assert done[1].error is not None and "pages" in done[1].error


def test_engine_oversubscribed_pool_drains_queue():
    """A pool half the contiguous footprint still serves every request —
    admission simply waits for pages to free (the scale story: memory
    follows live tokens, not n_slots x max_seq)."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (9, 5, 13, 7)]
    # contiguous-equivalent would need 2 slots x 8 pages; give it 5 (+null)
    outs = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=64,
                           cache_kind="paged", page_size=8, n_pages=6)
    assert all(len(o) == 4 for o in outs)
