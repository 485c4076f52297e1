"""Paged KV cache: allocator invariants, page-table kernel parity against
the gather-based reference, and paged serving token-identical to the
cache-free forward on mixed-length request batches (including slot reuse
and pool exhaustion)."""
import functools

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


@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("off", [24, 13], ids=["page_boundary", "mid_page"])
def test_paged_chunk_prefill_kernel_at_offsets(off, group):
    """The paged chunked-prefill kernel vs the gather-based oracle for a
    chunk that starts on a page boundary or mid-page (its rows then span
    three pages of 8), with one or four query heads per kv head."""
    B, C, KV, dh = 2, 8, 2, 16
    ps, n_p = 8, 8
    n_pages = 1 + B * n_p
    ks = jax.random.split(jax.random.PRNGKey(5 + off), 3)
    q = jax.random.normal(ks[0], (B, C, KV * group, dh), jnp.float32)
    kp = _stack(jax.random.normal(ks[1], (KV, n_pages, ps, dh), jnp.float32))
    vp = _stack(jax.random.normal(ks[2], (KV, n_pages, ps, dh), jnp.float32))
    pt = jnp.asarray(np.random.default_rng(off).permutation(
        np.arange(1, n_pages)).reshape(B, n_p), jnp.int32)
    from repro.kernels.decode import ops as dec_ops
    out = dec_ops.paged_chunk_prefill_attention(q, kp, vp, pt, jnp.int32(off),
                                                LAYER, interpret=True)
    want = dec_ref.paged_chunk_prefill_reference(q, kp, vp, pt,
                                                 jnp.int32(off), LAYER)
    assert bool(jnp.isfinite(out).all())
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
def test_paged_matches_contiguous_decode(impl, paged_prefill):
    """Prompts chunk-prefilled into pages scattered over a shuffled pool,
    then decoded through the page table, give the full-sequence
    (contiguous) forward's logits at every decoded position."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    B, P, ps, n_p = 2, 13, 8, 4
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, P + 4), 0,
                              cfg.vocab_size)
    full = transformer.forward(params, toks, cfg, remat=False)
    ids = np.random.default_rng(1).permutation(np.arange(1, 1 + B * n_p))
    fcfg = FamousConfig(impl=impl)
    caches, pt = paged_prefill(params, cfg, toks[:, :P], max_seq=ps * n_p,
                               page_size=ps, fcfg=fcfg,
                               page_table=jnp.asarray(ids.reshape(B, n_p),
                                                      jnp.int32))
    clen = jnp.full((B,), P, jnp.int32)
    for t in range(P, P + 4):           # the last opens a new page
        lg, caches = transformer.decode_step(params, toks[:, t], caches,
                                             clen, cfg, fcfg, page_table=pt)
        np.testing.assert_allclose(lg, full[:, t], atol=5e-4)
        clen = clen + 1


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


def _fp_pools(pools):
    """The K/V pools of a cache tree as fp, dequantized when int8."""
    if "k_scale" not in pools:
        return {name: pools[name] for name in ("k", "v")}
    return {name: pools[name].astype(jnp.float32)
            * pools[name + "_scale"].swapaxes(-1, -2)
            for name in ("k", "v")}


@pytest.mark.parametrize("step", ["decode", "chunk"])
@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_paged_step_writes_only_its_rows_into_each_layer(kv, step,
                                                         identity_layout):
    """One decode step, or one prefill chunk, on a 3-layer stack of pools
    (Pallas kernels, interpreted, over a shuffled page table): every
    (layer, kv head, page, offset) it does not write is bit-identical
    after it, and the rows it writes are the K/V the same step computes
    through XLA over fp pools holding the same history (dequantized) laid
    out under another page table (int8: within a quantization step), so a
    row written into the wrong page or layer, or a kernel that reads
    another layer's pool, fails."""
    cfg = shrink(get_config("qwen2-7b"), num_layers=3)
    params = _params(cfg)
    B, ps, n_p = 2, 4, 4
    n_pages = 1 + B * n_p
    pt = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, n_pages)).reshape(B, n_p), jnp.int32)
    ident = 1 + jnp.arange(B * n_p, dtype=jnp.int32).reshape(B, n_p)
    paged = _filled(transformer.make_caches(
        cfg, B, n_p * ps, jnp.float32, page_size=ps, n_pages=n_pages,
        kv_dtype=kv), jax.random.PRNGKey(1))
    moved = {"blocks": {"pos0": {
        name: identity_layout(pool, pt)
        for name, pool in _fp_pools(paged["blocks"]["pos0"]).items()}}}
    pcfg = FamousConfig(impl="pallas")
    if step == "decode":
        toks = jnp.asarray([3, 7], jnp.int32)
        clen = jnp.asarray([5, 10], jnp.int32)
        act = jnp.ones((B,), bool)
        _, new = transformer.decode_step(params, toks, paged, clen, cfg, pcfg,
                                         page_table=pt, active=act)
        _, ref = transformer.decode_step(params, toks, moved, clen, cfg,
                                         FCFG, page_table=ident, active=act)
        rows = [(b, int(n)) for b, n in enumerate(clen)]
    else:
        slot, offset, C = 1, 4, 8
        toks = jnp.asarray([[5, 9, 2, 11, 4, 8, 0, 0]], jnp.int32)
        args = (jnp.int32(slot), jnp.int32(offset), jnp.int32(6))
        new = transformer.prefill_chunk(params, toks, paged, *args, cfg, pcfg,
                                        page_table=pt)
        ref = transformer.prefill_chunk(params, toks, moved, *args, cfg,
                                        FCFG, page_table=ident)
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
            got = new[name][at]                            # (L, KV, dh)
            want = ref[name][:, :, int(ident[b, p // ps]), p % ps]
            if kv == "fp":
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            else:
                # in quantization steps: layer 0's input is the same on
                # both paths, so its rows round within half a step; later
                # layers' inputs drift, as the int8 step attends over the
                # rows it wrote quantized and the fp one over them exact,
                # by less than another half
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


def test_paged_engine_token_identical_mixed_lengths(greedy_reference):
    """6 mixed-length requests through 2 slots: slot reuse after retirement,
    decode-time page growth across page boundaries, length-1 admission."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (5, 9, 17, 3, 33, 1)]
    base = [greedy_reference(params, cfg, p, 6) for p in prompts]
    paged = _engine_outputs(params, cfg, prompts, 6, n_slots=2, max_seq=64,
                            page_size=8)
    assert base == paged


def test_paged_engine_pallas_kernel_path():
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 12)]
    xla = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=32,
                          page_size=8)
    pallas = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=32,
                             page_size=8, fcfg=FamousConfig(impl="pallas"))
    assert xla == pallas


def test_paged_engine_int8_kernel_path_matches_xla():
    """Both impls read the SAME quantized pages, so int8 pallas vs int8
    xla is ordinary kernel parity — greedy outputs identical."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (5, 12)]
    xla = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=32,
                          page_size=8, kv_dtype="int8")
    pallas = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=32,
                             page_size=8, kv_dtype="int8",
                             fcfg=FamousConfig(impl="pallas"))
    assert xla == pallas


def _train_chain(params, cfg):
    """``params`` taught a 32-token successor cycle by SGD, and the cycle
    unrolled: a model whose top-1 logit margins exceed int8's drift
    (random-init logits are near-ties that flip under any lossy cache)."""
    rng = np.random.default_rng(5)
    sub = np.sort(rng.choice(cfg.vocab_size, 32, replace=False))
    cyc = rng.permutation(32)
    succ = {int(sub[cyc[i]]): int(sub[cyc[(i + 1) % 32]]) for i in range(32)}
    chain = [int(sub[cyc[0]])]
    while len(chain) < 512 + 33:
        chain.append(succ[chain[-1]])
    chain = np.array(chain, np.int32)

    @jax.jit
    def sgd_step(p, batch):
        def loss_fn(p):
            logits = transformer.forward(p, batch[:, :-1], cfg, FCFG)
            lp = jax.nn.log_softmax(logits, -1)
            return -jnp.take_along_axis(lp, batch[:, 1:, None], -1).mean()

        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - b, p, g), loss

    for _ in range(120):
        offs = rng.integers(0, 512, size=8)
        params, loss = sgd_step(
            params, jnp.asarray(np.stack([chain[o:o + 33] for o in offs])))
    assert float(loss) < 0.2, f"chain model failed to train: loss {loss}"
    return params, chain


def test_int8_kv_keeps_a_trained_models_greedy_tokens(paged_prefill):
    """On a trained model, int8 KV pages serve the fp pages' greedy tokens,
    and the decode logits drift from fp's by at most 0.5, far below the
    trained model's top-1 margins."""
    cfg = shrink(get_config("qwen2-7b"))
    params, chain = _train_chain(_params(cfg), cfg)
    rng = np.random.default_rng(11)
    prompts = []
    for _ in range(6):
        n, off = int(rng.integers(4, 49)), int(rng.integers(0, 400))
        prompts.append([int(t) for t in chain[off:off + n]])
    outs = {kvd: _engine_outputs(params, cfg, prompts, 8, n_slots=4,
                                 max_seq=64, chunk=16, kv_dtype=kvd)
            for kvd in ("fp", "int8")}
    assert outs["fp"] == outs["int8"]
    # the drift, through the steps themselves: prefill all but the last
    # prompt token, then decode 8 greedy tokens
    step = jax.jit(functools.partial(transformer.decode_step, cfg=cfg,
                                     fcfg=FCFG))
    drift = 0.0
    for prompt in prompts[:3]:
        logits = {}
        for kvd in ("fp", "int8"):
            caches, pt = paged_prefill(params, cfg,
                                       jnp.asarray([prompt[:-1]], jnp.int32),
                                       max_seq=64, page_size=16, chunk=16,
                                       kv_dtype=kvd)
            tok, clen, rows = prompt[-1], len(prompt) - 1, []
            for _ in range(8):
                lg, caches = step(params, jnp.asarray([tok], jnp.int32),
                                  caches, jnp.asarray([clen], jnp.int32),
                                  page_table=pt)
                rows.append(np.asarray(lg[0]))
                tok, clen = int(np.argmax(rows[-1])), clen + 1
            logits[kvd] = np.stack(rows)
        assert (logits["fp"].argmax(-1) == logits["int8"].argmax(-1)).all()
        drift = max(drift, float(np.abs(logits["fp"] - logits["int8"]).max()))
    assert drift <= 0.5, drift


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
                           page_size=4, kv_dtype="int8")
    paged = _engine_outputs(params, cfg, prompts, 8, n_slots=2, max_seq=32,
                            page_size=4, n_pages=6, kv_dtype="int8")
    assert base == paged


def test_paged_engine_hybrid_arch(greedy_reference):
    """Hybrid recurrent/local arch: recurrent state and ring buffers keep
    their per-slot buffers beside the global layers' pool, and serving
    gives the forward's tokens."""
    cfg = shrink(get_config("recurrentgemma-2b"))
    params = _params(cfg)
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (7, 3, 11)]
    base = [greedy_reference(params, cfg, p, 4) for p in prompts]
    paged = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=64,
                            page_size=16)
    assert base == paged


def test_paged_engine_pure_recurrent_small_pool(greedy_reference):
    """A pure-recurrent arch keeps nothing in the pool, yet the allocator
    still admits by pages: a pool of 2 pages of 8 (4 fewer than a full
    one) queues and preempts requests, and serving gives the forward's
    tokens."""
    cfg = shrink(get_config("rwkv6-1.6b"))
    params = _params(cfg)
    rng = np.random.default_rng(12)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (6, 3, 9)]
    base = [greedy_reference(params, cfg, p, 4) for p in prompts]
    n_pages = 3
    assert n_pages < PagedCacheConfig.default_pool(2, 24, 8)
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=24,
                           page_size=8, n_pages=n_pages, chunk=8)
    reqs = [Request(rid=i, tokens=list(p), max_new=4)
            for i, p in enumerate(prompts)]
    done = sorted(engine.run(reqs), key=lambda r: r.rid)
    assert [r.out for r in done] == base
    assert all(r.error is None for r in done)
    # the first request's growth into its second page preempted the second
    assert engine.sched.fairness(1).get("preemptions") == 1
    engine.alloc.assert_invariants()


def test_engine_admission_control():
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    # 3-page pool (n_pages=4 incl. null): a 20-token prompt needs 3 pages of
    # 8 -> admissible; a second request then cannot be admitted.
    engine = ServingEngine(params, cfg, FCFG, n_slots=2, max_seq=32,
                           page_size=8, n_pages=4)
    engine.add_request(Request(rid=0, tokens=list(range(1, 21)), max_new=2))
    with pytest.raises(PagePoolExhausted):
        engine.add_request(Request(rid=1, tokens=list(range(1, 10)), max_new=2))
    # engine state untouched by the failed admission: slot 1 still free,
    # and the first request decodes to completion.
    assert engine.slot_req[1] is None
    done = engine.run([])
    assert len(done) == 1 and len(done[0].out) == 2
    assert engine.alloc.free_pages == 3  # retirement returned every page


def test_engine_preemption_resumes_token_identically(greedy_reference):
    """Two sequences whose decode-time growth collides on the last free
    page: the younger is preempted mid-generation, resumed after the elder
    retires, and still produces exactly the forward's tokens."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=7)) for _ in range(2)]
    base = [greedy_reference(params, cfg, p, 8) for p in prompts]
    # 5 allocatable pages of 4: both prompts admit (2 pages each), the first
    # boundary crossing takes the last page, the second forces a preemption
    paged = _engine_outputs(params, cfg, prompts, 8, n_slots=2, max_seq=32,
                            page_size=4, n_pages=6)
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
                           page_size=8, n_pages=4)
    done = sorted(engine.run([ok, huge]), key=lambda r: r.rid)
    assert len(done) == 2
    assert done[0].error is None and len(done[0].out) == 3
    assert done[1].error is not None and "pages" in done[1].error


def test_engine_oversubscribed_pool_drains_queue():
    """A pool half the full footprint still serves every request —
    admission simply waits for pages to free (the scale story: memory
    follows live tokens, not n_slots x max_seq)."""
    cfg = shrink(get_config("qwen2-7b"))
    params = _params(cfg)
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (9, 5, 13, 7)]
    # a full pool would hold 2 slots x 8 pages; give it 5 (+null)
    outs = _engine_outputs(params, cfg, prompts, 4, n_slots=2, max_seq=64,
                           page_size=8, n_pages=6)
    assert all(len(o) == 4 for o in outs)
