"""Single-token decode attention Pallas kernel (serving hot loop).

One query token per sequence attends to a (possibly ring-buffered) KV cache.
Grid: (B·KV, Skv/block_k) — key tiles stream sequentially with online
softmax; the per-kv-head group of query heads (GQA) rides along as the row
dimension of the (group, dh) query block, so one cache DMA feeds all grouped
query heads (FAMOUS's shared-K-BRAM PE grouping).

``cache_len`` masking reads a scalar-prefetched (B,) int32 operand — the
runtime-programmable "sequence length register" of the paper's µB.

Two cache layouts share the online-softmax inner loop:

  * ``decode_attention``       — per-slot (BKV, Skv, dh) caches: the
    sliding-window layers' ring buffers.
  * ``paged_decode_attention`` — the layer stack's kv-head-major page
    pools (L, KV, n_pages, page_size, dh), left in HBM; a scalar-prefetched
    layer index and per-slot page table aim the kernel's own page DMAs (no
    slice of the stack, no gather materialisation), the TPU analogue of
    FAMOUS's banked-BRAM tiling.
    Grid (B, n_blocks): a step copies one block of ``pages_per_block``
    pages of a slot, every kv head of a page in one strided DMA, into a
    double-buffered VMEM buffer, and runs each kv head's (group, block)
    score tile and PV matmul.  Blocks at or past the slot's length issue
    no copy and no compute, so the work follows the live cache, not
    ``max_seq``.  kv-head-major keeps each page a whole (page_size, dh)
    tile per head; int8 pools carry (L, KV, n_pages, 1, page_size) scale
    rows, the layer's gathered per slot before the kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_compat as pc

NEG_INF = -1e30


def online_softmax_step(s, ok, v, acc_ref, m_ref, l_ref, v_scale=None):
    """Fold one masked score tile ``s`` (rows, keys) and its value tile
    ``v`` (keys, dh) into the running (acc, max, sum) scratch.
    ``v_scale``: optional (1, keys) per-key value scales (int8 pools)."""
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    pv = p if v_scale is None else p * v_scale
    # a narrower value type (the latent kernel's bf16 rows) takes the
    # weights in its type; float32 values take them as they are
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, block_k: int, n_k: int,
                   window: int):
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (group, dh)
    k = k_ref[0].astype(jnp.float32)                  # (block_k, dh)
    v = v_ref[0].astype(jnp.float32)
    valid_len = len_ref[b]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (group, bk)
    pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = pos < valid_len
    if window:
        ok &= pos > valid_len - 1 - window
    online_softmax_step(s, ok, v, acc_ref, m_ref, l_ref)

    @pl.when(ik == n_k - 1)
    def _flush():
        o_ref[0, ...] = (acc_ref[...]
                         / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


# One grid step of the paged decode kernel holds one block of one slot: at
# most this many tokens, and at most this many bytes of K across the kv
# heads (as many again of V).  A page is copied for every kv head at once,
# one strided DMA (4 KiB per head at page 16, dh 128, bf16): copies of one
# head's page alone are too small to reach HBM bandwidth.  1 MiB is 512
# tokens of qwen2-7b (4 kv heads) and 128 of deepseek-7b (32); K and V,
# double-buffered, then hold 4 MiB of VMEM.
BLOCK_TOKENS = 512
BLOCK_BYTES = 1024 * 1024


def pages_per_block(n_p: int, page_size: int, n_kv: int, dh: int,
                    dtype) -> int:
    """Pages one grid step of the paged decode kernel walks: as many as
    fill :data:`BLOCK_TOKENS` tokens and :data:`BLOCK_BYTES` of K over the
    ``n_kv`` heads, and no more than the ``n_p`` pages of a slot's table."""
    page_bytes = n_kv * page_size * dh * jnp.dtype(dtype).itemsize
    return max(1, min(n_p, BLOCK_TOKENS // page_size,
                      BLOCK_BYTES // page_bytes))


def _live_blocks(length, block_tokens: int, n_blocks):
    """Blocks a slot of ``length`` tokens walks: at least one, so that
    every slot writes its output (a dead slot's length is 1)."""
    return jnp.clip((length + block_tokens - 1) // block_tokens, 1, n_blocks)


def _paged_decode_kernel(len_ref, pt_ref, layer_ref, q_ref, k_hbm, *refs,
                         scale: float, page_size: int, ppb: int, n_p: int,
                         quantized: bool, v_width: int):
    """One grid step (b, j): block j of slot b — pages ``[j*ppb,
    (j+1)*ppb)`` of its table, every kv head — copied page by page from
    layer ``layer_ref[0]`` of the stacked HBM pools into one of two VMEM
    buffers, then folded into each kv head's running softmax.  Blocks at
    or past the slot's length are
    skipped whole; each live step starts the copies of the next live block
    (of this slot or the next) into the other buffer before it waits on
    its own.

    ``v_width`` 0: V is its own pool, the operand after K.  ``v_width`` >
    0 (latent attention): there is one pool, V is the first ``v_width``
    columns of K's rows, and the dots take the pool's type (bf16 rows
    stay bf16, accumulation in float32)."""
    if not v_width:
        v_hbm, *refs = refs
    if quantized:
        ks_ref, vs_ref, *refs = refs
    o_ref, acc_ref, m_ref, l_ref, k_buf, *refs = refs
    if not v_width:
        v_buf, *refs = refs
    sem_ref, slot_ref = refs
    pools = ((k_hbm, k_buf),) if v_width else ((k_hbm, k_buf), (v_hbm, v_buf))
    b, j = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    n_kv, dh = q_ref.shape[1], q_ref.shape[3]
    block_tokens = ppb * page_size

    def page_copies(row, blk, slot, method):
        """Start (or wait on) the copies of block ``blk``'s live pages."""
        first = blk * ppb
        live = jnp.minimum(pl.cdiv(len_ref[row] - first * page_size,
                                   page_size), n_p - first)
        for i in range(ppb):
            @pl.when(i < live)
            def _():
                page = pt_ref[row, first + i]
                for pool, buf in pools:
                    getattr(pc.make_async_copy(pool.at[layer, :, page],
                                               buf.at[slot, i],
                                               sem_ref.at[slot]), method)()

    @pl.when((b == 0) & (j == 0))
    def _first_block():
        slot_ref[0] = 0
        page_copies(0, 0, 0, "start")

    n_live = _live_blocks(len_ref[b], block_tokens, pl.num_programs(1))

    @pl.when(j < n_live)
    def _block():
        slot = slot_ref[0]
        in_row = j + 1 < n_live
        nb = jnp.where(in_row, b, b + 1)

        @pl.when(nb < pl.num_programs(0))
        def _prefetch():
            page_copies(nb, jnp.where(in_row, j + 1, 0), 1 - slot, "start")

        slot_ref[0] = 1 - slot

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        page_copies(b, j, slot, "wait")
        # keys of this block below the length; rows past it were not
        # refilled and hold stale bits, so V's rows are zeroed as well as
        # the scores masked (a zero weight times a NaN row is NaN)
        n_ok = len_ref[b] - j * block_tokens
        row_ok = jax.lax.broadcasted_iota(
            jnp.int32, (block_tokens, 1), 0) < n_ok
        for h in range(n_kv):   # unrolled: a loop of kv heads runs slower
            q = q_ref[0, h].astype(jnp.float32) * scale     # (group, dh)
            if v_width:
                k = k_buf[slot, :, h].reshape(block_tokens, dh)
                q = q.astype(k.dtype)
                v = k_buf[slot, :, h, :, :v_width].reshape(block_tokens,
                                                            v_width)
            else:
                k = k_buf[slot, :, h].astype(jnp.float32).reshape(
                    block_tokens, dh)
                v = v_buf[slot, :, h].astype(jnp.float32).reshape(
                    block_tokens, dh)
            v = jnp.where(row_ok, v, 0.0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            ok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < n_ok
            state = (acc_ref.at[h], m_ref.at[h], l_ref.at[h])
            if quantized:
                # dequantize in VMEM: a key's fp32 scale factors out of its
                # dot product, and a value's folds into its softmax weight
                # — so the (1, block_tokens) scale rows multiply the
                # (group, block) tiles and HBM only ever streams the int8
                # bytes plus the scale rows.
                s = s * ks_ref[0, h]
                online_softmax_step(s, ok, v, *state,
                                    v_scale=jnp.where(ok[:1], vs_ref[0, h],
                                                      0.0))
            else:
                online_softmax_step(s, ok, v, *state)

        @pl.when(j == n_live - 1)
        def _flush():
            o_ref[0] = (acc_ref[...] / jnp.maximum(
                l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_decode_call(q, k_pages, v_pages, scales, page_table, cache_len,
                       layer, scale, interpret, v_width: int = 0):
    """The paged decode launch over layer ``layer`` of the stacked pools.
    ``v_pages`` None: latent attention, whose values are the first
    ``v_width`` columns of ``k_pages``' rows."""
    B, KV, group, dh = q.shape
    page_size = k_pages.shape[3]
    n_p = page_table.shape[1]
    ppb = pages_per_block(n_p, page_size, KV, dh, k_pages.dtype)
    n_blocks = pl.cdiv(n_p, ppb)
    block_tokens = ppb * page_size
    dv = v_width or dh
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    kernel = functools.partial(_paged_decode_kernel, scale=float(scale),
                               page_size=page_size, ppb=ppb, n_p=n_p,
                               quantized=bool(scales), v_width=v_width)
    slot_spec = pl.BlockSpec((1, KV, group, dh),
                             lambda b, j, lens, pt, lyr: (b, 0, 0, 0))
    out_spec = pl.BlockSpec((1, KV, group, dv),
                            lambda b, j, lens, pt, lyr: (b, 0, 0, 0))
    pools = (k_pages,) if v_width else (k_pages, v_pages)
    # A page's (1, page_size) scale row is narrower than the 128-lane tile
    # a DMA moves, so the int8 scales are gathered per slot by XLA, (B, KV,
    # 1, n_blocks * block_tokens), and reach the kernel as (1, block) rows
    # through the pipeline; a skipped block keeps the last live block's
    # index, which the pipeline does not fetch again.
    scale_spec = pl.BlockSpec(
        (1, KV, 1, block_tokens),
        lambda b, j, lens, pt, lyr: (b, 0, 0, jnp.minimum(
            j, _live_blocks(lens[b], block_tokens, n_blocks) - 1)))
    scale_rows = [_scale_rows(s, layer, page_table, n_blocks * block_tokens)
                  for s in scales]
    grid_spec = pc.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # cache_len, page_table, layer
        grid=(B, n_blocks),
        in_specs=[slot_spec] + [pl.BlockSpec(memory_space=pc.ANY)] * len(pools)
        + [scale_spec] * len(scales),
        out_specs=out_spec,
        scratch_shapes=[
            pc.VMEM((KV, group, dv), jnp.float32),
            pc.VMEM((KV, group, 1), jnp.float32),
            pc.VMEM((KV, group, 1), jnp.float32),
        ] + [pc.VMEM((2, ppb, KV, page_size, dh), p.dtype) for p in pools]
        + [
            pc.DMA_SEMAPHORES((2,)),
            pc.SMEM((1,), jnp.int32),    # the buffer the next block uses
        ],
    )
    # both dims "arbitrary": a step prefetches the next live block across
    # slots, so the grid must run in order (v5e has one core)
    return pc.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, group, dv), q.dtype),
        compiler_params=pc.compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), page_table.astype(jnp.int32),
      layer_operand(layer), q, *pools, *scale_rows)


def layer_operand(layer):
    """The layer index as the (1,) int32 operand a kernel scalar-prefetches."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _scale_rows(scale_pool, layer, page_table, width: int):
    """(L, KV, n_pages, 1, page_size) scale pools -> layer ``layer``'s
    scales of each slot in table order, (B, KV, 1, width) fp32, zero past
    the table's end.  One gather of those rows: the layer's pool is never
    sliced whole."""
    heads = jnp.arange(scale_pool.shape[1])[None, :, None]
    rows = scale_pool[layer, heads, page_table[:, None], 0]   # (B, KV, n_p, ps)
    B, KV = rows.shape[:2]
    rows = rows.astype(jnp.float32).reshape(B, KV, 1, -1)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, 0), (0, width - rows.shape[-1])))


def paged_decode_attention(q, k_pages, v_pages, page_table, cache_len,
                           layer, *, scale: float | None = None,
                           interpret: bool = False):
    """Page-table-indexed decode attention.

    q: (B, KV, group, dh); pools: the layer stack's (L, KV, n_pages,
    page_size, dh); page_table: (B, n_p) int32 page ids; cache_len: (B,)
    int32; layer: () int32, the layer of the stack read.
    Returns (B, KV, group, dh).

    The page table, lengths and layer are *scalar-prefetched*: they reach
    SMEM before the kernel body runs, and the kernel reads
    ``page_table[b, p]`` and ``layer`` to aim each page DMA and
    ``cache_len[b]`` to stop the walk — the grid program never changes
    shape when sequences grow or move, or from layer to layer, only the
    prefetched indices do (the paper's µB reprograms addresses, never
    re-synthesises).
    """
    return _paged_decode_call(q, k_pages, v_pages, (), page_table,
                              cache_len, layer, scale, interpret)


def mla_decode_attention(q, pool, page_table, cache_len, layer, *,
                         scale: float, rank: int, interpret: bool = False):
    """Paged decode of latent attention (MLA): every query head of a slot
    against one cached row per token.

    q: (B, 1, H, row) absorbed queries ``[q_nope W_UK^T; q_pe]``; pool:
    the layer stack's (L, 1, n_pages, page_size, row) latent rows
    ``[c_kv; k_pe]``, ``row`` a multiple of 128 (Mosaic copies no narrower
    page; zero-padded past the latent's own width); page_table:
    (B, n_p) int32; cache_len: (B,) int32; layer: () int32.  Scores take
    the whole row, the values its first ``rank`` columns.  Returns
    (B, 1, H, rank).

    The same block walk as :func:`paged_decode_attention` (one "kv head"
    of ``row``-wide pages, the H heads as its group), copying each page
    once for both the scores and the values."""
    return _paged_decode_call(q, pool, None, (), page_table, cache_len,
                              layer, scale, interpret, v_width=rank)


def paged_decode_attention_int8(q, k_pages, v_pages, k_scale, v_scale,
                                page_table, cache_len, layer, *,
                                scale: float | None = None,
                                interpret: bool = False):
    """Int8 paged decode attention with in-kernel dequantization.

    q: (B, KV, group, dh) fp; pools: (L, KV, n_pages, page_size, dh)
    **int8**; scale pools: (L, KV, n_pages, 1, page_size) fp32
    per-token-per-kv-head scales; page_table: (B, n_p) int32; cache_len:
    (B,) int32; layer: () int32.

    Each grid step copies the block's int8 pages plus its (1, block)
    scale rows (gathered per slot in table order) and widens to fp32 only
    in VMEM — HBM traffic per token drops from 4 B/elem to 1 B/elem +
    4 B/head (FAMOUS's 8-bit fixed-point operands, paged).
    """
    assert k_pages.dtype == jnp.int8 and v_pages.dtype == jnp.int8
    return _paged_decode_call(q, k_pages, v_pages, (k_scale, v_scale),
                              page_table, cache_len, layer, scale, interpret)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     scale: float | None = None, block_k: int = 512,
                     interpret: bool = False):
    """q: (BKV, group, dh); caches: (BKV, Skv, dh); cache_len: (BKV,) int32.
    Returns (BKV, group, dh)."""
    BKV, group, dh = q.shape
    Skv = k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    block_k = min(block_k, Skv)
    assert Skv % block_k == 0
    n_k = Skv // block_k
    kernel = functools.partial(_decode_kernel, scale=float(scale),
                               block_k=block_k, n_k=n_k, window=window)
    grid_spec = pc.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,           # cache_len
        grid=(BKV, n_k),
        in_specs=[
            pl.BlockSpec((1, group, dh), lambda b, ik, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, ik, lens: (b, ik, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, ik, lens: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, group, dh), lambda b, ik, lens: (b, 0, 0)),
        scratch_shapes=[
            pc.VMEM((group, dh), jnp.float32),
            pc.VMEM((group, 1), jnp.float32),
            pc.VMEM((group, 1), jnp.float32),
        ],
    )
    return pc.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BKV, group, dh), q.dtype),
        compiler_params=pc.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), q, k_cache, v_cache)
