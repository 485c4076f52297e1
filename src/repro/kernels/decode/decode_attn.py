"""Single-token decode attention Pallas kernel (serving hot loop).

One query token per sequence attends to a (possibly ring-buffered) KV cache.
Grid: (B·KV, Skv/block_k) — key tiles stream sequentially with online
softmax; the per-kv-head group of query heads (GQA) rides along as the row
dimension of the (group, dh) query block, so one cache DMA feeds all grouped
query heads (FAMOUS's shared-K-BRAM PE grouping).

``cache_len`` masking reads a scalar-prefetched (B,) int32 operand — the
runtime-programmable "sequence length register" of the paper's µB.

Two cache layouts share the online-softmax inner loop:

  * ``decode_attention``       — contiguous (BKV, Skv, dh) per-slot caches.
  * ``paged_decode_attention`` — a shared kv-head-major
    (KV, n_pages, page_size, dh) page pool; a scalar-prefetched per-slot
    page table drives the K/V BlockSpec index_map, so each key tile is
    DMA'd straight from its page (no gather materialisation), the TPU
    analogue of FAMOUS's banked-BRAM tiling.  kv-head-major keeps every
    block's last two dims a whole (page_size, dh) page, the tiling Mosaic
    requires; int8 pools carry (KV, n_pages, 1, page_size) scale rows for
    the same reason.
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
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
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


def _paged_decode_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, *refs,
                         scale: float, page_size: int, n_p: int,
                         quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (group, dh)
    k = k_ref[0, 0].astype(jnp.float32)               # (page_size, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    valid_len = len_ref[b]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (group, ps)
    pos = ip * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = pos < valid_len
    if quantized:
        # dequantize in VMEM: a key's fp32 scale factors out of its dot
        # product, and a value's folds into its softmax weight — so the
        # (1, page_size) scale rows multiply the (group, page_size) tiles
        # and HBM only ever streams the int8 bytes plus one scale row.
        s = s * ks_ref[0, 0]
        online_softmax_step(s, ok, v, acc_ref, m_ref, l_ref,
                             v_scale=vs_ref[0, 0])
    else:
        online_softmax_step(s, ok, v, acc_ref, m_ref, l_ref)

    @pl.when(ip == n_p - 1)
    def _flush():
        o_ref[0, 0, ...] = (acc_ref[...]
                            / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_decode_call(q, k_pages, v_pages, scales, page_table, cache_len,
                       scale, interpret):
    B, KV, group, dh = q.shape
    page_size = k_pages.shape[2]
    n_p = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    kernel = functools.partial(_paged_decode_kernel, scale=float(scale),
                               page_size=page_size, n_p=n_p,
                               quantized=bool(scales))
    page_spec = pl.BlockSpec((1, 1, page_size, dh),
                             lambda b, g, ip, lens, pt: (g, pt[b, ip], 0, 0))
    scale_spec = pl.BlockSpec((1, 1, 1, page_size),
                              lambda b, g, ip, lens, pt: (g, pt[b, ip], 0, 0))
    grid_spec = pc.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # cache_len, page_table
        grid=(B, KV, n_p),
        in_specs=[
            pl.BlockSpec((1, 1, group, dh),
                         lambda b, g, ip, lens, pt: (b, g, 0, 0)),
            page_spec, page_spec] + [scale_spec] * len(scales),
        out_specs=pl.BlockSpec((1, 1, group, dh),
                               lambda b, g, ip, lens, pt: (b, g, 0, 0)),
        scratch_shapes=[
            pc.VMEM((group, dh), jnp.float32),
            pc.VMEM((group, 1), jnp.float32),
            pc.VMEM((group, 1), jnp.float32),
        ],
    )
    return pc.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, group, dh), q.dtype),
        compiler_params=pc.compiler_params("parallel", "parallel",
                                           "arbitrary"),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), page_table.astype(jnp.int32),
      q, k_pages, v_pages, *(s.astype(jnp.float32) for s in scales))


def paged_decode_attention(q, k_pages, v_pages, page_table, cache_len, *,
                           scale: float | None = None,
                           interpret: bool = False):
    """Page-table-indexed decode attention.

    q: (B, KV, group, dh); pools: (KV, n_pages, page_size, dh);
    page_table: (B, n_p) int32 page ids; cache_len: (B,) int32.
    Returns (B, KV, group, dh).

    The page table and lengths are *scalar-prefetched*: they reach SMEM
    before the kernel body runs, and the K/V BlockSpec index_maps read
    ``page_table[b, ip]`` to aim each page DMA — the grid program never
    changes shape when sequences grow or move, only the prefetched indices
    do (the paper's µB reprograms addresses, never re-synthesises).
    """
    return _paged_decode_call(q, k_pages, v_pages, (), page_table,
                              cache_len, scale, interpret)


def paged_decode_attention_int8(q, k_pages, v_pages, k_scale, v_scale,
                                page_table, cache_len, *,
                                scale: float | None = None,
                                interpret: bool = False):
    """Int8 paged decode attention with in-kernel dequantization.

    q: (B, KV, group, dh) fp; pools: (KV, n_pages, page_size, dh) **int8**;
    scale pools: (KV, n_pages, 1, page_size) fp32 per-token-per-kv-head
    scales; page_table: (B, n_p) int32; cache_len: (B,) int32.

    The scale pools ride the same page-table index_map as K/V, so each grid
    step DMAs one int8 page plus its (1, page_size) scale row and widens
    to fp32 only in VMEM — HBM traffic per token drops from 4 B/elem to
    1 B/elem + 4 B/head (FAMOUS's 8-bit fixed-point operands, paged).
    """
    assert k_pages.dtype == jnp.int8 and v_pages.dtype == jnp.int8
    return _paged_decode_call(q, k_pages, v_pages, (k_scale, v_scale),
                              page_table, cache_len, scale, interpret)


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
