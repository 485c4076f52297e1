"""Chunked-prefill attention Pallas kernels (the serving prefill hot loop).

A fixed-shape chunk of C query tokens at absolute positions
``[q_offset, q_offset + C)`` attends causally to the resident prefix plus
its own chunk, already written into the slot's pages of the shared page
pool.  One executable serves every (prompt
length, chunk index) pair: the offset arrives as a runtime scalar and the
page table is scalar-prefetched, exactly like ``paged_decode_attention``
in decode_attn.py — the paper's "reprogram loop bounds, never
re-synthesise" (§IV-C) applied to prefill.

GQA rides along as in the decode kernels: the rows of the query block are
the (group, chunk-position) pairs of one kv head — row ``g * C + c`` is
query head ``g`` at chunk position ``c`` — so a single K/V tile DMA feeds
every grouped query head and every chunk position at once (FAMOUS's
shared-K-BRAM PE grouping).

Correctness-over-speed note: the grid covers every page of the slot's
table and relies on the ``k_pos <= q_pos`` mask; pages entirely beyond the
chunk contribute nothing.  Skipping them needs a dynamic grid
(offset-dependent) — one executable per offset.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_compat as pc
from repro.kernels.decode.decode_attn import (NEG_INF, layer_operand,
                                              online_softmax_step)


def _paged_chunk_prefill_kernel(off_ref, layer_ref, pt_ref, q_ref, k_ref,
                                v_ref, *refs,
                                scale: float, page_size: int, n_p: int,
                                chunk: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    del layer_ref                                     # read by index maps
    q = q_ref[0, 0].astype(jnp.float32) * scale       # (group*C, dh)
    k = k_ref[0, 0, 0].astype(jnp.float32)            # (page_size, dh)
    v = v_ref[0, 0, 0].astype(jnp.float32)
    off = off_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    k_pos = ip * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % chunk
    ok = k_pos <= off + c
    if quantized:
        # dequantize in VMEM (see decode_attn._paged_decode_kernel)
        s = s * ks_ref[0, 0, 0]
        online_softmax_step(s, ok, v, acc_ref, m_ref, l_ref,
                             v_scale=vs_ref[0, 0, 0])
    else:
        online_softmax_step(s, ok, v, acc_ref, m_ref, l_ref)

    @pl.when(ip == n_p - 1)
    def _flush():
        o_ref[0, 0, ...] = (acc_ref[...]
                            / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_chunk_prefill_call(q, k_pages, v_pages, scales, page_table,
                              q_offset, layer, chunk, scale, interpret):
    B, KV, rows, dh = q.shape
    page_size = k_pages.shape[3]
    n_p = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    assert rows % chunk == 0, (rows, chunk)
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    kernel = functools.partial(_paged_chunk_prefill_kernel,
                               scale=float(scale), page_size=page_size,
                               n_p=n_p, chunk=chunk, quantized=bool(scales))
    # a page of kv head g of the layer: the stacked pools' leading index
    # is the scalar-prefetched layer, so no launch slices the stack
    page_spec = pl.BlockSpec(
        (1, 1, 1, page_size, dh),
        lambda b, g, ip, off, lyr, pt: (lyr[0], g, pt[b, ip], 0, 0))
    scale_spec = pl.BlockSpec(
        (1, 1, 1, 1, page_size),
        lambda b, g, ip, off, lyr, pt: (lyr[0], g, pt[b, ip], 0, 0))
    grid_spec = pc.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # q_offset, layer, page_table
        grid=(B, KV, n_p),
        in_specs=[
            pl.BlockSpec((1, 1, rows, dh),
                         lambda b, g, ip, off, lyr, pt: (b, g, 0, 0)),
            page_spec, page_spec] + [scale_spec] * len(scales),
        out_specs=pl.BlockSpec((1, 1, rows, dh),
                               lambda b, g, ip, off, lyr, pt: (b, g, 0, 0)),
        scratch_shapes=[
            pc.VMEM((rows, dh), jnp.float32),
            pc.VMEM((rows, 1), jnp.float32),
            pc.VMEM((rows, 1), jnp.float32),
        ],
    )
    return pc.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, dh), q.dtype),
        compiler_params=pc.compiler_params("parallel", "parallel",
                                           "arbitrary"),
        interpret=interpret,
    )(off, layer_operand(layer), page_table.astype(jnp.int32), q, k_pages,
      v_pages, *scales)


def paged_chunk_prefill(q, k_pages, v_pages, page_table, q_offset, layer, *,
                        chunk: int, scale: float | None = None,
                        interpret: bool = False):
    """Page-table-indexed chunked-prefill attention.

    q: (B, KV, group*C, dh) with row = g*C + c; pools: the layer stack's
    (L, KV, n_pages, page_size, dh); page_table: (B, n_p) int32; q_offset:
    () int32 runtime scalar; layer: () int32, the layer of the stack read.
    Returns (B, KV, group*C, dh).  The page table, offset and layer are
    scalar-prefetched — the K/V BlockSpec index_maps read ``layer`` and
    ``page_table[b, ip]`` to aim each page DMA, so the grid program never
    changes shape when prompts grow, chunks advance or layers change.
    """
    return _paged_chunk_prefill_call(q, k_pages, v_pages, (), page_table,
                                     q_offset, layer, chunk, scale, interpret)


def paged_chunk_prefill_int8(q, k_pages, v_pages, k_scale, v_scale,
                             page_table, q_offset, layer, *, chunk: int,
                             scale: float | None = None,
                             interpret: bool = False):
    """Int8 paged chunked-prefill attention with in-kernel dequantization.

    Same contract as ``paged_chunk_prefill`` except the K/V pools are int8
    and carry fp32 per-token-per-kv-head scale pools of shape
    (L, KV, n_pages, 1, page_size); the scale rows ride the same page-table
    index_map and are applied to the score and probability tiles in VMEM.
    """
    assert k_pages.dtype == jnp.int8 and v_pages.dtype == jnp.int8
    return _paged_chunk_prefill_call(q, k_pages, v_pages, (k_scale, v_scale),
                                     page_table, q_offset, layer, chunk,
                                     scale, interpret)
