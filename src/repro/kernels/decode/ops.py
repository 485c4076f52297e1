"""Jitted wrappers for the decode / chunked-prefill attention kernels:
(B, S, H, dh) model layout to the kernels' GQA-flattened row layouts.

The ring-buffer wrapper (:func:`decode_attention`) flattens **kv-major** —
row ``kv * B + b``, not ``b * KV + kv`` — so the merged row dim is a
concatenation of contiguous per-kv-head blocks.  Under tensor parallelism
the caches shard over the kv-head dim; kv-major keeps each device's rows a
contiguous slab of the flattened operand (b-major would interleave shards
token-by-token), so GSPMD partitions the reshape instead of all-gathering
around it.  Row order is otherwise irrelevant: rows are independent, and
the inverse transpose restores the exact (B, S, H, dh) layout, bit for
bit.  The paged wrappers keep (B, KV, group, dh) unflattened — already
shardable — and take the layer stack's pools (L, KV, n_pages, page_size,
dh) with the layer to read as a runtime operand, so no caller slices a
layer out."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pallas_compat as pc
from repro.kernels.decode import chunk_prefill as chunk_kernels
from repro.kernels.decode import decode_attn


@functools.partial(jax.jit, static_argnames=("window", "scale", "block_k",
                                             "interpret"))
def decode_attention(q, k_cache, v_cache, cache_len, *, window=0, scale=None,
                     block_k=512, interpret=None):
    """q: (B, 1, H, dh); caches: (B, Skv, KV, dh); cache_len: (B,) int32.
    Returns (B, 1, H, dh)."""
    B, _, H, dh = q.shape
    Skv, KV = k_cache.shape[1], k_cache.shape[2]
    group = H // KV
    interpret = pc.interpret_default() if interpret is None else interpret
    # (B, 1, H, dh) -> (B, KV, group, dh) -> kv-major (KV*B, group, dh)
    qf = (q[:, 0].reshape(B, KV, group, dh).transpose(1, 0, 2, 3)
          .reshape(KV * B, group, dh))
    kf = k_cache.transpose(2, 0, 1, 3).reshape(KV * B, Skv, dh)
    vf = v_cache.transpose(2, 0, 1, 3).reshape(KV * B, Skv, dh)
    lens = jnp.tile(cache_len, KV)
    out = decode_attn.decode_attention(qf, kf, vf, lens, window=window,
                                       scale=scale, block_k=block_k,
                                       interpret=interpret)
    return (out.reshape(KV, B, group, dh).transpose(1, 0, 2, 3)
            .reshape(B, 1, H, dh))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, page_table, cache_len, layer,
                           *, scale=None, interpret=None):
    """q: (B, 1, H, dh); pools: (L, KV, n_pages, page_size, dh);
    page_table: (B, n_p) int32; cache_len: (B,) int32; layer: () int32.
    Returns (B, 1, H, dh)."""
    B, _, H, dh = q.shape
    KV = k_pages.shape[1]
    group = H // KV
    interpret = pc.interpret_default() if interpret is None else interpret
    qf = q[:, 0].reshape(B, KV, group, dh)
    out = decode_attn.paged_decode_attention(qf, k_pages, v_pages,
                                             page_table, cache_len, layer,
                                             scale=scale, interpret=interpret)
    return out.reshape(B, 1, H, dh)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def mla_decode_attention(q, pool, page_table, cache_len, layer, *, scale,
                         rank, interpret=None):
    """q: (B, 1, H, row) absorbed queries; pool: (L, 1, n_pages,
    page_size, row) latent rows; page_table: (B, n_p) int32; cache_len:
    (B,) int32; layer: () int32.  Returns the latent output (B, 1, H,
    rank)."""
    interpret = pc.interpret_default() if interpret is None else interpret
    return decode_attn.mla_decode_attention(q, pool, page_table, cache_len,
                                            layer, scale=scale, rank=rank,
                                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_int8(q, k_pages, v_pages, k_scale, v_scale,
                                page_table, cache_len, layer, *, scale=None,
                                interpret=None):
    """Int8 variant of :func:`paged_decode_attention`: pools are int8 with
    fp32 (L, KV, n_pages, 1, page_size) scale pools, dequantized
    in-kernel."""
    B, _, H, dh = q.shape
    KV = k_pages.shape[1]
    group = H // KV
    interpret = pc.interpret_default() if interpret is None else interpret
    qf = q[:, 0].reshape(B, KV, group, dh)
    out = decode_attn.paged_decode_attention_int8(
        qf, k_pages, v_pages, k_scale, v_scale, page_table, cache_len, layer,
        scale=scale, interpret=interpret)
    return out.reshape(B, 1, H, dh)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_verify_attention(q, k_pages, v_pages, page_table, cache_len, layer,
                           *, scale=None, interpret=None):
    """Paged speculative verify: q: (B, W, H, dh); pools:
    (L, KV, n_pages, page_size, dh); page_table: (B, n_p) int32; cache_len:
    (B,) int32; layer: () int32.  Returns (B, W, H, dh).  Each (slot,
    verify position) pair becomes its own row of the scalar-prefetched
    page-table decode kernel, with length ``cache_len[b] + j + 1`` (clipped
    at the table's end; the engine discards those pad rows) — only the page
    *table* is repeated per verify position (a few ints per row), never the
    pool itself."""
    B, W, H, dh = q.shape
    KV, ps = k_pages.shape[1], k_pages.shape[3]
    n_p = page_table.shape[1]
    group = H // KV
    interpret = pc.interpret_default() if interpret is None else interpret
    qf = q.reshape(B * W, KV, group, dh)
    pt = jnp.broadcast_to(page_table[:, None], (B, W, n_p)).reshape(B * W, n_p)
    lens = jnp.minimum(cache_len[:, None] + jnp.arange(W, dtype=jnp.int32)
                       + 1, n_p * ps).reshape(-1)
    out = decode_attn.paged_decode_attention(qf, k_pages, v_pages, pt, lens,
                                             layer, scale=scale,
                                             interpret=interpret)
    return out.reshape(B, W, H, dh)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_verify_attention_int8(q, k_pages, v_pages, k_scale, v_scale,
                                page_table, cache_len, layer, *, scale=None,
                                interpret=None):
    """Int8 variant of :func:`paged_verify_attention` — same page-table
    broadcast, riding the int8 paged decode kernel."""
    B, W, H, dh = q.shape
    KV, ps = k_pages.shape[1], k_pages.shape[3]
    n_p = page_table.shape[1]
    group = H // KV
    interpret = pc.interpret_default() if interpret is None else interpret
    qf = q.reshape(B * W, KV, group, dh)
    pt = jnp.broadcast_to(page_table[:, None], (B, W, n_p)).reshape(B * W, n_p)
    lens = jnp.minimum(cache_len[:, None] + jnp.arange(W, dtype=jnp.int32)
                       + 1, n_p * ps).reshape(-1)
    out = decode_attn.paged_decode_attention_int8(
        qf, k_pages, v_pages, k_scale, v_scale, pt, lens, layer,
        scale=scale, interpret=interpret)
    return out.reshape(B, W, H, dh)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_chunk_prefill_attention(q, k_pages, v_pages, page_table, q_offset,
                                  layer, *, scale=None, interpret=None):
    """q: (B, C, H, dh); pools: (L, KV, n_pages, page_size, dh); page_table:
    (B, n_p) int32; q_offset: () int32; layer: () int32.  Returns
    (B, C, H, dh)."""
    B, C, H, dh = q.shape
    KV = k_pages.shape[1]
    group = H // KV
    interpret = pc.interpret_default() if interpret is None else interpret
    qf = (q.reshape(B, C, KV, group, dh).transpose(0, 2, 3, 1, 4)
          .reshape(B, KV, group * C, dh))
    out = chunk_kernels.paged_chunk_prefill(qf, k_pages, v_pages, page_table,
                                            q_offset, layer, chunk=C,
                                            scale=scale, interpret=interpret)
    return (out.reshape(B, KV, group, C, dh).transpose(0, 3, 1, 2, 4)
            .reshape(B, C, H, dh))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_chunk_prefill_attention_int8(q, k_pages, v_pages, k_scale, v_scale,
                                       page_table, q_offset, layer, *,
                                       scale=None, interpret=None):
    """Int8 variant of :func:`paged_chunk_prefill_attention`."""
    B, C, H, dh = q.shape
    KV = k_pages.shape[1]
    group = H // KV
    interpret = pc.interpret_default() if interpret is None else interpret
    qf = (q.reshape(B, C, KV, group, dh).transpose(0, 2, 3, 1, 4)
          .reshape(B, KV, group * C, dh))
    out = chunk_kernels.paged_chunk_prefill_int8(
        qf, k_pages, v_pages, k_scale, v_scale, page_table, q_offset, layer,
        chunk=C, scale=scale, interpret=interpret)
    return (out.reshape(B, KV, group, C, dh).transpose(0, 3, 1, 2, 4)
            .reshape(B, C, H, dh))