"""Pure-jnp oracle for the decode attention kernel."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def decode_reference(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     scale: float | None = None):
    """q: (BKV, group, dh); caches: (BKV, Skv, dh); cache_len: (BKV,)."""
    BKV, group, dh = q.shape
    Skv = k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    s = jnp.einsum("bgd,bkd->bgk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(Skv)[None, :]
    ok = pos < cache_len[:, None]
    if window:
        ok &= pos > (cache_len[:, None] - 1 - window)
    s = jnp.where(ok[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bgk,bkd->bgd", p,
                      v_cache.astype(jnp.float32)).astype(q.dtype)


def gather_pages(pages, page_table, layer):
    """Flatten layer ``layer`` of a stack of kv-head-major page pools into
    per-sequence contiguous caches, in one gather of the table's pages.

    pages: (L, KV, n_pages, page_size, dh); page_table: (B, n_p) int32;
    layer: () int32.  Returns (B, n_p * page_size, KV, dh).
    """
    _, KV, _, ps, dh = pages.shape
    B, n_p = page_table.shape
    g = pages[layer, :, page_table]           # (B, n_p, KV, ps, dh)
    return g.transpose(0, 1, 3, 2, 4).reshape(B, n_p * ps, KV, dh)


def gather_pages_int8(pages, scale_pool, page_table, layer):
    """Dequantizing gather for int8 page pools (XLA oracle path).

    pages: (L, KV, n_pages, page_size, dh) int8; scale_pool: (L, KV,
    n_pages, 1, page_size) fp32 per-token-per-kv-head scales; page_table:
    (B, n_p); layer: () int32.  Returns fp32 (B, n_p * page_size, KV, dh)
    — what the Pallas int8 kernels compute tile-by-tile in VMEM,
    materialised whole.
    """
    g = gather_pages(pages, page_table, layer).astype(jnp.float32)
    s = gather_pages(scale_pool.swapaxes(-1, -2), page_table,
                     layer)                                 # (B, S, KV, 1)
    return g * s.astype(jnp.float32)


def chunk_prefill_reference(q, k_cache, v_cache, q_offset, *,
                            scale: float | None = None):
    """Dense oracle for the chunked-prefill kernels.

    q: (B, C, H, dh) at positions [q_offset, q_offset+C); caches:
    (B, Skv, KV, dh) with the chunk rows already written.  Query i sees
    cache position j iff j <= q_offset + i.  Returns (B, C, H, dh).
    """
    B, C, H, dh = q.shape
    Skv, KV = k_cache.shape[1], k_cache.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    k = jnp.repeat(k_cache, H // KV, axis=2)
    v = jnp.repeat(v_cache, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    ok = jnp.arange(Skv)[None, :] <= (q_offset + jnp.arange(C))[:, None]
    s = jnp.where(ok[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def paged_chunk_prefill_reference(q, k_pages, v_pages, page_table, q_offset,
                                  layer, *, scale: float | None = None):
    """Gather-based oracle for the paged chunked-prefill kernel."""
    k = gather_pages(k_pages, page_table, layer)
    v = gather_pages(v_pages, page_table, layer)
    return chunk_prefill_reference(q, k, v, q_offset, scale=scale)


def paged_decode_reference(q, k_pages, v_pages, page_table, cache_len, layer,
                           *, scale: float | None = None):
    """Gather-based oracle for the paged kernel.

    q: (B, KV, group, dh); pools: (L, KV, n_pages, page_size, dh);
    page_table: (B, n_p); cache_len: (B,); layer: ().  Returns
    (B, KV, group, dh).
    """
    B, KV, group, dh = q.shape
    k = gather_pages(k_pages, page_table, layer)     # (B, Skv, KV, dh)
    v = gather_pages(v_pages, page_table, layer)
    qf = q.reshape(B * KV, group, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, -1, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, -1, dh)
    lens = jnp.repeat(cache_len, KV)
    out = decode_reference(qf, kf, vf, lens, scale=scale)
    return out.reshape(B, KV, group, dh)


def paged_decode_reference_int8(q, k_pages, v_pages, k_scale, v_scale,
                                page_table, cache_len, layer, *,
                                scale: float | None = None):
    """Dequantizing-gather oracle for the int8 paged decode kernel."""
    k = gather_pages_int8(k_pages, k_scale, page_table, layer)
    v = gather_pages_int8(v_pages, v_scale, page_table, layer)
    B, KV, group, dh = q.shape
    qf = q.reshape(B * KV, group, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, -1, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, -1, dh)
    out = decode_reference(qf, kf, vf, jnp.repeat(cache_len, KV), scale=scale)
    return out.reshape(B, KV, group, dh)


def paged_chunk_prefill_reference_int8(q, k_pages, v_pages, k_scale, v_scale,
                                       page_table, q_offset, layer, *,
                                       scale: float | None = None):
    """Dequantizing-gather oracle for the int8 paged chunk-prefill kernel."""
    k = gather_pages_int8(k_pages, k_scale, page_table, layer)
    v = gather_pages_int8(v_pages, v_scale, page_table, layer)
    return chunk_prefill_reference(q, k, v, q_offset, scale=scale)


def mla_decode_reference(q, pool, page_table, cache_len, layer, *,
                         scale: float, rank: int):
    """Gather-based oracle for the latent (MLA) decode kernel.

    q: (B, 1, H, row); pool: (L, 1, n_pages, page_size, row); page_table:
    (B, n_p); cache_len: (B,); layer: ().  Returns (B, 1, H, rank) in q's
    type."""
    B = q.shape[0]
    rows = pool[layer, 0][page_table].reshape(B, -1, pool.shape[-1])
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhr,bkr->bhk", q[:, 0].astype(jnp.float32), rows) * scale
    ok = jnp.arange(rows.shape[1])[None, None, :] < cache_len[:, None, None]
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhk,bkr->bhr", p, rows[..., :rank])
    return out[:, None].astype(q.dtype)
