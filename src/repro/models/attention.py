"""Attention block: GQA dense MHA built on the FAMOUS core, with KV caching
for serving: global layers in shared page pools, sliding-window layers in
per-slot ring buffers."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import famous
from repro.core import quant as quant_lib
from repro.models import layers
from repro.models.module import ParamSpec
from repro.parallel.incontext import constrain_attn_activations


def attn_spec(cfg: ModelConfig) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    spec = {
        "wq": ParamSpec((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.attention_bias:
        spec["bq"] = ParamSpec((h, dh), ("heads", "head_dim"), init="zeros")
        spec["bk"] = ParamSpec((kv, dh), ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = ParamSpec((kv, dh), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        spec["q_norm"] = ParamSpec((dh,), (None,), init="ones")
        spec["k_norm"] = ParamSpec((dh,), (None,), init="ones")
    return spec


def ring_cache_shape(cfg: ModelConfig, batch: int, max_seq: int,
                     dtype) -> dict:
    """A sliding-window layer's per-slot ring buffer: position p of slot b
    lives at ``[b, p % ring]``, ``ring = min(window, max_seq)``."""
    ring = min(max_seq, cfg.window)
    sds = jax.ShapeDtypeStruct((batch, ring, cfg.num_kv_heads, cfg.head_dim),
                               dtype)
    return {"k": sds, "v": sds}


def make_ring_cache(cfg: ModelConfig, batch: int, max_seq: int,
                    dtype) -> dict:
    """Zero-filled :func:`ring_cache_shape` buffers."""
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  ring_cache_shape(cfg, batch, max_seq, dtype))


RING_CACHE_AXES = {"k": ("batch", None, "kv_heads", "head_dim"),
                   "v": ("batch", None, "kv_heads", "head_dim")}

# Paged pools have no slot axis and are kv-head-major —
# (kv, n_pages, page_size, dh) — so each (page_size, dh) page is one tile
# the Pallas kernels can DMA whole, and tensor parallelism shards the
# leading kv-head dim; page ids/tables are head-agnostic and the host-side
# allocator stays single-copy.  int8 pools carry fp32 scale pools
# (kv, n_pages, 1, page_size) — one lane-major scale row per page — that
# shard the same way.
PAGED_ATTN_CACHE_AXES = {"k": ("kv_heads", None, None, "head_dim"),
                         "v": ("kv_heads", None, None, "head_dim")}
PAGED_ATTN_CACHE_AXES_INT8 = {**PAGED_ATTN_CACHE_AXES,
                              "k_scale": ("kv_heads", None, None, None),
                              "v_scale": ("kv_heads", None, None, None)}


def paged_attn_cache_shape(cfg: ModelConfig, n_pages: int, page_size: int,
                           dtype, kv_dtype: str = "fp") -> dict:
    """Shared page pool for a global-attention layer: every sequence's K/V
    live in fixed-size pages addressed through a per-slot page table (no
    per-slot batch axis here — the pool is the batch).

    ``kv_dtype="int8"`` stores the pools as int8 with parallel fp32
    ``k_scale``/``v_scale`` pools of shape (kv, n_pages, 1, page_size) —
    one symmetric scale per (token, kv head), written in the same scatter
    as the page row so scale rows share the page's id/lifetime by
    construction (alloc/free/shrink/COW all stay in lockstep for free).
    """
    kv, dh = cfg.num_kv_heads, cfg.head_dim
    if kv_dtype == "int8":
        sds = jax.ShapeDtypeStruct((kv, n_pages, page_size, dh), jnp.int8)
        ssd = jax.ShapeDtypeStruct((kv, n_pages, 1, page_size), jnp.float32)
        return {"k": sds, "v": sds, "k_scale": ssd, "v_scale": ssd}
    assert kv_dtype == "fp", kv_dtype
    sds = jax.ShapeDtypeStruct((kv, n_pages, page_size, dh), dtype)
    return {"k": sds, "v": sds}


def make_paged_attn_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                          dtype, kv_dtype: str = "fp") -> dict:
    """Zero-filled :func:`paged_attn_cache_shape` pools."""
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        paged_attn_cache_shape(cfg, n_pages, page_size, dtype, kv_dtype))


def _kv_quantize(x):
    """Per-(token, kv-head) symmetric int8 over head_dim: x (..., kv, dh)
    -> (int8 (..., kv, dh), fp32 scale (..., kv))."""
    q, s = quant_lib.quantize(x, axis=-1)
    return q, s[..., 0].astype(jnp.float32)


def _paged_write(cache: dict, layer, pids, offs, k, v) -> dict:
    """Scatter per-token K/V rows (..., kv, dh) into layer ``layer`` of the
    stacked kv-head-major page pools (L, kv, n_pages, page_size, dh) at
    (pids, offs) — quantizing at write time when the pool is int8
    (``k_scale`` present).  Only the rows move: the stack is the layer
    scan's carry, updated in place."""
    # one dh-row per (kv head, token): a scatter of (kv, dh) windows makes
    # XLA give the carried stack a kv-minor layout, which every Pallas
    # call then copies whole into the layout it reads
    heads = jnp.arange(cache["k"].shape[1])
    at = (pids[..., None], offs[..., None])

    def put(pool, rows):   # rows (..., kv, dh) -> pool[layer, h, pids, offs]
        return pool.at[layer, heads, at[0], at[1]].set(rows.astype(pool.dtype))

    def put_scale(pool, s):   # s (..., kv) -> pool[layer, :, pids, 0, offs]
        # a (kv,) column per token: an element scatter gives the carried
        # scale stack a layout of its own, which every launch of the
        # chunk-prefill kernel would then copy back, layer by layer
        s, p, o = s.reshape(-1, s.shape[-1]), pids.reshape(-1), offs.reshape(-1)
        for i in range(s.shape[0]):
            pool = jax.lax.dynamic_update_slice(
                pool, s[i].reshape(1, -1, 1, 1, 1), (layer, 0, p[i], 0, o[i]))
        return pool

    if "k_scale" in cache:
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        return {"k": put(cache["k"], kq), "v": put(cache["v"], vq),
                "k_scale": put_scale(cache["k_scale"], ks),
                "v_scale": put_scale(cache["v_scale"], vs)}
    return {"k": put(cache["k"], k), "v": put(cache["v"], v)}


def _pool_scales(cache: dict) -> dict:
    """kwargs routing famous.* paged attention onto the int8 kernels."""
    if "k_scale" in cache:
        return {"k_scale": cache["k_scale"], "v_scale": cache["v_scale"]}
    return {}


def _project(p, x, cfg: ModelConfig, fcfg: famous.FamousConfig, positions):
    q, k, v = famous.qkv_projection(
        x, p["wq"], p["wk"], p["wv"], p.get("bq"), p.get("bk"), p.get("bv"),
        cfg=fcfg)
    if cfg.qk_norm:
        q = layers.rms_head_norm(q, p["q_norm"])
        k = layers.rms_head_norm(k, p["k_norm"])
    if cfg.rope:
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, positions, cfg.rope_theta)
    return constrain_attn_activations(q, k, v, cfg.num_heads)


def apply_attn(p: dict, x: jax.Array, cfg: ModelConfig,
               fcfg: famous.FamousConfig, *, window: int = 0,
               q_offset: int = 0) -> jax.Array:
    """Full-sequence attention (training / encoder / prefill compute)."""
    S = x.shape[1]
    positions = q_offset + jnp.arange(S)
    q, k, v = _project(p, x, cfg, fcfg, positions)
    out = famous.attention(q, k, v, causal=cfg.causal, window=window,
                           q_offset=q_offset, cfg=fcfg)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(out.dtype))


def apply_attn_chunk(p: dict, x: jax.Array, cache: dict, slot, offset,
                     n_valid, cfg: ModelConfig, fcfg: famous.FamousConfig):
    """Chunked prefill of a sliding-window layer for one slot of the
    *batched* ring buffers.

    x: (1, C, D) — the chunk at absolute positions [offset, offset+C)
    (``offset`` a runtime scalar); cache: {"k","v"} (n_slots, ring, kv,
    dh).  Gathers the pre-chunk ring in *position order* (positions
    offset-ring .. offset-1; ring slot = pos % ring; negative /
    not-yet-written positions are masked by attention_at_positions),
    attends over [gathered ring ‖ chunk], then writes the chunk's last
    min(n_valid, ring) real positions into the ring.  Returns
    (out (1, C, D), new cache).
    """
    C = x.shape[1]
    positions = offset + jnp.arange(C)
    q, k, v = _project(p, x, cfg, fcfg, positions)
    ring = cache["k"].shape[1]
    kv, dh = cache["k"].shape[2], cache["k"].shape[3]
    row_k = jax.lax.dynamic_slice(cache["k"], (slot, 0, 0, 0),
                                  (1, ring, kv, dh))[0]
    row_v = jax.lax.dynamic_slice(cache["v"], (slot, 0, 0, 0),
                                  (1, ring, kv, dh))[0]
    prev_pos = offset - ring + jnp.arange(ring)
    order = prev_pos % ring
    keys = jnp.concatenate([row_k[order][None].astype(k.dtype), k], axis=1)
    vals = jnp.concatenate([row_v[order][None].astype(v.dtype), v], axis=1)
    k_pos = jnp.concatenate([prev_pos, positions])
    out = famous.attention_at_positions(q, keys, vals, positions, k_pos,
                                        window=cfg.window)
    # Write only the last min(n_valid, ring) *real* chunk positions —
    # pad-tail junk must not clobber live window slots, and positions
    # older than the final ring window would alias newer ones.  Masked
    # writes are redirected to an out-of-bounds index and dropped; the
    # surviving indices are distinct, so scatter order is irrelevant.
    c_arr = jnp.arange(C)
    write_ok = (c_arr < n_valid) & (c_arr >= n_valid - ring)
    idx = jnp.where(write_ok, positions % ring, ring)
    row_k = row_k.at[idx].set(k[0].astype(row_k.dtype), mode="drop")
    row_v = row_v.at[idx].set(v[0].astype(row_v.dtype), mode="drop")
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], row_k[None],
                                          (slot, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], row_v[None],
                                          (slot, 0, 0, 0)),
    }
    o = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(out.dtype))
    return o, cache


def apply_attn_chunk_paged(p: dict, x: jax.Array, cache: dict, layer,
                           page_table, slot, offset, cfg: ModelConfig,
                           fcfg: famous.FamousConfig):
    """Chunked prefill against the shared page pool.

    x: (1, C, D); cache: {"k","v"} the layer stack's pools (L, kv,
    n_pages, page_size, dh), of which this is layer ``layer``; page_table:
    (n_slots, n_p) int32.  The chunk's K/V scatter into the slot's pages
    (position p -> page ``page_table[slot, p // ps]``, offset ``p % ps``);
    pad positions past the reserved pages land on the null page, which
    absorbs them by convention.  Returns (out, new cache).
    """
    C = x.shape[1]
    positions = offset + jnp.arange(C)
    q, k, v = _project(p, x, cfg, fcfg, positions)
    ps = cache["k"].shape[3]
    pt_row = page_table[slot]                          # (n_p,)
    pids = pt_row[positions // ps]
    offs = positions % ps
    cache = _paged_write(cache, layer, pids, offs, k[0], v[0])
    out = famous.paged_chunked_prefill_attention(q, cache["k"], cache["v"],
                                                 pt_row[None], offset, layer,
                                                 cfg=fcfg,
                                                 **_pool_scales(cache))
    o = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(out.dtype))
    return o, cache


def apply_attn_decode(p: dict, x: jax.Array, cache: dict, cache_len,
                      cfg: ModelConfig, fcfg: famous.FamousConfig):
    """One-token decode of a sliding-window layer against its per-slot ring
    buffers. x: (B, 1, D); cache_len: (B,) valid entries BEFORE this token.
    Returns (out, new_cache)."""
    positions = cache_len[:, None]  # (B, 1) absolute positions
    q, k, v = _project(p, x, cfg, fcfg, positions)
    ring = cache["k"].shape[1]
    slot = cache_len % ring

    def write(buf, new):
        return jax.vmap(
            lambda b, n, s: jax.lax.dynamic_update_slice_in_dim(b, n, s, axis=0)
        )(buf, new, slot)

    cache = {"k": write(cache["k"], k), "v": write(cache["v"], v)}
    valid = jnp.minimum(cache_len + 1, ring)
    out = famous.decode_attention(q, cache["k"], cache["v"], valid, cfg=fcfg)
    o = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(out.dtype))
    return o, cache


def apply_attn_verify_paged(p: dict, x: jax.Array, cache: dict, layer,
                            page_table, cache_len, cfg: ModelConfig,
                            fcfg: famous.FamousConfig):
    """Speculative verify against the shared page pool.  x: (B, W, D);
    cache: {"k","v"} the layer stack's pools (L, kv, n_pages, page_size,
    dh), of which this is layer ``layer``; page_table: (B, n_p) int32;
    cache_len: (B,) valid entries BEFORE the first verify token.  Position
    p of slot b scatters into page
    ``page_table[b, p // ps]`` — explicitly redirected to the null page
    when ``p // ps`` runs past the table (pad rows of a nearly-full slot;
    a clamped gather would alias a live page and corrupt it).  Rollback of
    rejected tokens is the allocator's job (``PageAllocator.shrink``
    returns the tail pages grown for them); the junk K/V they leave in the
    kept pages lies past the accepted ``cache_len``, masked by every later
    causal read and overwritten by the next verify/decode writes before it
    ever becomes visible.
    """
    B, W = x.shape[:2]
    positions = cache_len[:, None] + jnp.arange(W)      # (B, W)
    q, k, v = _project(p, x, cfg, fcfg, positions)
    ps = cache["k"].shape[3]
    n_p = page_table.shape[1]
    blk = positions // ps
    b_idx = jnp.arange(B)[:, None]
    pids = jnp.where(blk < n_p,
                     page_table[b_idx, jnp.minimum(blk, n_p - 1)], 0)
    offs = positions % ps
    cache = _paged_write(cache, layer, pids, offs, k, v)
    out = famous.paged_verify_attention(q, cache["k"], cache["v"],
                                        page_table, cache_len, layer,
                                        cfg=fcfg, **_pool_scales(cache))
    o = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(out.dtype))
    return o, cache


def apply_attn_decode_paged(p: dict, x: jax.Array, cache: dict, layer,
                            page_table, cache_len, cfg: ModelConfig,
                            fcfg: famous.FamousConfig):
    """One-token decode against the shared page pool.

    x: (B, 1, D); cache: {"k","v"} the layer stack's pools (L, kv,
    n_pages, page_size, dh), of which this is layer ``layer``;
    page_table: (B, pages_per_slot) int32; cache_len: (B,) valid entries
    BEFORE this token.  The new token's K/V scatter into page
    ``page_table[b, len // page_size]`` at offset ``len % page_size``.
    Slots may *alias* read-only prefix pages (prefix cache), but every
    write position lies past the slot's shared prefix in a private page,
    so the batched scatter never collides on a non-null page; inactive
    slots write the null page.  Returns (out, cache).
    """
    B = x.shape[0]
    positions = cache_len[:, None]
    q, k, v = _project(p, x, cfg, fcfg, positions)
    ps = cache["k"].shape[3]
    pids = page_table[jnp.arange(B), cache_len // ps]      # (B,)
    offs = cache_len % ps
    cache = _paged_write(cache, layer, pids, offs, k[:, 0], v[:, 0])
    out = famous.paged_decode_attention(q, cache["k"], cache["v"],
                                        page_table, cache_len + 1, layer,
                                        cfg=fcfg, **_pool_scales(cache))
    o = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(out.dtype))
    return o, cache
