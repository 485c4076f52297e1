"""Multi-head latent attention (MLA, DeepSeek-V2; arXiv:2405.04434).

Per token, with no query compression:

    q = x Wq                          (H, nope + rope) -> q_nope, q_pe
    [c_kv; k_pe] = x Wkv_a            (rank + rope);  c_kv <- RMSNorm(c_kv)
    [k_nope; v] = c_kv Wkv_b          (H, nope + v)
    q_pe, k_pe rotated by RoPE (pairs (2i, 2i+1) at frequency i, YaRN)
    scores = (q_nope . k_nope + q_pe . k_pe) * scale;  out = softmax . v Wo

The cache holds only the row ``[c_kv; k_pe]`` per token (rank + rope
wide, one row for every head).  Serving runs the *absorbed* form against
it: ``q_lat = q_nope W_UK^T`` scores against c_kv, q_pe against k_pe, and
the output is taken in latent space and lifted by ``W_UV`` then ``Wo``, so
K and V are never expanded per head.  Training (:func:`apply_mla`) runs
the plain form above; the two agree (tests/test_mla.py).

The cache is a paged pool ``{"c": (1, n_pages, page_size, width)}`` (one
"kv head", so the paged decode kernel's block walk serves it unchanged; see
kernels/decode), which the serving steps take stacked over the layers and
address by layer.  The
stored width is the row's rounded up to the 128-lane tile (576 -> 640):
Mosaic copies no narrower page.  Rows and queries carry zeros there, which
add nothing to any score.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import famous
from repro.models.module import ParamSpec

NORM_EPS = 1e-6

PAGED_CACHE_AXES = {"c": (None, None, None, None)}


LANES = 128


def stored_width(cfg: ModelConfig) -> int:
    """Width of a cached row as stored: ``latent_row`` rounded up to the
    lane tile."""
    return -(-cfg.latent_row // LANES) * LANES


def _pad_to(x, width: int):
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),))


def mla_spec(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.num_heads
    r, nope, rope, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    return {
        "wq": ParamSpec((d, h, nope + rope), ("embed", "heads", None)),
        "wkv_a": ParamSpec((d, r + rope), ("embed", None)),
        "kv_norm": ParamSpec((r,), (None,), init="ones"),
        "wkv_b": ParamSpec((r, h, nope + dv), (None, "heads", None)),
        "wo": ParamSpec((h, dv, d), ("heads", None, "embed")),
    }


# ---------------------------------------------------------------------------
# RoPE (YaRN) and the softmax scale
# ---------------------------------------------------------------------------


def inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Rotary frequencies of the ``qk_rope_head_dim`` dims (float32 (dim/2,)),
    YaRN-ramped when ``yarn_factor`` is set: extrapolated below the ramp,
    interpolated (/factor) above it."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not cfg.yarn_factor:
        return extra.astype(np.float32)
    inter = extra / cfg.yarn_factor

    def correction(rotations):
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-1/2, times YaRN's attention factor squared."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor > 1 and cfg.yarn_mscale_all_dim:
        m = 0.1 * cfg.yarn_mscale_all_dim * math.log(cfg.yarn_factor) + 1.0
        s *= m * m
    return s


def rope_pairs(x, positions, freq):
    """Rotate pairs (2i, 2i+1) of the last dim by ``positions * freq[i]``.
    x: (B, S, ..., dim); positions: (B, S) or (S,)."""
    pos = jnp.broadcast_to(positions, x.shape[:2]).astype(jnp.float32)
    ang = pos.reshape(pos.shape + (1,) * (x.ndim - 2)) * jnp.asarray(freq)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------


def _rms(x, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + NORM_EPS)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def latent_rows(p, x, positions, cfg: ModelConfig):
    """The cached rows ``[RMSNorm(c_kv); rope(k_pe); 0]`` of x (B, S, D):
    (B, S, stored width)."""
    r = cfg.kv_lora_rank
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c = _rms(kv[..., :r], p["kv_norm"])
    k_pe = rope_pairs(kv[..., r:], positions, inv_freq(cfg))
    return _pad_to(jnp.concatenate([c, k_pe], -1), stored_width(cfg))


def queries(p, x, positions, cfg: ModelConfig):
    """(q_nope (B, S, H, nope), rotated q_pe (B, S, H, rope))."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(x.dtype))
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], rope_pairs(q[..., nope:], positions, inv_freq(cfg))


def absorbed_queries(p, x, positions, cfg: ModelConfig):
    """Queries in latent space: ``[q_nope W_UK^T; q_pe; 0]`` (B, S, H,
    stored width), scoring directly against cached rows."""
    q_nope, q_pe = queries(p, x, positions, cfg)
    w_uk = p["wkv_b"][..., :cfg.qk_nope_head_dim].astype(x.dtype)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)
    return _pad_to(jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], -1),
                   stored_width(cfg))


def absorbed_output(p, o_lat, cfg: ModelConfig):
    """Latent-space attention output (B, S, H, rank) -> (B, S, D)."""
    w_uv = p["wkv_b"][..., cfg.qk_nope_head_dim:].astype(o_lat.dtype)
    o = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(o.dtype))


def latent_attention(q, rows, ok, scale: float, rank: int):
    """Absorbed attention in XLA.  q: (B, Sq, H, row); rows: (B, Skv, row);
    ok: (B, Sq, Skv) bool.  Returns the latent output (B, Sq, H, rank):
    the softmax over ``q . row`` applied to the rows' first ``rank`` dims.
    Operands stay in their type, accumulation in float32."""
    s = jnp.einsum("bqhr,bkr->bhqk", q, rows.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkr->bqhr", pr.astype(rows.dtype), rows[..., :rank],
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# training / full sequence: the plain form
# ---------------------------------------------------------------------------


def apply_mla(p, x, cfg: ModelConfig, fcfg: famous.FamousConfig, *,
              q_offset: int = 0):
    """Full-sequence causal MLA in the plain form (K, V per head)."""
    S = x.shape[1]
    positions = q_offset + jnp.arange(S)
    q_nope, q_pe = queries(p, x, positions, cfg)
    rows = latent_rows(p, x, positions, cfg)
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = jnp.einsum("bsr,rhe->bshe", rows[..., :r], p["wkv_b"].astype(x.dtype))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhe,bke->bhqk", q_pe,
                      rows[..., r:r + cfg.qk_rope_head_dim].astype(q_pe.dtype),
                      preferred_element_type=jnp.float32)) * softmax_scale(cfg)
    s = jnp.where(_causal(jnp.arange(S)[None], S)[:, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhv->bqhv", pr, v)
    del fcfg
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(out.dtype))


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def paged_cache_shape(cfg: ModelConfig, n_pages: int, page_size: int,
                      dtype) -> dict:
    return {"c": jax.ShapeDtypeStruct((1, n_pages, page_size,
                                       stored_width(cfg)), dtype)}


def _zeros(tree):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), tree)


def make_paged_cache(cfg, n_pages, page_size, dtype):
    return _zeros(paged_cache_shape(cfg, n_pages, page_size, dtype))


def _causal(q_pos, n_keys):
    """(B, Sq, n_keys) mask: key j visible to the query at q_pos iff j <= it."""
    return jnp.arange(n_keys)[None, None, :] <= q_pos[..., None]


# ---------------------------------------------------------------------------
# serving: paged latent pool
# ---------------------------------------------------------------------------


def _paged_write(pool, layer, pids, offs, rows):
    """Rows (..., row) into layer ``layer`` of the stacked (L, 1, n_pages,
    page_size, row) pools, in place."""
    return pool.at[layer, 0, pids, offs].set(rows.astype(pool.dtype))


def apply_mla_chunk_paged(p, x, cache, layer, page_table, slot, offset,
                          cfg: ModelConfig, fcfg):
    """One prefill chunk against layer ``layer`` of the stacked paged pools:
    the chunk's rows scatter into the slot's pages (pads past the reserved
    pages land on the null page), then the absorbed form attends over the
    slot's whole table, gathered (XLA) from the stack."""
    C = x.shape[1]
    positions = offset + jnp.arange(C)
    rows = latent_rows(p, x, positions, cfg)
    pool = cache["c"]
    ps = pool.shape[3]
    pt_row = page_table[slot]
    pool = _paged_write(pool, layer, pt_row[positions // ps], positions % ps,
                        rows[0])
    own = pool[layer, 0, pt_row].reshape(1, -1, pool.shape[-1])  # (1, S, row)
    q = absorbed_queries(p, x, positions, cfg)
    ok = _causal(positions[None], own.shape[1])
    o = latent_attention(q, own, ok, softmax_scale(cfg), cfg.kv_lora_rank)
    return absorbed_output(p, o, cfg), {"c": pool}


def apply_mla_decode_paged(p, x, cache, layer, page_table, cache_len,
                           cfg: ModelConfig, fcfg: famous.FamousConfig):
    """One token per slot against layer ``layer`` of the stacked paged
    pools: the new row goes to page ``page_table[b, len // ps]``;
    impl="pallas" runs the latent decode kernel over each slot's live pages
    of that layer, other impls gather the table."""
    B = x.shape[0]
    positions = cache_len[:, None]
    rows = latent_rows(p, x, positions, cfg)
    pool = cache["c"]
    ps = pool.shape[3]
    pool = _paged_write(pool, layer, page_table[jnp.arange(B), cache_len // ps],
                        cache_len % ps, rows[:, 0])
    q = absorbed_queries(p, x, positions, cfg)
    scale, r = softmax_scale(cfg), cfg.kv_lora_rank
    if fcfg.impl == "pallas":
        from repro.kernels.decode import ops as dec_ops
        o = dec_ops.mla_decode_attention(q, pool, page_table, cache_len + 1,
                                         layer, scale=scale, rank=r)
    else:
        own = pool[layer, 0, page_table].reshape(B, -1, pool.shape[-1])
        o = latent_attention(q, own, _causal(positions, own.shape[1]), scale,
                             r)
    return absorbed_output(p, o, cfg), {"c": pool}
