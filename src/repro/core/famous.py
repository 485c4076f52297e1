"""FAMOUS core: flexible, tiled, dense multi-head attention (the paper's
contribution), adapted from UltraScale+ FPGAs to TPU.

The paper decomposes MHA into three processing modules —

  * ``QKV_PM`` :  Q/K/V = X·W{q,k,v} + B{q,k,v}   (Algorithm 1, column-tiled)
  * ``QK_PM``  :  S = softmax(Q·Kᵀ / √d_k)        (Algorithm 2 + LUT softmax)
  * ``SV_PM``  :  A = S·V                          (Algorithm 3)

— each with its own PE-array geometry, with the weight matrices tiled along
the *reduction* dimension in tiles of size ``TS`` so one tile fits in BRAM.

This module provides three interchangeable implementations of the same math:

  impl="reference"  paper-faithful: explicit TS-tile loop with partial-sum
                    accumulation (Alg 1) and a fully materialised S matrix
                    (the FPGA stores S in BRAM; feasible at the paper's SL=64).
                    This is the reproduction baseline.
  impl="xla"        TPU-native XLA path: fused projections and an *online*
                    (running max/sum) softmax over key tiles — identical math,
                    same tiling structure, but S is never materialised.  Used
                    by training, serving and the multi-pod dry-run.
  impl="pallas"     hand-written Pallas TPU kernels (kernels/qkv, kernels/
                    attention) with BlockSpec VMEM tiling — the TS analogue is
                    the (block_q, block_k, block_d) triple.  Trainable: the
                    attention kernel carries a flash custom-VJP whose dq and
                    dk/dv passes are themselves Pallas kernels (blockwise
                    recompute from the saved LSE, mirroring _flash_bwd_rule
                    below), and the QKV matmul kernel differentiates through
                    itself.  Validated in interpret mode on CPU; selected on
                    real TPU backends.

GQA extends the paper (which is pure MHA): K/V heads are broadcast to query
heads inside the QK/SV modules, mirroring how FAMOUS shares K BRAMs across PE
groups.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import quant as quant_lib


@dataclasses.dataclass(frozen=True)
class FamousConfig:
    """Tiling + dispatch knobs (the TS analogue and runtime maxima)."""

    tile_d: int = 512       # TS for the QKV_PM reduction dim (d_model)
    tile_q: int = 512       # query-tile rows held on-chip in QK/SV modules
    tile_k: int = 512       # key-tile columns streamed through QK/SV modules
    impl: str = "xla"       # reference | xla | pallas
    quant: str = "none"     # none | int8  (paper uses 8-bit fixed point)
    # Runtime-programmable maxima (paper §IV-C: h/d_model/SL adjustable below
    # synthesis-time maxima without re-synthesis).
    max_heads: int = 0
    max_seq: int = 0
    max_d_model: int = 0
    # Device mesh the Pallas kernels run under (serving TP), and the mesh
    # axis (or axes) holding attention heads and kv heads there, as the
    # serving engine's sharding rules place them: GSPMD cannot partition a
    # Mosaic call, so each launch is shard_mapped over that axis (see
    # _per_device).  mesh None: one device.
    mesh: Any = None
    head_axis: Any = None


# Where each kernel wrapper's operands keep their heads (or kv heads): the
# dim index, or None for operands every device holds whole.
HEADS = 2        # (B, S, H|KV, dh) activations and ring caches
POOL = 1         # (L, KV, n_pages, ...) the layer stack's page and scale pools
REP = None       # page tables, lengths, offsets, layer indices, x


def _per_device(cfg: FamousConfig, fn, in_dims, out_dims, *args):
    """Launch a Pallas kernel wrapper once per device of ``cfg.mesh``.

    ``in_dims``/``out_dims`` name each operand's and output's head dim.
    Those dims split over ``cfg.head_axis``, where the params and caches
    already live, so each device runs the kernel on its own heads and its
    own slice of the page pool with no collective.  When a head count does
    not divide the axis (the rules then replicate it), or the heads are
    not split at all, every device runs the whole kernel on replicated
    operands."""
    mesh, axis = cfg.mesh, cfg.head_axis
    if mesh is None or mesh.size == 1:
        return fn(*args)
    names = () if axis is None else axis if isinstance(axis, tuple) else (axis,)
    tp = math.prod(mesh.shape[a] for a in names)
    split = tp > 1 and all(d is None or a.shape[d] % tp == 0
                           for d, a in zip(in_dims, args))

    def spec(d):
        return P() if d is None or not split else P(*(None,) * d, axis)

    out_specs = (tuple(map(spec, out_dims)) if isinstance(out_dims, tuple)
                 else spec(out_dims))
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(map(spec, in_dims)),
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# QKV_PM — Algorithm 1
# ---------------------------------------------------------------------------

def qkv_projection_reference(x, wq, wk, wv, bq=None, bk=None, bv=None, *,
                             tile_d: int = 64):
    """Paper-faithful Algorithm 1: column-tiled projection with accumulation.

    x : (..., S, D); w* : (D, H, dh) — tiled along D (the reduction dim, the
    one FAMOUS tiles since "the first dimension is already reduced by the
    number of heads").  Each iteration loads one (TS,)-slice of x and one
    (TS, H, dh) tile of each weight and accumulates partial products, exactly
    as the BRAM tiles are swapped and partial sums accumulated on the FPGA.
    """
    d = x.shape[-1]
    tile_d = min(tile_d, d)
    assert d % tile_d == 0, (d, tile_d)
    n_tiles = d // tile_d

    def one(w):
        acc = jnp.zeros(x.shape[:-1] + w.shape[1:], jnp.float32)
        for t in range(n_tiles):  # the (d_model / TS) BRAM-reload iterations
            xs = jax.lax.dynamic_slice_in_dim(x, t * tile_d, tile_d, axis=-1)
            ws = jax.lax.dynamic_slice_in_dim(w, t * tile_d, tile_d, axis=0)
            acc = acc + jnp.einsum(
                "...sd,dhe->...she", xs.astype(jnp.float32), ws.astype(jnp.float32)
            )
        return acc

    q, k, v = one(wq), one(wk), one(wv)
    # Bias load is overlapped with compute on the FPGA; added at the end.
    if bq is not None:
        q, k, v = q + bq, k + bk, v + bv
    return q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype)


def qkv_projection_xla(x, wq, wk, wv, bq=None, bk=None, bv=None, *,
                       quantized: bool = False):
    """Fused XLA projection (one read of x feeds three matmuls, like the
    shared X BRAM in QKV_PM).  Optional int8 path = 8-bit fixed point."""
    if quantized:
        q = quant_lib.int8_einsum("...sd,dhe->...she", x, wq)
        k = quant_lib.int8_einsum("...sd,dhe->...she", x, wk)
        v = quant_lib.int8_einsum("...sd,dhe->...she", x, wv)
    else:
        w = jnp.concatenate(
            [wq.reshape(wq.shape[0], -1), wk.reshape(wk.shape[0], -1),
             wv.reshape(wv.shape[0], -1)], axis=-1)
        qkv = jnp.einsum("...sd,df->...sf", x, w.astype(x.dtype))
        nq = wq.shape[1] * wq.shape[2]
        nk = wk.shape[1] * wk.shape[2]
        q = qkv[..., :nq].reshape(x.shape[:-1] + wq.shape[1:])
        k = qkv[..., nq:nq + nk].reshape(x.shape[:-1] + wk.shape[1:])
        v = qkv[..., nq + nk:].reshape(x.shape[:-1] + wv.shape[1:])
    if bq is not None:
        q, k, v = q + bq.astype(q.dtype), k + bk.astype(k.dtype), v + bv.astype(v.dtype)
    return q, k, v


def qkv_projection(x, wq, wk, wv, bq=None, bk=None, bv=None, *,
                   cfg: FamousConfig = FamousConfig()):
    if cfg.impl == "reference":
        return qkv_projection_reference(x, wq, wk, wv, bq, bk, bv,
                                        tile_d=cfg.tile_d)
    if cfg.impl == "pallas":
        from repro.kernels.qkv import ops as qkv_ops
        w, b = 1, 0              # (D, H|KV, dh) weights, (H|KV, dh) biases
        biases = () if bq is None else (bq, bk, bv)
        return _per_device(
            cfg, lambda x, wq, wk, wv, *bs: qkv_ops.qkv_projection(
                x, wq, wk, wv, *bs, tile_d=cfg.tile_d, quant=cfg.quant),
            (REP, w, w, w) + (b,) * len(biases), (HEADS,) * 3,
            x, wq, wk, wv, *biases)
    return qkv_projection_xla(x, wq, wk, wv, bq, bk, bv,
                              quantized=cfg.quant == "int8")


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, *, causal: bool, window: int, dtype=jnp.float32):
    """Additive mask bias (0 / -inf) for (len(q_pos), len(k_pos))."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, -jnp.inf).astype(dtype)


def _broadcast_kv(x, num_q_heads):
    """GQA: repeat kv heads to match query heads. x: (B, S, KV, dh)."""
    kv = x.shape[-2]
    if kv == num_q_heads:
        return x
    return jnp.repeat(x, num_q_heads // kv, axis=-2)


# ---------------------------------------------------------------------------
# QK_PM + softmax + SV_PM — Algorithms 2 & 3
# ---------------------------------------------------------------------------

def attention_reference(q, k, v, *, causal=True, window=0, scale=None,
                        q_offset=0):
    """Paper-faithful QK_PM/SV_PM: materialise S (the FPGA keeps S in BRAM),
    full softmax, then S·V.  Fine at the paper's SL=64; the baseline oracle."""
    B, Sq, H, dh = q.shape
    Skv = k.shape[1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    k = _broadcast_kv(k, H)
    v = _broadcast_kv(v, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Skv)
    s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window)[None, None]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _flash_forward(q, k, v, *, causal, window, scale, q_offset, block_k):
    """Online-softmax forward over key tiles. q,k,v: (B,S,H,dh), kv already
    broadcast to H heads. Returns (out (B,Sq,H,dh), lse (B,H,Sq))."""
    B, Sq, H, dh = q.shape
    Skv = k.shape[1]
    nkb = Skv // block_k
    q_pos = q_offset + jnp.arange(Sq)
    # §Perf C1 (REFUTED): casting P blocks to bf16 before the PV dot
    # materialised both the f32 and bf16 copies in the XLA path (+26% HBM
    # traffic); P stays f32 here — the VMEM-resident Pallas kernel is the
    # path that truly removes this traffic on TPU.
    p_dtype = jnp.float32

    kb = k.reshape(B, nkb, block_k, H, dh).swapaxes(0, 1)
    vb = v.reshape(B, nkb, block_k, H, dh).swapaxes(0, 1)

    def step(carry, blk):
        acc, m, l = carry
        kt, vt, kb_idx = blk
        k_pos = kb_idx * block_k + jnp.arange(block_k)
        # C2: native-dtype QK dot with f32 accumulation — bf16 operands hit
        # the MXU fast path and halve the q/k HBM reads vs upcast-first.
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kt,
                       preferred_element_type=jnp.float32) * scale
        s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window)[None, None]
        m_new = jnp.maximum(m, s.max(-1))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> use where
        safe_m = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(jnp.where(jnp.isinf(s), -jnp.inf, s - safe_m[..., None]))
        corr = jnp.where(jnp.isinf(m), jnp.zeros_like(m), jnp.exp(m - safe_m))
        l = l * corr + p.sum(-1)
        # probabilities cross HBM in p_dtype (§Perf iteration C1): the
        # (bq, bk) P block is the dominant HBM traffic of the XLA flash path
        # at 32k; the row stats (m, l) and accumulator stay f32.
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(p_dtype),
            vt.astype(p_dtype)).astype(jnp.float32)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, H, Sq, dh), jnp.float32)
    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (kb, vb, jnp.arange(nkb)))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).swapaxes(1, 2).astype(q.dtype)
    lse = jnp.where(jnp.isinf(m), m, m + jnp.log(l_safe))
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, window, scale, q_offset, block_k):
    out, _ = _flash_forward(q, k, v, causal=causal, window=window,
                            scale=scale, q_offset=q_offset, block_k=block_k)
    return out


def _flash_fwd_rule(q, k, v, causal, window, scale, q_offset, block_k):
    out, lse = _flash_forward(q, k, v, causal=causal, window=window,
                              scale=scale, q_offset=q_offset, block_k=block_k)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, scale, q_offset, block_k, res, dout):
    """Flash backward: recompute probabilities block-by-block — memory per
    step is O(Sq·block_k); the full S / P matrices are never stacked (the
    naive scan backward saved them per block: 8 GiB/layer at 4k·f32)."""
    q, k, v, out, lse = res
    B, Sq, H, dh = q.shape
    Skv = k.shape[1]
    nkb = Skv // block_k
    p_dtype = jnp.float32  # see C1 note in _flash_forward
    qf = q.astype(jnp.float32) * scale
    do = dout.astype(jnp.float32).swapaxes(1, 2)          # (B,H,Sq,dh)
    delta = jnp.sum(do * out.astype(jnp.float32).swapaxes(1, 2), -1)  # (B,H,Sq)
    q_pos = q_offset + jnp.arange(Sq)
    kb = k.reshape(B, nkb, block_k, H, dh).swapaxes(0, 1)
    vb = v.reshape(B, nkb, block_k, H, dh).swapaxes(0, 1)

    def step(carry, blk):
        dq, dk_acc, dv_acc = carry
        kt, vt, kb_idx = blk
        k_pos = kb_idx * block_k + jnp.arange(block_k)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kt.astype(jnp.float32))
        s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window)[None, None]
        p = jnp.where(jnp.isinf(s) | jnp.isinf(lse[..., None]), 0.0,
                      jnp.exp(s - lse[..., None]))        # (B,H,Sq,block)
        pb = p.astype(p_dtype)                            # C1: low-p HBM blocks
        dv = jnp.einsum("bhqk,bhqd->bkhd", pb,
                        do.astype(p_dtype)).astype(jnp.float32)
        dp = jnp.einsum("bhqd,bkhd->bhqk", do, vt.astype(jnp.float32))
        ds = (p * (dp - delta[..., None])).astype(p_dtype)  # d(scores)
        dq = dq + scale * jnp.einsum("bhqk,bkhd->bqhd", ds,
                                     kt.astype(p_dtype)).astype(jnp.float32)
        dk = scale * jnp.einsum("bhqk,bqhd->bkhd", ds,
                                q.astype(p_dtype)).astype(jnp.float32)
        # accumulate dk/dv into the carry (dynamic-update-slice): with the
        # query dim sharded, XLA reduces the partial sums ONCE after the
        # scan instead of all-reducing every block (§Perf iteration Q4).
        dk_acc = jax.lax.dynamic_update_slice_in_dim(
            dk_acc, dk, kb_idx * block_k, axis=1)
        dv_acc = jax.lax.dynamic_update_slice_in_dim(
            dv_acc, dv, kb_idx * block_k, axis=1)
        return (dq, dk_acc, dv_acc), None

    dq0 = jnp.zeros((B, Sq, H, dh), jnp.float32)
    dkv0 = jnp.zeros((B, Skv, H, dh), jnp.float32)
    (dq, dk, dv), _ = jax.lax.scan(
        step, (dq0, dkv0, dkv0), (kb, vb, jnp.arange(nkb)))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def attention_xla(q, k, v, *, causal=True, window=0, scale=None, q_offset=0,
                  block_k: int = 512):
    """TPU-adapted QK/SV modules: same tiling idea, online softmax over key
    tiles (running max/sum) so S is never materialised, with a flash-style
    custom VJP (blockwise recompute) so the backward never stacks P either.
    This is what the dry-run lowers and what training uses on non-TPU
    backends."""
    B, Sq, H, dh = q.shape
    Skv = k.shape[1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    k = _broadcast_kv(k, H)
    v = _broadcast_kv(v, H)
    if Skv <= block_k or Skv % block_k:
        return attention_reference(q, k, v, causal=causal, window=window,
                                   scale=scale, q_offset=q_offset)
    return _flash_attention(q, k, v, causal, window, scale, q_offset,
                            block_k)


def attention(q, k, v, *, causal=True, window=0, scale=None, q_offset=0,
              cfg: FamousConfig = FamousConfig()):
    """Dense multi-head attention — FAMOUS QK_PM → softmax → SV_PM."""
    if cfg.impl == "reference":
        return attention_reference(q, k, v, causal=causal, window=window,
                                   scale=scale, q_offset=q_offset)
    if cfg.impl == "pallas":
        # Fully Pallas path (fwd + custom-VJP bwd kernels); tile_q/tile_k are
        # clamped to the sequence lengths inside the wrapper.
        from repro.kernels.attention import ops as attn_ops
        return _per_device(
            cfg, functools.partial(attn_ops.mha, causal=causal, window=window,
                                   scale=scale, q_offset=q_offset,
                                   block_q=cfg.tile_q, block_k=cfg.tile_k),
            (HEADS,) * 3, HEADS, q, k, v)
    return attention_xla(q, k, v, causal=causal, window=window, scale=scale,
                         q_offset=q_offset, block_k=cfg.tile_k)


def decode_attention(q, k_cache, v_cache, cache_len, *, window=0, scale=None,
                     cfg: FamousConfig = FamousConfig()):
    """One-token attention against a KV cache (serving decode step).

    q: (B, 1, H, dh); caches: (B, S_max, KV, dh); cache_len: (B,) int32 —
    number of valid cache entries (the new token's k/v already written).
    """
    B, _, H, dh = q.shape
    Smax = k_cache.shape[1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    if cfg.impl == "pallas":
        from repro.kernels.decode import ops as dec_ops
        return _per_device(
            cfg, functools.partial(dec_ops.decode_attention, window=window,
                                   scale=scale, block_k=cfg.tile_k),
            (HEADS, HEADS, HEADS, REP), HEADS, q, k_cache, v_cache, cache_len)
    k = _broadcast_kv(k_cache, H)
    v = _broadcast_kv(v_cache, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    pos = jnp.arange(Smax)[None, :]                      # (1, Smax)
    ok = pos < cache_len[:, None]
    if window:
        ok &= pos > (cache_len[:, None] - 1 - window)
    s = jnp.where(ok[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _verify_dense(q, k, v, cache_len, scale):
    """Dense masked speculative-verify attention over gathered (B, S, KV,
    dh) views: query j of slot b, at position ``cache_len[b] + j``, attends
    keys at positions ``<= cache_len[b] + j`` (W == 1 is a plain decode)."""
    B, W, H, dh = q.shape
    Smax = k.shape[1]
    k = _broadcast_kv(k, H)
    v = _broadcast_kv(v, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    q_pos = cache_len[:, None] + jnp.arange(W)[None, :]         # (B, W)
    ok = jnp.arange(Smax)[None, None, :] <= q_pos[:, :, None]   # (B, W, Smax)
    s = jnp.where(ok[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _paged_kernel(cfg, op, q, k_pages, v_pages, page_table, lens_or_offset,
                  layer, scale, k_scale, v_scale):
    """Dispatch one paged Pallas wrapper of kernels/decode/ops.py (fp or
    int8 pools) per device of ``cfg.mesh``."""
    from repro.kernels.decode import ops as dec_ops
    if k_scale is None:
        fn = functools.partial(getattr(dec_ops, op), scale=scale)
        return _per_device(cfg, fn, (HEADS, POOL, POOL, REP, REP, REP), HEADS,
                           q, k_pages, v_pages, page_table, lens_or_offset,
                           layer)
    fn = functools.partial(getattr(dec_ops, op + "_int8"), scale=scale)
    return _per_device(cfg, fn, (HEADS,) + (POOL,) * 4 + (REP,) * 3, HEADS,
                       q, k_pages, v_pages, k_scale, v_scale, page_table,
                       lens_or_offset, layer)


def _gather_layer(k_pages, v_pages, page_table, layer, k_scale, v_scale):
    """Layer ``layer``'s K/V of each slot's table, gathered from the stacked
    pools into contiguous (B, n_p * page_size, KV, dh) views (dequantized
    when the pools are int8) — the XLA reference path."""
    from repro.kernels.decode.ref import gather_pages, gather_pages_int8
    if k_scale is not None:
        return (gather_pages_int8(k_pages, k_scale, page_table, layer),
                gather_pages_int8(v_pages, v_scale, page_table, layer))
    return (gather_pages(k_pages, page_table, layer),
            gather_pages(v_pages, page_table, layer))


def paged_verify_attention(q, k_pages, v_pages, page_table, cache_len, layer,
                           *, scale=None, k_scale=None, v_scale=None,
                           cfg: FamousConfig = FamousConfig()):
    """Speculative-verify attention against a *paged* KV cache.

    q: (B, W, H, dh) at per-slot positions ``cache_len[b] + j``; pools: the
    layer stack's (L, KV, n_pages, page_size, dh); page_table: (B, n_p)
    int32; layer: () int32, the layer read.  Query j attends keys at
    positions ``<= cache_len[b] + j`` — W == 1 is exactly a decode, so a
    zero-draft slot verifies as a plain decode; the per-slot offsets are
    runtime operands, so one executable serves every draft-length mix.
    impl="pallas" flattens (slot, verify position) pairs into rows of the
    scalar-prefetched page-table decode kernel; other impls gather the
    table into a contiguous view and run the dense masked form.

    With ``k_scale``/``v_scale`` (fp32 (L, KV, n_pages, 1, page_size)
    pools) the K/V pools are int8 and dequantized in-kernel (pallas) or via
    the dequantizing gather (other impls) — the ``kv_dtype="int8"`` path.
    """
    dh = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    if cfg.impl == "pallas":
        return _paged_kernel(cfg, "paged_verify_attention", q, k_pages, v_pages,
                             page_table, cache_len, layer, scale, k_scale,
                             v_scale)
    k, v = _gather_layer(k_pages, v_pages, page_table, layer, k_scale, v_scale)
    return _verify_dense(q, k, v, cache_len, scale)


def attention_at_positions(q, k, v, q_pos, k_pos, *, window=0, scale=None):
    """Dense masked attention with *explicit* absolute positions.

    q: (B, Sq, H, dh) at positions ``q_pos`` (Sq,); k/v: (B, Skv, KV, dh) at
    positions ``k_pos`` (Skv,).  Causal: query i sees key j iff
    ``k_pos[j] <= q_pos[i]`` (and within ``window`` when set); negative
    ``k_pos`` entries mark invalid keys (e.g. ring-buffer slots not yet
    written) and are always masked.  XLA-only helper for the ring-buffer
    chunked-prefill path, where keys are a gathered window rather than a
    cache prefix.
    """
    B, Sq, H, dh = q.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    k = _broadcast_kv(k, H)
    v = _broadcast_kv(v, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    ok = (k_pos[None, :] <= q_pos[:, None]) & (k_pos >= 0)[None, :]
    if window:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(ok[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _chunk_dense(q, k, v, q_offset, scale):
    """Dense masked chunked-prefill attention over gathered (B, S, KV, dh)
    views: chunk query i, at position ``q_offset + i``, attends keys at
    positions ``<= q_offset + i`` (the resident prefix plus its own
    chunk)."""
    B, C, H, dh = q.shape
    Skv = k.shape[1]
    k = _broadcast_kv(k, H)
    v = _broadcast_kv(v, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(C)
    ok = jnp.arange(Skv)[None, :] <= q_pos[:, None]
    s = jnp.where(ok[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_chunked_prefill_attention(q, k_pages, v_pages, page_table, q_offset,
                                    layer, *, scale=None, k_scale=None,
                                    v_scale=None,
                                    cfg: FamousConfig = FamousConfig()):
    """Chunked-prefill attention against a *paged* KV cache.

    q: (B, C, H, dh) at positions ``[q_offset, q_offset + C)``; pools: the
    layer stack's (L, KV, n_pages, page_size, dh); page_table: (B, n_p)
    int32; layer: () int32.  The chunk's K/V must already be scattered
    into the slot's pages of that layer; the chunk attends causally to the
    resident prefix *plus its own chunk*.  ``q_offset`` is a runtime
    scalar — one executable serves every (prompt length, chunk index)
    pair, the paper's "reprogram loop bounds, never re-synthesise" applied
    to prefill.  impl="pallas" reuses the scalar-prefetched page-table
    BlockSpec machinery of ``paged_decode_attention``; other impls gather
    the table into a contiguous view and run the dense masked form.
    ``k_scale``/``v_scale`` select the int8-pool path (see
    paged_verify_attention).
    """
    B, C, H, dh = q.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    if cfg.impl == "pallas":
        return _paged_kernel(cfg, "paged_chunk_prefill_attention", q, k_pages,
                             v_pages, page_table, q_offset, layer, scale,
                             k_scale, v_scale)
    k, v = _gather_layer(k_pages, v_pages, page_table, layer, k_scale, v_scale)
    return _chunk_dense(q, k, v, q_offset, scale)


def paged_decode_attention(q, k_pages, v_pages, page_table, cache_len, layer,
                           *, scale=None, k_scale=None, v_scale=None,
                           cfg: FamousConfig = FamousConfig()):
    """One-token attention against a *paged* KV cache.

    q: (B, 1, H, dh); pools: the layer stack's (L, KV, n_pages, page_size,
    dh), shared by every sequence; page_table: (B, n_p) int32 page ids per
    slot; cache_len: (B,) int32 valid entries (the new token's k/v already
    written to its page); layer: () int32, the layer read.

    impl="pallas" streams pages directly via a scalar-prefetched page table
    (kernels/decode); other impls gather the table into a contiguous
    per-slot view and reuse the dense decode path — the XLA reference the
    kernel is validated against.  ``k_scale``/``v_scale`` select the int8
    pool path (see paged_verify_attention).
    """
    B, _, H, dh = q.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    if cfg.impl == "pallas":
        return _paged_kernel(cfg, "paged_decode_attention", q, k_pages, v_pages,
                             page_table, cache_len, layer, scale, k_scale,
                             v_scale)
    k, v = _gather_layer(k_pages, v_pages, page_table, layer, k_scale, v_scale)
    return decode_attention(q, k, v, cache_len, scale=scale, cfg=cfg)


# ---------------------------------------------------------------------------
# Full MHA layer (projection + attention + output) — the paper's fig. 3 box.
# ---------------------------------------------------------------------------

def mha_block(x, params, *, num_heads, num_kv_heads, causal=True, window=0,
              qk_norm_fn=None, cfg: FamousConfig = FamousConfig(),
              rope_fn=None, q_offset=0):
    """x: (B, S, D).  params: dict with wq/wk/wv (D,H,dh), optional b*,
    wo (H, dh, D).  Returns (B, S, D)."""
    q, k, v = qkv_projection(
        x, params["wq"], params["wk"], params["wv"],
        params.get("bq"), params.get("bk"), params.get("bv"), cfg=cfg)
    if qk_norm_fn is not None:
        q, k = qk_norm_fn(q, k)
    if rope_fn is not None:
        q, k = rope_fn(q, k)
    out = attention(q, k, v, causal=causal, window=window, q_offset=q_offset,
                    cfg=cfg)
    return jnp.einsum("bshe,hed->bsd", out, params["wo"].astype(out.dtype))
