"""Fused MHA Pallas TPU kernels — FAMOUS QK_PM → softmax → SV_PM in one
pass over key tiles, plus flash backward kernels (dq and dk/dv).

Mapping from the paper (DESIGN.md §2): the (block_q, block_k) tile pair is
the TS analogue; Q tiles stay resident in VMEM (the Q BRAM), K/V tiles
stream through (the K/V BRAMs being reloaded per iteration), the MXU plays
the PE array and the VPU the LUT-based softmax.  Unlike the FPGA (SL=64),
S is never materialised: an online (running max/sum) softmax accumulates
into a VMEM scratch accumulator across the key-tile grid dimension.

Grid: (B·H, Sq/block_q, Skv/block_k) — the last dimension is sequential
("arbitrary"), carrying (acc, m, l) scratch across key tiles; batch·head and
query tiles are parallel.  GQA is handled in the K/V index maps (q head h
reads kv head h // group), mirroring FAMOUS's shared-K-BRAM PE groups.

Backward (FlashAttention-style blockwise recompute, mirroring the XLA
``_flash_bwd_rule`` in core/famous.py): the forward additionally emits the
per-row log-sum-exp (LSE); the backward never stores S or P but recomputes
the (block_q, block_k) probability tile from Q, K and the saved LSE.  Two
kernels:

* ``_mha_bwd_dq_kernel``  — grid (B·H, Sq/block_q, Skv/block_k), key tiles
  sequential, accumulating dq for one query tile in VMEM scratch.
* ``_mha_bwd_dkv_kernel`` — grid (B·H, Skv/block_k, Sq/block_q), query
  tiles sequential, accumulating dk and dv for one key tile in VMEM
  scratch.  GQA: gradients are produced per *query* head; the wrapper
  reduces over the head group to recover the shared-KV-head gradient
  (the adjoint of the shared-K-BRAM broadcast).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_compat as pc

NEG_INF = -1e30


def _tile_mask(s_shape, iq, ik, *, causal: bool, window: int, block_q: int,
               block_k: int, q_offset: int):
    """Boolean validity mask for one (block_q, block_k) score tile."""
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s_shape, 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    ok = jnp.ones(s_shape, dtype=jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window:
        ok &= k_pos > q_pos - window
    return ok


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _mha_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, window: int, block_q: int,
                block_k: int, num_k_blocks: int, q_offset: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)                  # (bk, dh)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    ok = _tile_mask(s.shape, iq, ik, causal=causal, window=window,
                    block_q=block_q, block_k=block_k, q_offset=q_offset)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def mha_forward(q, k, v, *, causal: bool = True, window: int = 0,
                scale: float | None = None, q_offset: int = 0,
                block_q: int = 512, block_k: int = 512,
                interpret: bool = False, return_lse: bool = False):
    """q: (BH, Sq, dh); k, v: (BKV, Skv, dh) with BH = BKV * group.
    Returns (BH, Sq, dh), plus the f32 row log-sum-exp (BH, Sq, 1) when
    ``return_lse`` (the flash backward residual; a trailing unit dim keeps
    its (block_q, 1) blocks legal under Mosaic's tiling rule)."""
    BH, Sq, dh = q.shape
    BKV, Skv, _ = k.shape
    group = BH // BKV
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    nq, nk = Sq // block_q, Skv // block_k
    grid = (BH, nq, nk)

    kernel = functools.partial(
        _mha_kernel, scale=float(scale), causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=nk, q_offset=q_offset)

    out, lse = pc.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik, group=group: (bh // group, ik, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda bh, iq, ik, group=group: (bh // group, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pc.VMEM((block_q, dh), jnp.float32),   # acc
            pc.VMEM((block_q, 1), jnp.float32),    # running max m
            pc.VMEM((block_q, 1), jnp.float32),    # running sum l
        ],
        compiler_params=pc.compiler_params("parallel", "parallel",
                                           "arbitrary"),
        interpret=interpret,
    )(q, k, v)
    return (out, lse) if return_lse else out


# ---------------------------------------------------------------------------
# backward — dq (key tiles sequential)
# ---------------------------------------------------------------------------

def _mha_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dq_acc, *, scale: float, causal: bool,
                       window: int, block_q: int, block_k: int,
                       num_k_blocks: int, q_offset: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0].astype(jnp.float32)                  # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)                # (bq, dh)
    lse = lse_ref[0]                                  # (bq, 1)
    delta = delta_ref[0]                              # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = _tile_mask(s.shape, iq, ik, causal=causal, window=window,
                    block_q=block_q, block_k=block_k, q_offset=q_offset)
    p = jnp.where(ok, jnp.exp(s - lse), 0.0)          # recomputed P tile
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dq_acc[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == num_k_blocks - 1)
    def _flush():
        dq_ref[0, ...] = dq_acc[...] * scale


# ---------------------------------------------------------------------------
# backward — dk/dv (query tiles sequential)
# ---------------------------------------------------------------------------

def _mha_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                        causal: bool, window: int, block_q: int,
                        block_k: int, num_q_blocks: int, q_offset: int):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32)                  # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)                # (bq, dh)
    lse = lse_ref[0]                                  # (bq, 1)
    delta = delta_ref[0]                              # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = _tile_mask(s.shape, iq, ik, causal=causal, window=window,
                    block_q=block_q, block_k=block_k, q_offset=q_offset)
    p = jnp.where(ok, jnp.exp(s - lse), 0.0)          # (bq, bk)
    dv_acc[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)                             # (bq, bk)
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(iq == num_q_blocks - 1)
    def _flush():
        dk_ref[0, ...] = dk_acc[...] * scale
        dv_ref[0, ...] = dv_acc[...]


def mha_backward(q, k, v, out, lse, dout, *, causal: bool = True,
                 window: int = 0, scale: float | None = None,
                 q_offset: int = 0, block_q: int = 512, block_k: int = 512,
                 interpret: bool = False):
    """Flash backward.  q/dout/out: (BH, Sq, dh); k, v: (BKV, Skv, dh);
    lse: (BH, Sq, 1) f32.  Returns f32 (dq (BH, Sq, dh), dk, dv
    (BKV, Skv, dh)) with the GQA head-group reduction already applied."""
    BH, Sq, dh = q.shape
    BKV, Skv, _ = k.shape
    group = BH // BKV
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    nq, nk = Sq // block_q, Skv // block_k

    # D_i = Σ_d dO_i·O_i — the softmax-normalisation correction, computed
    # once outside the kernels (cheap elementwise; one pass over O/dO).
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                     # (BH, Sq, 1)
    lse = lse.astype(jnp.float32)

    common = dict(scale=float(scale), causal=causal, window=window,
                  block_q=block_q, block_k=block_k, q_offset=q_offset)

    q_spec = pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, block_k, dh), lambda bh, iq, ik, group=group: (bh // group, ik, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda bh, iq, ik: (bh, iq, 0))

    dq = pc.pallas_call(
        functools.partial(_mha_bwd_dq_kernel, num_k_blocks=nk, **common),
        grid=(BH, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, block_q, dh),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), jnp.float32),
        scratch_shapes=[pc.VMEM((block_q, dh), jnp.float32)],
        compiler_params=pc.compiler_params("parallel", "parallel",
                                           "arbitrary"),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    # dk/dv grid transposes the tile loops: (bh, ik, iq), query sequential.
    q_spec_t = pl.BlockSpec((1, block_q, dh), lambda bh, ik, iq: (bh, iq, 0))
    kv_spec_t = pl.BlockSpec(
        (1, block_k, dh), lambda bh, ik, iq, group=group: (bh // group, ik, 0))
    row_spec_t = pl.BlockSpec((1, block_q, 1),
                              lambda bh, ik, iq: (bh, iq, 0))
    dkv_spec = pl.BlockSpec((1, block_k, dh), lambda bh, ik, iq: (bh, ik, 0))

    dk, dv = pc.pallas_call(
        functools.partial(_mha_bwd_dkv_kernel, num_q_blocks=nq, **common),
        grid=(BH, nk, nq),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, Skv, dh), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Skv, dh), jnp.float32)],
        scratch_shapes=[pc.VMEM((block_k, dh), jnp.float32),
                        pc.VMEM((block_k, dh), jnp.float32)],
        compiler_params=pc.compiler_params("parallel", "parallel",
                                           "arbitrary"),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    if group > 1:
        # adjoint of the shared-KV-head broadcast: sum over the head group
        dk = dk.reshape(BKV, group, Skv, dh).sum(axis=1)
        dv = dv.reshape(BKV, group, Skv, dh).sum(axis=1)
    return dq, dk, dv
