"""Jitted public wrapper for the fused MHA kernel.

Layout adaptation: model code uses (B, S, H, dh); the kernel uses flattened
(B·H, S, dh).  Backward: a flash custom-VJP whose forward *and* backward run
Pallas kernels — the forward additionally emits the per-row LSE, and the
backward recomputes P tile-by-tile in the dq / dk-dv kernels
(kernels/attention/mha.py), so ``impl="pallas"`` trains end-to-end with no
fallback to the XLA flash path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import pallas_compat as pc
from repro.kernels.attention import mha as mha_kernel


def _to_flat(x):  # (B, S, H, dh) -> (B*H, S, dh)
    B, S, H, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, dh)


def _from_flat(x, B, H):  # (B*H, S, dh) -> (B, S, H, dh)
    BH, S, dh = x.shape
    return x.reshape(B, H, S, dh).transpose(0, 2, 1, 3)


# --- flash custom VJP over the flattened (BH, S, dh) layout ----------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_mha(q, k, v, causal, window, scale, q_offset, block_q, block_k,
               interpret):
    return mha_kernel.mha_forward(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret)


def _flash_mha_fwd(q, k, v, causal, window, scale, q_offset, block_q,
                   block_k, interpret):
    out, lse = mha_kernel.mha_forward(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret, return_lse=True)
    return out, (q, k, v, out, lse)


def _flash_mha_bwd(causal, window, scale, q_offset, block_q, block_k,
                   interpret, res, dout):
    q, k, v, out, lse = res
    dq, dk, dv = mha_kernel.mha_backward(
        q, k, v, out, lse, dout, causal=causal, window=window, scale=scale,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "q_offset", "block_q", "block_k",
    "interpret"))
def mha(q, k, v, *, causal=True, window=0, scale=None, q_offset=0,
        block_q=512, block_k=512, interpret=None):
    """q: (B, Sq, H, dh); k, v: (B, Skv, KV, dh). Returns (B, Sq, H, dh).

    Differentiable: gradients flow through the flash backward Pallas
    kernels (custom VJP), with the GQA head-group reduction applied to
    dk/dv inside the kernel wrapper."""
    B, Sq, H, dh = q.shape
    Skv = k.shape[1]
    interpret = pc.interpret_default() if interpret is None else interpret
    # resolve data-independent knobs here so the custom-VJP nondiff args are
    # concrete (the backward kernels reuse the exact forward tiling + scale)
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(dh)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    out = _flash_mha(_to_flat(q), _to_flat(k), _to_flat(v), causal, window,
                     scale, q_offset, block_q, block_k, interpret)
    return _from_flat(out, B, H)
