"""Jitted wrapper: fused Q/K/V projection via the tiled matmul kernel.

Fuses [Wq|Wk|Wv] into one (D, F) matrix so the X tile is read once per grid
step and feeds all three projections — the QKV_PM shared-X-BRAM trick.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant as quant_lib
from repro.kernels import pallas_compat as pc
from repro.kernels.qkv import qkv_proj


def _tile(n: int, cap: int, align: int) -> int:
    """The widest multiple of ``align`` up to ``cap`` that divides ``n``, or
    all of ``n`` when it fits under ``cap`` or nothing divides it: Mosaic
    takes a block whose last two dims are multiples of (8, 128) or the
    whole array dim."""
    if n <= cap:
        return n
    for b in range(cap - cap % align, 0, -align):
        if n % b == 0:
            return b
    return n


@functools.partial(jax.jit, static_argnames=("tile_d", "quant", "interpret"))
def qkv_projection(x, wq, wk, wv, bq=None, bk=None, bv=None, *,
                   tile_d: int = 512, quant: str = "none", interpret=None):
    """x: (B, S, D); w*: (D, H|KV, dh). Returns (q, k, v).

    ``tile_d`` (the TS reduction tile) goes to the kernel as given; the
    token and fused-output tiles are picked here from the shapes, since a
    tensor-parallel shard's fused width ((H + 2 KV) dh / TP) need not be a
    multiple of the kernel's default."""
    B, S, D = x.shape
    interpret = pc.interpret_default() if interpret is None else interpret
    shapes = [wq.shape[1:], wk.shape[1:], wv.shape[1:]]
    w = jnp.concatenate([wq.reshape(D, -1), wk.reshape(D, -1),
                         wv.reshape(D, -1)], axis=-1)
    xt = x.reshape(B * S, D)
    blocks = dict(block_t=_tile(B * S, 256, 8),
                  block_f=_tile(w.shape[1], 256, 128), block_d=tile_d)
    if quant == "int8":
        xq, sx = quant_lib.quantize(xt, axis=1)
        wqz, sw = quant_lib.quantize(w, axis=0)
        out = qkv_proj.matmul_tiled_int8(
            xq, wqz, sx, sw, out_dtype=jnp.float32, interpret=interpret,
            **blocks).astype(x.dtype)
    else:
        out = qkv_proj.matmul_tiled(xt, w, interpret=interpret, **blocks)
    nq = shapes[0][0] * shapes[0][1]
    nk = shapes[1][0] * shapes[1][1]
    q = out[:, :nq].reshape(B, S, *shapes[0])
    k = out[:, nq:nq + nk].reshape(B, S, *shapes[1])
    v = out[:, nq + nk:].reshape(B, S, *shapes[2])
    if bq is not None:
        q = q + bq.astype(q.dtype)
        k = k + bk.astype(k.dtype)
        v = v + bv.astype(v.dtype)
    return q, k, v
