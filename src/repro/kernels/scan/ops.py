"""Jitted wrappers for the recurrence kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pallas_compat as pc
from repro.kernels.scan import linear_scan


@functools.partial(jax.jit, static_argnames=("block_r", "block_s", "interpret"))
def rglru(a, b, *, block_r=512, block_s=256, interpret=None):
    interpret = pc.interpret_default() if interpret is None else interpret
    return linear_scan.rglru_scan(a, b, block_r=block_r, block_s=block_s,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, logw, u, *, chunk=64, interpret=None):
    """r,k,v,logw: (B, H, S, dh); u: (H, dh). Returns (B, H, S, dh) f32."""
    B, H, S, dh = r.shape
    interpret = pc.interpret_default() if interpret is None else interpret
    flat = lambda x: x.reshape(B * H, S, dh)
    uu = jnp.broadcast_to(u[None], (B, H, dh)).reshape(B * H, dh)
    out = linear_scan.wkv6_scan(flat(r), flat(k), flat(v), flat(logw), uu,
                                chunk=chunk, interpret=interpret)
    return out.reshape(B, H, S, dh)
