"""Gradient parity of the Pallas flash-attention custom VJP (interpret mode
on CPU) against the reference path: jax.grad through
``attention(..., cfg=FamousConfig(impl="pallas"))`` must match the
materialised-S oracle within fp32 tolerance for causal, windowed and GQA
configurations — with the backward running the Pallas dq / dk-dv kernels,
never the XLA flash backward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import famous
from repro.kernels.attention import mha as mha_kernel
from repro.kernels.attention import ops as attn_ops

# B, S, H, KV, dh, causal, window, block_q, block_k
CASES = [
    (2, 128, 4, 4, 32, True, 0, 64, 64),      # causal MHA
    (2, 128, 4, 2, 32, True, 0, 64, 64),      # causal GQA (group 2)
    (1, 256, 4, 1, 16, True, 64, 64, 128),    # windowed causal MQA
    (2, 128, 4, 4, 32, False, 0, 128, 64),    # bidirectional
    (1, 192, 6, 3, 16, True, 32, 96, 64),     # window + GQA, uneven blocks
]


def _inputs(B, S, H, KV, dh, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, dh)) * 0.5
    k = jax.random.normal(ks[1], (B, S, KV, dh)) * 0.5
    v = jax.random.normal(ks[2], (B, S, KV, dh)) * 0.5
    w = jax.random.normal(ks[3], (B, S, H, dh))   # cotangent projection
    return q, k, v, w


@pytest.mark.parametrize("B,S,H,KV,dh,causal,window,bq,bk", CASES)
def test_pallas_grad_matches_reference(B, S, H, KV, dh, causal, window,
                                       bq, bk):
    q, k, v, w = _inputs(B, S, H, KV, dh)
    cfg = famous.FamousConfig(impl="pallas", tile_q=bq, tile_k=bk)

    def loss_pallas(q, k, v):
        out = famous.attention(q, k, v, causal=causal, window=window, cfg=cfg)
        return jnp.sum(out * w)

    def loss_ref(q, k, v):
        out = famous.attention_reference(q, k, v, causal=causal,
                                         window=window)
        return jnp.sum(out * w)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gp, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-5, rtol=1e-4, err_msg=f"d{name}")


def test_custom_vjp_forward_regression():
    """The custom-VJP wrapper's primal output is the same kernel forward —
    taking gradients must not perturb the forward value."""
    q, k, v, w = _inputs(2, 128, 4, 2, 32, seed=1)
    ref = famous.attention_reference(q, k, v, causal=True)

    out_plain = attn_ops.mha(q, k, v, causal=True, block_q=64, block_k=64)
    out_vjp, _ = jax.value_and_grad(
        lambda q_: jnp.sum(attn_ops.mha(q_, k, v, causal=True, block_q=64,
                                        block_k=64) * w))(q)
    np.testing.assert_allclose(np.asarray(out_plain), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # value_and_grad over the same wrapper reduces the same forward
    np.testing.assert_allclose(out_vjp, float(jnp.sum(ref * w)), rtol=1e-5)


def test_backward_uses_pallas_kernels(monkeypatch):
    """No fallback: the VJP must trace through mha_backward (the Pallas dq /
    dk-dv kernels), not the XLA flash backward."""
    calls = []
    real = mha_kernel.mha_backward

    def counting(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(mha_kernel, "mha_backward", counting)
    # unique shape so the jitted wrapper cannot reuse a cached trace
    q, k, v, w = _inputs(1, 160, 2, 1, 8, seed=2)
    jax.grad(lambda q_: jnp.sum(attn_ops.mha(
        q_, k, v, causal=True, block_q=32, block_k=32) * w))(q)
    assert calls, "backward did not go through the Pallas mha_backward"


def test_forward_lse_matches_reference_logsumexp():
    """The LSE residual the backward consumes equals the row logsumexp of
    the masked scores."""
    B, S, H, dh = 1, 128, 2, 16
    q, k, v, _ = _inputs(B, S, H, H, dh, seed=3)
    qf, kf = attn_ops._to_flat(q), attn_ops._to_flat(k)
    _, lse = mha_kernel.mha_forward(qf, kf, attn_ops._to_flat(v),
                                    causal=True, block_q=64, block_k=64,
                                    interpret=True, return_lse=True)
    scale = 1.0 / np.sqrt(dh)
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) * scale
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    ref = jax.nn.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse[..., 0]), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_qkv_matmul_grad_matches_xla():
    """The tiled QKV projection kernel differentiates through itself."""
    from repro.kernels.qkv import qkv_proj
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    # every axis spans two blocks, so the forward reduces over D blocks
    # and the dW pass over T blocks
    x = jax.random.normal(ks[0], (256, 256)) * 0.5
    w = jax.random.normal(ks[1], (256, 256)) * 0.05
    g = jax.random.normal(ks[2], (256, 256))

    def loss_k(x, w):
        return jnp.sum(qkv_proj.matmul_tiled(x, w, block_t=128, block_f=128,
                                             block_d=128, interpret=True) * g)

    def loss_x(x, w):
        return jnp.sum((x @ w) * g)

    gk = jax.grad(loss_k, argnums=(0, 1))(x, w)
    gx = jax.grad(loss_x, argnums=(0, 1))(x, w)
    for a, b in zip(gk, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
