"""Per-kernel validation: sweep shapes/dtypes, assert_allclose vs ref.py
pure-jnp oracles (interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention import ref as attn_ref
from repro.kernels.decode import ops as dec_ops
from repro.kernels.decode import ref as dec_ref
from repro.kernels.qkv import ops as qkv_ops
from repro.kernels.qkv import qkv_proj
from repro.kernels.qkv import ref as qkv_ref
from repro.kernels.scan import ops as scan_ops
from repro.kernels.scan import ref as scan_ref


def _rand(key, shape, dtype, scale=0.5):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32) * scale
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# fused MHA kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,dh,bq,bk", [
    (2, 256, 4, 2, 64, 128, 128),
    (1, 512, 8, 8, 32, 256, 128),
    (2, 128, 4, 1, 64, 64, 64),
    (1, 256, 6, 2, 16, 128, 256),   # block_k > Skv clamps
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mha_kernel_matches_ref(B, S, H, KV, dh, bq, bk, causal, dtype):
    q = _rand(0, (B, S, H, dh), dtype)
    k = _rand(1, (B, S, KV, dh), dtype)
    v = _rand(2, (B, S, KV, dh), dtype)
    out = attn_ops.mha(q, k, v, causal=causal, block_q=bq, block_k=bk)
    r = attn_ref.mha_reference(attn_ops._to_flat(q), attn_ops._to_flat(k),
                               attn_ops._to_flat(v), causal=causal)
    r = attn_ops._from_flat(r, B, H)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


def test_mha_kernel_window():
    q = _rand(0, (2, 256, 4, 32), jnp.float32)
    k = _rand(1, (2, 256, 2, 32), jnp.float32)
    v = _rand(2, (2, 256, 2, 32), jnp.float32)
    out = attn_ops.mha(q, k, v, causal=True, window=64, block_q=64, block_k=64)
    r = attn_ops._from_flat(
        attn_ref.mha_reference(attn_ops._to_flat(q), attn_ops._to_flat(k),
                               attn_ops._to_flat(v), causal=True, window=64),
        2, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-6)


# ---------------------------------------------------------------------------
# tiled QKV projection kernel (Algorithm 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,D,F,bt,bf,bd", [
    (128, 256, 384, 64, 128, 128),
    (256, 512, 128, 128, 128, 256),
    (64, 128, 128, 64, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_tiled(T, D, F, bt, bf, bd, dtype):
    x = _rand(3, (T, D), dtype)
    w = _rand(4, (D, F), dtype, scale=0.05)
    out = qkv_proj.matmul_tiled(x, w, block_t=bt, block_f=bf, block_d=bd,
                                interpret=True)
    r = qkv_ref.matmul_reference(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


def test_matmul_tiled_int8():
    x = _rand(5, (128, 256), jnp.float32)
    w = _rand(6, (256, 128), jnp.float32, scale=0.05)
    xq, sx = quant.quantize(x, axis=1)
    wq, sw = quant.quantize(w, axis=0)
    out = qkv_proj.matmul_tiled_int8(xq, wq, sx, sw, block_d=128,
                                     interpret=True)
    r = qkv_ref.matmul_int8_reference(xq, wq, sx, sw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=1e-4)
    # and the int8 result approximates the f32 matmul
    full = qkv_ref.matmul_reference(x, w, out_dtype=jnp.float32)
    err = np.abs(np.asarray(out) - np.asarray(full)).max()
    assert err < 0.05, err


@pytest.mark.parametrize("quant_mode", ["none", "int8"])
def test_qkv_projection_wrapper(quant_mode):
    B, S, D, H, KV, dh = 2, 32, 256, 4, 2, 16
    x = _rand(7, (B, S, D), jnp.float32)
    wq = _rand(8, (D, H, dh), jnp.float32, 0.05)
    wk = _rand(9, (D, KV, dh), jnp.float32, 0.05)
    wv = _rand(10, (D, KV, dh), jnp.float32, 0.05)
    bq = _rand(11, (H, dh), jnp.float32, 0.01)
    bk = _rand(12, (KV, dh), jnp.float32, 0.01)
    bv = _rand(13, (KV, dh), jnp.float32, 0.01)
    q, k, v = qkv_ops.qkv_projection(x, wq, wk, wv, bq, bk, bv,
                                     tile_d=128, quant=quant_mode)
    qr, kr, vr = qkv_ref.qkv_reference(x, wq, wk, wv, bq, bk, bv)
    tol = 1e-5 if quant_mode == "none" else 0.05
    for a, b in [(q, qr), (k, kr), (v, vr)]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol)


# ---------------------------------------------------------------------------
# decode attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,dh,Skv,bk,window", [
    (2, 4, 2, 32, 256, 64, 0),
    (3, 8, 1, 16, 128, 128, 0),
    (2, 4, 4, 32, 256, 64, 16),
])
def test_decode_kernel(B, H, KV, dh, Skv, bk, window):
    q = _rand(14, (B, 1, H, dh), jnp.float32)
    kc = _rand(15, (B, Skv, KV, dh), jnp.float32)
    vc = _rand(16, (B, Skv, KV, dh), jnp.float32)
    clen = jnp.asarray(np.random.default_rng(0).integers(1, Skv, B), jnp.int32)
    out = dec_ops.decode_attention(q, kc, vc, clen, window=window, block_k=bk)
    group = H // KV
    qf = q[:, 0].reshape(B, KV, group, dh).reshape(B * KV, group, dh)
    kf = kc.transpose(0, 2, 1, 3).reshape(B * KV, Skv, dh)
    vf = vc.transpose(0, 2, 1, 3).reshape(B * KV, Skv, dh)
    r = dec_ref.decode_reference(qf, kf, vf, jnp.repeat(clen, KV),
                                 window=window)
    r = r.reshape(B, KV, group, dh).reshape(B, 1, H, dh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-6)


# ---------------------------------------------------------------------------
# linear-recurrence kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,R,br,bs", [
    (2, 128, 256, 128, 32),
    (1, 64, 256, 128, 64),
    (3, 96, 32, 32, 32),
])
def test_rglru_kernel(B, S, R, br, bs):
    a = jax.nn.sigmoid(_rand(17, (B, S, R), jnp.float32, 1.0))
    b = _rand(18, (B, S, R), jnp.float32, 0.1)
    out = scan_ops.rglru(a, b, block_r=br, block_s=bs)
    r = scan_ref.rglru_reference(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("B,H,S,dh,chunk", [
    (2, 3, 128, 16, 32),
    (1, 2, 64, 32, 64),
    (2, 1, 96, 16, 32),
])
def test_wkv6_kernel(B, H, S, dh, chunk):
    r = _rand(19, (B, H, S, dh), jnp.float32)
    k = _rand(20, (B, H, S, dh), jnp.float32)
    v = _rand(21, (B, H, S, dh), jnp.float32)
    logw = -jnp.exp(jnp.clip(_rand(22, (B, H, S, dh), jnp.float32, 1.0),
                             -20, 0))
    u = _rand(23, (H, dh), jnp.float32)
    out = scan_ops.wkv6(r, k, v, logw, u, chunk=chunk)
    flat = lambda x: x.reshape(B * H, S, dh)
    uu = jnp.broadcast_to(u[None], (B, H, dh)).reshape(B * H, dh)
    ref = scan_ref.wkv6_reference(flat(r), flat(k), flat(v), flat(logw), uu)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref).reshape(
        B * H, S, dh).reshape(B, H, S, dh), atol=1e-4, rtol=1e-3)
