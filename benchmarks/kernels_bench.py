"""Kernel micro-benchmarks: FAMOUS Pallas kernels (interpret mode — CPU
correctness path) vs their XLA equivalents, plus the analytical VMEM/II
breakdown per module that a real-TPU run would validate."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.core import analytical, famous


def run():
    print("# kernel-level: XLA path timings (CPU) + per-module analytical "
          "v5e breakdown")
    B, SL, D, H = 1, 2048, 1024, 8
    dh = D // H
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (B, SL, D), jnp.float32)
    ws = [jax.random.normal(k, (D, H, dh), jnp.float32) * 0.05
          for k in ks[1:]]
    cfg = famous.FamousConfig(impl="xla")

    @jax.jit
    def qkv(x, wq, wk, wv):
        return famous.qkv_projection(x, wq, wk, wv, cfg=cfg)

    us = common.timeit(qkv, x, *ws)
    common.emit("kernels/qkv_xla", us, f"tokens={B*SL}")

    q, k, v = qkv(x, *ws)

    @jax.jit
    def attn(q, k, v):
        return famous.attention(q, k, v, causal=True, cfg=cfg)

    us = common.timeit(attn, q, k, v)
    common.emit("kernels/attention_xla_flash", us, "")

    # ---- fwd+bwd (training path): flash custom-VJP, XLA vs Pallas --------
    # The Pallas path runs in interpret mode off-TPU, so it gets a smaller
    # topology — this benchmarks the kernel *plumbing* (fwd + dq + dk/dv
    # custom VJP) on CPU; a real-TPU run exercises the compiled kernels.
    Bg, Sg, Hg, KVg, dhg = 1, 512, 4, 2, 64
    kg = jax.random.split(jax.random.PRNGKey(1), 4)
    qg = jax.random.normal(kg[0], (Bg, Sg, Hg, dhg), jnp.float32) * 0.5
    kk = jax.random.normal(kg[1], (Bg, Sg, KVg, dhg), jnp.float32) * 0.5
    vg = jax.random.normal(kg[2], (Bg, Sg, KVg, dhg), jnp.float32) * 0.5
    ct = jax.random.normal(kg[3], (Bg, Sg, Hg, dhg), jnp.float32)

    def make_loss(icfg):
        def loss(q, k, v):
            out = famous.attention(q, k, v, causal=True, cfg=icfg)
            return jnp.sum(out * ct)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    grad_xla = make_loss(famous.FamousConfig(impl="xla", tile_k=128))
    us = common.timeit(grad_xla, qg, kk, vg)
    common.emit("kernels/attention_fwd_bwd_xla", us,
                f"shape={Bg}x{Sg}x{Hg}x{dhg};gqa={Hg//KVg}")

    grad_pl = make_loss(famous.FamousConfig(impl="pallas", tile_q=128,
                                            tile_k=128))
    us = common.timeit(grad_pl, qg, kk, vg, warmup=1, iters=3)
    common.emit("kernels/attention_fwd_bwd_pallas_interpret", us,
                f"shape={Bg}x{Sg}x{Hg}x{dhg};gqa={Hg//KVg}")

    # ---- decode (serving hot path): contiguous vs paged KV cache ---------
    # Same math, two cache layouts: a per-slot (B, Skv, KV, dh) stripe vs a
    # shared page pool addressed through a scalar-prefetched page table.
    Bd, Hd, KVd, dhd, Skv, ps = 4, 8, 4, 64, 1024, 64
    n_p = Skv // ps
    kd = jax.random.split(jax.random.PRNGKey(2), 4)
    qd = jax.random.normal(kd[0], (Bd, 1, Hd, dhd), jnp.float32)
    kc = jax.random.normal(kd[1], (Bd, Skv, KVd, dhd), jnp.float32)
    vc = jax.random.normal(kd[2], (Bd, Skv, KVd, dhd), jnp.float32)
    lens = jnp.asarray([Skv, Skv // 2, 100, 7], jnp.int32)  # mixed residency
    dcfg = famous.FamousConfig(impl="xla")

    @jax.jit
    def dense_decode(q, k, v, lens):
        return famous.decode_attention(q, k, v, lens, cfg=dcfg)

    us = common.timeit(dense_decode, qd, kc, vc, lens)
    common.emit("kernels/decode_contiguous_xla", us, f"skv={Skv};b={Bd}")

    n_pages = 1 + Bd * n_p
    ids = jnp.arange(1, n_pages).reshape(Bd, n_p).astype(jnp.int32)
    kp = jnp.zeros((KVd, n_pages, ps, dhd), jnp.float32).at[:, ids].set(
        kc.reshape(Bd, n_p, ps, KVd, dhd).transpose(3, 0, 1, 2, 4))
    vp = jnp.zeros((KVd, n_pages, ps, dhd), jnp.float32).at[:, ids].set(
        vc.reshape(Bd, n_p, ps, KVd, dhd).transpose(3, 0, 1, 2, 4))

    @jax.jit
    def paged_decode(q, kp, vp, pt, lens):
        return famous.paged_decode_attention(q, kp[None], vp[None], pt, lens, 0,
                                             cfg=dcfg)

    us = common.timeit(paged_decode, qd, kp, vp, ids, lens)
    common.emit("kernels/decode_paged_gather_xla", us,
                f"page={ps};pages={n_pages}")

    pcfg = famous.FamousConfig(impl="pallas")

    @jax.jit
    def paged_decode_pl(q, kp, vp, pt, lens):
        return famous.paged_decode_attention(q, kp[None], vp[None], pt, lens, 0,
                                             cfg=pcfg)

    us = common.timeit(paged_decode_pl, qd, kp, vp, ids, lens,
                       warmup=1, iters=3)
    common.emit("kernels/decode_paged_pallas_interpret", us,
                f"page={ps};pages={n_pages}")

    # ---- int8 quantized pool: same decode, in-kernel dequant -------------
    # The pool shrinks 4x (int8 payload; the per-token-per-head fp32 scale
    # adds 4/dh) — the rows measure what the dequant costs on top of the
    # fp paged path at identical geometry.
    from repro.core import quant as quant_lib
    kq8, kscale = quant_lib.quantize(kp, axis=-1)
    vq8, vscale = quant_lib.quantize(vp, axis=-1)
    kscale, vscale = kscale.swapaxes(-1, -2), vscale.swapaxes(-1, -2)

    @jax.jit
    def paged_decode_q8(q, kp, vp, ks, vs, pt, lens):
        return famous.paged_decode_attention(q, kp[None], vp[None], pt, lens,
                                             0, k_scale=ks[None],
                                             v_scale=vs[None],
                                             cfg=dcfg)

    us = common.timeit(paged_decode_q8, qd, kq8, vq8, kscale, vscale,
                       ids, lens)
    fp_bytes = kp.nbytes + vp.nbytes
    q8_bytes = (kq8.nbytes + vq8.nbytes + kscale.astype(jnp.float32).nbytes
                + vscale.astype(jnp.float32).nbytes)
    common.emit("kernels/decode_paged_int8_gather_xla", us,
                f"page={ps};pages={n_pages};"
                f"bytes_vs_fp={q8_bytes/fp_bytes:.3f}")

    @jax.jit
    def paged_decode_q8_pl(q, kp, vp, ks, vs, pt, lens):
        return famous.paged_decode_attention(q, kp[None], vp[None], pt, lens,
                                             0, k_scale=ks[None],
                                             v_scale=vs[None],
                                             cfg=pcfg)

    us = common.timeit(paged_decode_q8_pl, qd, kq8, vq8, kscale, vscale,
                       ids, lens, warmup=1, iters=3)
    common.emit("kernels/decode_paged_int8_pallas_interpret", us,
                f"page={ps};pages={n_pages}")

    lat = analytical.mha_latency(batch=B, seq=SL, heads=H, kv_heads=H,
                                 head_dim=dh, d_model=D)
    for m in lat.modules:
        common.emit(f"kernels/v5e_pred_{m.name}", m.t_total * 1e6,
                    f"ii_us={m.ii*1e6:.2f};steps={m.steps};"
                    f"vmem_kib={m.vmem_bytes/1024:.0f}")
    tuned = analytical.autotune_tiles(batch=B, seq=SL, heads=H, kv_heads=H,
                                      head_dim=dh, d_model=D)
    common.emit("kernels/v5e_autotuned_total",
                tuned["latency"].total * 1e6, f"tiles={tuned['tiles']}")


if __name__ == "__main__":
    run()
