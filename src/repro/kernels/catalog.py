"""The serving main path's Pallas kernels at one model's widths.

Each :class:`KernelCase` pairs a Pallas launch (always compiled, never
interpreted) with its XLA/jnp oracle over one operand tuple.  Two consumers
share the list:

* ``tests/test_tpu_compile.py`` compiles every case ahead of time for a
  described v5e chip, from the operands' shapes alone
  (``jax.eval_shape(case.make, key)``), so Mosaic's tiling rules are
  checked on every PR without a chip;
* ``chip_smoke.py`` runs every case on the chip with seeded operands and
  compares it with its oracle.

:func:`latent_cases` holds the latent-attention (MLA) model's kernels —
its paged decode kernel and the held-expert grouped matmul — at its own
widths.  Shapes are those of the engine ``chip_smoke.py`` serves, which
imports the sizes below: ``SLOTS`` slots of ``MAX_SEQ`` tokens in ``PAGE``-token
pages (a pool of ``SLOTS * MAX_SEQ / PAGE`` pages plus the null page), one
``CHUNK``-token prefill chunk, one decode token per slot, in bf16.  The
paged kernels read the last layer of a ``LAYERS``-layer stack of pools, as
the layer scan hands them theirs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import quant as quant_lib
from repro.kernels.attention import mha, ref as attn_ref
from repro.kernels.decode import decode_attn, ops as dec_ops
from repro.kernels.decode import ref as dec_ref
from repro.kernels.qkv import qkv_proj

SLOTS, MAX_SEQ, PAGE, CHUNK = 4, 2048, 16, 32
LAYERS = 2
DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    make: Callable        # PRNG key -> operand tuple
    kernel: Callable      # (*operands) -> output(s), compiled Pallas
    reference: Callable   # (*operands) -> output(s), XLA oracle


def _int8_pool(key, shape):
    """Symmetric per-token int8 quantization of a normal pool: (int8 pool
    (..., n_pages, ps, dh), fp32 scale rows (..., n_pages, 1, ps))."""
    q, s = quant_lib.quantize(jax.random.normal(key, shape), axis=-1)
    return q, s.swapaxes(-1, -2)


def _mha_reference_lse(q, k, v):
    """``mha_reference`` plus the causal row log-sum-exp the flash forward
    emits as its backward residual."""
    group = q.shape[0] // k.shape[0]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   jnp.repeat(k, group, axis=0).astype(jnp.float32))
    s = s / math.sqrt(q.shape[-1])
    n = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
    return attn_ref.mha_reference(q, k, v, causal=True), lse


def main_path_cases(cfg: ModelConfig) -> list[KernelCase]:
    """Every kernel the paged, chunked-prefill serving path (and the
    window-ring and training paths next to it) launches, at ``cfg``'s
    widths and the module's serving sizes."""
    KV, H, dh = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    group = H // KV
    slots, page_size, chunk, S = SLOTS, PAGE, CHUNK, MAX_SEQ
    pages_per_slot = S // page_size
    n_pages = 1 + slots * pages_per_slot
    D = cfg.d_model
    F = (H + 2 * KV) * dh                     # fused [Wq|Wk|Wv] width
    pool = (LAYERS, KV, n_pages, page_size, dh)
    layer = jnp.int32(LAYERS - 1)

    def table(key):
        ids = jax.random.permutation(key, jnp.arange(1, n_pages))
        return ids.reshape(slots, pages_per_slot).astype(jnp.int32)

    def lens(key, n):
        return jax.random.randint(key, (n,), 1, S + 1, jnp.int32)

    def normal(key, shape):
        return jax.random.normal(key, shape).astype(DTYPE)

    def paged_decode(key):
        ks = jax.random.split(key, 5)
        return (normal(ks[0], (slots, KV, group, dh)), normal(ks[1], pool),
                normal(ks[2], pool), table(ks[3]), lens(ks[4], slots), layer)

    def paged_decode_int8(key):
        ks = jax.random.split(key, 5)
        kq, kscale = _int8_pool(ks[1], pool)
        vq, vscale = _int8_pool(ks[2], pool)
        return (normal(ks[0], (slots, KV, group, dh)), kq, vq, kscale,
                vscale, table(ks[3]), lens(ks[4], slots), layer)

    def offset(key):      # a chunk-aligned offset inside the slot
        return chunk * jax.random.randint(key, (), 0, S // chunk, jnp.int32)

    def paged_chunk(key):
        ks = jax.random.split(key, 5)
        return (normal(ks[0], (1, chunk, H, dh)), normal(ks[1], pool),
                normal(ks[2], pool), table(ks[3])[:1], offset(ks[4]), layer)

    def paged_chunk_int8(key):
        ks = jax.random.split(key, 5)
        kq, kscale = _int8_pool(ks[1], pool)
        vq, vscale = _int8_pool(ks[2], pool)
        return (normal(ks[0], (1, chunk, H, dh)), kq, vq, kscale, vscale,
                table(ks[3])[:1], offset(ks[4]), layer)

    def decode(key):
        ks = jax.random.split(key, 4)
        rows = slots * KV
        return (normal(ks[0], (rows, group, dh)),
                normal(ks[1], (rows, S, dh)), normal(ks[2], (rows, S, dh)),
                lens(ks[3], rows))

    def mha_fwd(key):
        ks = jax.random.split(key, 3)
        return (normal(ks[0], (H, S, dh)), normal(ks[1], (KV, S, dh)),
                normal(ks[2], (KV, S, dh)))

    def matmul(key):
        ks = jax.random.split(key, 2)
        return normal(ks[0], (chunk, D)), normal(ks[1], (D, F))

    def matmul_int8(key):
        x, w = matmul(key)
        xq, sx = quant_lib.quantize(x, axis=1)
        wq, sw = quant_lib.quantize(w, axis=0)
        return xq, wq, sx, sw

    def int8_matmul_ref(xq, wq, sx, sw):
        acc = jnp.dot(xq.astype(jnp.int32), wq.astype(jnp.int32))
        return acc.astype(jnp.float32) * sx * sw

    return [
        KernelCase("paged_decode", paged_decode,
                   decode_attn.paged_decode_attention,
                   dec_ref.paged_decode_reference),
        KernelCase("paged_decode_int8", paged_decode_int8,
                   decode_attn.paged_decode_attention_int8,
                   dec_ref.paged_decode_reference_int8),
        KernelCase("paged_chunk_prefill", paged_chunk,
                   lambda *a: dec_ops.paged_chunk_prefill_attention(
                       *a, interpret=False),
                   dec_ref.paged_chunk_prefill_reference),
        KernelCase("paged_chunk_prefill_int8", paged_chunk_int8,
                   lambda *a: dec_ops.paged_chunk_prefill_attention_int8(
                       *a, interpret=False),
                   dec_ref.paged_chunk_prefill_reference_int8),
        KernelCase("decode", decode, decode_attn.decode_attention,
                   dec_ref.decode_reference),
        KernelCase("mha_forward_lse", mha_fwd,
                   lambda q, k, v: mha.mha_forward(q, k, v, causal=True,
                                                   return_lse=True),
                   _mha_reference_lse),
        KernelCase("matmul_tiled", matmul, qkv_proj.matmul_tiled,
                   lambda x, w: jnp.dot(x, w,
                                        preferred_element_type=jnp.float32)),
        KernelCase("matmul_tiled_int8", matmul_int8,
                   qkv_proj.matmul_tiled_int8, int8_matmul_ref),
    ]



def latent_cases(cfg: ModelConfig) -> list[KernelCase]:
    """The latent-attention model's kernels at ``cfg``'s widths: the paged
    latent decode over ``SLOTS`` slots of ``MAX_SEQ`` tokens (rows stored
    lane-padded), and one grouped matmul of a decode step's (token,
    expert) rows over the held experts' up projection."""
    from repro.models import mla, moe
    H, r, width = cfg.num_heads, cfg.kv_lora_rank, mla.stored_width(cfg)
    slots, page_size, S = SLOTS, PAGE, MAX_SEQ
    pages_per_slot = S // page_size
    n_pages = 1 + slots * pages_per_slot
    scale = mla.softmax_scale(cfg)
    E, D, F = len(cfg.held_experts), cfg.d_model, cfg.expert_d_ff
    rows = -(-SLOTS * cfg.experts_per_token // moe.GMM_ROWS) * moe.GMM_ROWS

    def latent_decode(key):
        ks = jax.random.split(key, 4)
        ids = jax.random.permutation(ks[2], jnp.arange(1, n_pages))
        return (jax.random.normal(ks[0], (slots, 1, H, width)).astype(DTYPE),
                jax.random.normal(ks[1], (LAYERS, 1, n_pages, page_size,
                                          width)).astype(DTYPE),
                ids.reshape(slots, pages_per_slot).astype(jnp.int32),
                jax.random.randint(ks[3], (slots,), 1, S + 1, jnp.int32),
                jnp.int32(LAYERS - 1))

    def grouped(key):
        ks = jax.random.split(key, 3)
        sizes = jax.random.multinomial(ks[2], rows // 2,
                                       jnp.full((E,), 1.0 / E)
                                       ).astype(jnp.int32)
        return (jax.random.normal(ks[0], (rows, D)).astype(DTYPE),
                jax.random.normal(ks[1], (E, D, F)).astype(DTYPE), sizes)

    def ragged_ref(lhs, rhs, sizes):
        out = jax.lax.ragged_dot(lhs, rhs, sizes,
                                 preferred_element_type=jnp.float32)
        live = jnp.arange(lhs.shape[0])[:, None] < sizes.sum()
        return jnp.where(live, out, 0.0).astype(lhs.dtype)

    def gmm(lhs, rhs, sizes):
        live = jnp.arange(lhs.shape[0])[:, None] < sizes.sum()
        return jnp.where(live, moe.grouped_matmul(lhs, rhs, sizes, "pallas"),
                         0.0)

    return [
        KernelCase("mla_decode", latent_decode,
                   lambda *a: decode_attn.mla_decode_attention(
                       *a, scale=scale, rank=r),
                   lambda *a: dec_ref.mla_decode_reference(
                       *a, scale=scale, rank=r)),
        KernelCase("moe_gmm", grouped, gmm, ragged_ref),
    ]
