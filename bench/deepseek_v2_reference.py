"""Plain float32 reference of DeepSeek-V2 (latent attention, a leading
dense layer, routed and shared experts), written from the published
description and ``modeling_deepseek.py``'s semantics, independent of the
program under test.  The plain (non-absorbed) form, with no cache and no
kernel:

    h = embed[tokens]
    per layer:  x = rms(h) * ln1
                q = x Wq -> (q_nope, q_pe) per head
                [c; k_pe] = x Wkv_a;  c = rms(c) * kv_norm
                [k_nope; v] = c Wkv_b per head
                q_pe, k_pe: YaRN RoPE (pairs permuted to halves, rotate-half)
                h = h + softmax((q_nope.k_nope + q_pe.k_pe) * scale, causal)
                        v Wo
                x = rms(h) * ln2
                dense layer: h = h + (silu(x Wgate) * (x Wup)) Wdown
                MoE layer:   p = softmax(x Wrouter) over every expert (f32);
                             top-k, not renormalised, times the routed scale;
                             h = h + sum over the held experts e in the top k
                                 of p_e E_e(x) + S(x)  (S: shared experts)
    logits = (rms(h) * final_norm) Wlm

Only the experts the configuration holds take part (the chip's share of
the deployment).  Weights come from ``bench/deepseek_v2_weights.py``,
drawn layer by layer (expert by expert) in the served type and widened to
float32; attention runs in query blocks so that a 16.9k-token sequence
fits.  Every matrix product runs at ``Precision.HIGHEST``.  The controls
(``"fp8"``, ``"int8"``) round every weight matrix and its input, as
``bench/reference.py`` does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import deepseek_v2_weights as W
from bench.reference import HI, _gaps, _lin

Q_BLOCK = 512       # query rows of one attention block


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_inv_freq(m):
    """DeepseekV2YarnRotaryEmbedding's frequencies, float32 (rope/2,)."""
    dim, base, factor = m["rope"], m["rope_theta"], m["yarn_factor"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))

    def corr_dim(rot):
        return (dim * math.log(m["yarn_original"] / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(corr_dim(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr_dim(m["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return (freq_inter * (1 - inv_freq_mask)
            + freq_extra * inv_freq_mask).astype(np.float32)


def _softmax_scale(m):
    def mscale(scale, ms):
        return 0.1 * ms * math.log(scale) + 1.0 if scale > 1 else 1.0
    s = (m["nope"] + m["rope"]) ** -0.5
    ms = mscale(m["yarn_factor"], m["yarn_mscale_all_dim"])
    return s * ms * ms


def _rope(x, inv_freq):
    """x (B, S, ..., dim) at positions 0..S-1: the published code's
    de-interleave of pairs (2i, 2i+1) to halves, then rotate-half with
    (mscale-ratio 1) cos/sin."""
    S, dim = x.shape[1], x.shape[-1]
    x = x.reshape(x.shape[:-1] + (dim // 2, 2))
    x = jnp.concatenate([x[..., 0], x[..., 1]], -1)
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([freqs, freqs], -1)           # (S, dim)
    emb = emb.reshape((1, S) + (1,) * (x.ndim - 3) + (dim,))
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    half = dim // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _attention(q_nope, q_pe, k_nope, k_pe, v, scale):
    """One sequence: q_nope/k_nope (S, H, nope), q_pe (S, H, rope), k_pe
    (S, rope), v (S, H, dv) -> (S, H * dv), causal, in query blocks."""
    S, H = q_nope.shape[:2]
    qb = min(Q_BLOCK, S)
    nb = S // qb

    def block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * qb, qb)   # noqa: E731
        s = (jnp.einsum("qhn,khn->hqk", sl(q_nope), k_nope, precision=HI)
             + jnp.einsum("qhr,kr->hqk", sl(q_pe), k_pe, precision=HI))
        s = s * scale
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(S)[None, None] <= qpos[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(nb))               # (nb, Q, H, dv)
    return o.reshape(S, -1)


def _swiglu(x, wg, wu, wd, control):
    return _lin(jax.nn.silu(_lin(x, wg, control)) * _lin(x, wu, control), wd,
                control)


@functools.lru_cache(maxsize=None)
def _layer_fn(mk: tuple, served, control, dense: bool):
    m = dict(mk)
    inv_freq = jnp.asarray(_yarn_inv_freq(m))
    scale = _softmax_scale(m)

    def layer(seeds, idx, h):
        w = lambda n: W.draw(seeds, m, n, served, idx).astype(   # noqa: E731
            jnp.float32)
        d, H, r = m["d"], m["h"], m["rank"]
        nope, rope, dv = m["nope"], m["rope"], m["dv"]
        B, S = h.shape[:2]
        x = _rms(h, w("ln1"), m["eps"])
        q = _lin(x, w("wq").reshape(d, -1), control).reshape(B, S, H, -1)
        kv = _lin(x, w("wkv_a"), control)
        c = _rms(kv[..., :r], w("kv_norm"), m["eps"])
        kvb = _lin(c, w("wkv_b").reshape(r, -1), control).reshape(B, S, H, -1)
        q_pe = _rope(q[..., nope:], inv_freq)
        k_pe = _rope(kv[..., r:], inv_freq)

        def one(args):
            return _attention(*args, scale)

        o = jax.lax.map(one, (q[..., :nope], q_pe, kvb[..., :nope], k_pe,
                              kvb[..., nope:]))
        h = h + _lin(o, w("wo").reshape(H * dv, d), control)
        x = _rms(h, w("ln2"), m["eps"])
        if dense:
            return h + _swiglu(x, w("w_gate"), w("w_up"), w("w_down"),
                               control)
        probs = jax.nn.softmax(_lin(x, w("router"), control), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, m["top_k"])
        top_p = top_p * m["routed_scale"]
        y = _swiglu(x, w("s_gate"), w("s_up"), w("s_down"), control)

        def expert(y, e):
            g = jnp.sum(jnp.where(top_i == e, top_p, 0.0), -1)[..., None]
            we = lambda n: W.draw_expert(seeds, m, n, served, idx,   # noqa: E731
                                         e).astype(jnp.float32)
            return y + g * _swiglu(x, we("e_gate"), we("e_up"),
                                   we("e_down"), control), None

        y, _ = jax.lax.scan(expert, y, jnp.asarray(m["held"], jnp.int32))
        return h + y

    return jax.jit(layer, donate_argnums=2)


@functools.lru_cache(maxsize=None)
def _embed_fn(mk: tuple, served):
    m = dict(mk)
    return jax.jit(lambda seeds, toks: W.draw_rows(
        seeds, m, "embed", toks, served).astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _head_fn(mk: tuple, served, control):
    m = dict(mk)

    def head(seeds, h, rows):
        x = h.reshape(-1, h.shape[-1])[rows]
        x = _rms(x, W.draw(seeds, m, "final_norm", served).astype(jnp.float32),
                 m["eps"])
        return _lin(x, W.draw(seeds, m, "lm_head", served).astype(jnp.float32),
                    control)

    return jax.jit(head)


def logits_at(m: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
              served=jnp.bfloat16, control=None):
    """float32 logits (len(rows), vocab) of the reference forward over
    ``tokens`` (B, S) int32, at flat positions ``rows`` (b * S + s).  A
    sequence longer than :data:`Q_BLOCK` is padded at its end to whole
    blocks (causal: the pad changes no earlier position)."""
    mk = tuple(sorted(m.items()))
    served = jnp.dtype(served)
    seeds = jnp.asarray(W.seed_words(seed))
    tokens = np.asarray(tokens, np.int32)
    S = tokens.shape[1]
    pad = -S % min(Q_BLOCK, S)
    h = _embed_fn(mk, served)(seeds, jnp.asarray(
        np.pad(tokens, ((0, 0), (0, pad)))))
    for i in range(m["layers"]):
        dense = i < m["dense_layers"]
        h = _layer_fn(mk, served, control, dense)(seeds, jnp.int32(i), h)
    b, s = np.divmod(np.asarray(rows), S)
    return _head_fn(mk, served, control)(
        seeds, h, jnp.asarray(b * (S + pad) + s, jnp.int32))


def score(m: dict, seed: int, items: list, s_pad: int, b_pad: int,
          n_score: int, dtype=jnp.bfloat16, controls=()) -> dict:
    """Judge served tokens against the reference; the interface and the
    sampling of scored tokens are ``bench/reference.py``'s ``score``."""
    if len(items) > b_pad:
        raise ValueError(f"{len(items)} requests to score, room for {b_pad}")
    toks = np.zeros((b_pad, s_pad), np.int32)
    for b, (prompt, out) in enumerate(items):
        seq = list(prompt) + list(out[:-1])
        if len(seq) > s_pad:
            raise ValueError(f"a sequence of {len(seq)} tokens, room for "
                             f"{s_pad}")
        toks[b, :len(seq)] = seq
    turns = sorted((j, b) for b, (_, out) in enumerate(items)
                   for j in range(len(out)))[:n_score]
    rows = [b * s_pad + len(items[b][0]) - 1 + j for j, b in turns]
    served = [items[b][1][j] for j, b in turns]
    n = len(rows)
    rows = np.array(rows + [0] * (n_score - n), np.int32)
    served = np.array(served + [0] * (n_score - n), np.int32)
    ref = logits_at(m, seed, toks, rows, dtype)
    out = {"gap": np.asarray(_gaps(ref, jnp.asarray(served)))[:n], "n": n,
           "control_gap": {}}
    for name in controls:
        ctl = logits_at(m, seed, toks, rows, dtype, control=name)
        first = jnp.argmax(ctl, -1).astype(jnp.int32)
        out["control_gap"][name] = np.asarray(_gaps(ref, first))[:n]
    return out
