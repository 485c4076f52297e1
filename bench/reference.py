"""Plain float32 reference of a dense pre-norm decoder (Qwen2, DeepSeek LLM
and the Llama block layout they share), written from the published
description and independent of the program under test.

    h = embed[tokens]
    per layer:  x = rms(h) * ln1
                q, k, v = x Wq (+bq), x Wk (+bk), x Wv (+bv); RoPE on q, k
                h = h + softmax(q k^T / sqrt(dh), causal) v Wo
                x = rms(h) * ln2
                h = h + (silu(x Wgate) * (x Wup)) Wdown
    logits = (rms(h) * final_norm) Wlm

Weights come from ``bench/weights.py`` (the benchmark's own seeded
generator), drawn layer by layer in the type the program serves them in
(bfloat16 on the chip) and widened to float32, so the reference holds one layer at a time and fits on the chip
beside nothing else.  Every matrix product runs at
``Precision.HIGHEST``.

``control="fp8"`` (or ``"int8"``) computes the same forward with every
weight matrix and its input rounded to that type (symmetric scales per
output channel and per token): the steps below the configuration's bf16
that a later change would be tempted to take.  The comparison must
reject the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST


FP8_MAX = 448.0     # largest finite float8_e4m3fn


def _rounded(x, axis, control):
    """``x`` rounded to the control's type with one scale per slice
    along ``axis`` (its absolute maximum maps to the type's largest)."""
    top = 127.0 if control == "int8" else FP8_MAX
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if control == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _lin(x, w, control):
    """x (..., d_in) @ w (d_in, d_out) in float32."""
    if control:
        x, w = _rounded(x, -1, control), _rounded(w, 0, control)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, n, dh), rotate-half convention, positions 0..S-1."""
    S, dh = x.shape[0], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, m):
    """One sequence: q (S, H, dh), k/v (S, KV, dh) -> (S, H*dh)."""
    S, H, dh = q.shape
    kv = k.shape[1]
    q = q.reshape(S, kv, H // kv, dh)
    s = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI) / np.sqrt(dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI)
    return o.reshape(S, H * dh)


@functools.lru_cache(maxsize=None)
def _layer_fn(mk: tuple, served, control):
    m = dict(mk)

    def layer(seeds, idx, h):
        w = {n: W.draw(seeds, m, n, served, idx).astype(jnp.float32)
             for n in W.LAYER_TENSORS if W.tensor_spec(m, n) is not None}
        d, H, kv, dh = m["d"], m["h"], m["kv"], m["dh"]
        x = _rms(h, w["ln1"], m["eps"])
        q = _lin(x, w["wq"].reshape(d, H * dh), control)
        k = _lin(x, w["wk"].reshape(d, kv * dh), control)
        v = _lin(x, w["wv"].reshape(d, kv * dh), control)
        if m["qkv_bias"]:
            q = q + w["bq"].reshape(-1)
            k = k + w["bk"].reshape(-1)
            v = v + w["bv"].reshape(-1)
        B, S = h.shape[:2]
        q = q.reshape(B, S, H, dh)
        k = k.reshape(B, S, kv, dh)
        v = v.reshape(B, S, kv, dh)

        def one(args):
            qb, kb, vb = args
            return _attend(_rope(qb, m["rope_theta"]),
                           _rope(kb, m["rope_theta"]), vb, m)

        o = jax.lax.map(one, (q, k, v))
        h = h + _lin(o, w["wo"].reshape(H * dh, d), control)
        x = _rms(h, w["ln2"], m["eps"])
        a = jax.nn.silu(_lin(x, w["w_gate"], control)) * _lin(x, w["w_up"],
                                                              control)
        return h + _lin(a, w["w_down"], control)

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
    ``tokens`` (B, S) int32, at flat positions ``rows`` (b * S + s), with
    the weights as the program serves them in ``served``."""
    mk = tuple(sorted(m.items()))
    served = jnp.dtype(served)
    seeds = jnp.asarray(W.seed_words(seed))
    h = _embed_fn(mk, served)(seeds, jnp.asarray(tokens, jnp.int32))
    layer = _layer_fn(mk, served, control)
    for i in range(m["layers"]):
        h = layer(seeds, jnp.int32(i), h)
    return _head_fn(mk, served, control)(seeds, h,
                                         jnp.asarray(rows, jnp.int32))


@jax.jit
def _gaps(ref, served):
    """Reference best logit minus the reference logit of ``served``."""
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]


def score(m: dict, seed: int, items: list, s_pad: int, b_pad: int,
          n_score: int, dtype=jnp.bfloat16, controls=()) -> dict:
    """Judge served tokens against the reference.

    ``items``: (prompt tokens, served tokens) pairs, at most ``b_pad`` of
    them, each with ``len(prompt) + len(served) - 1 <= s_pad``; at most
    ``n_score`` served tokens in all are scored, taken from the requests
    in turn (token 0 of each, then token 1, ...) so that every request
    has its share (the shapes are fixed so that every run uses one
    compiled program).  The logit of served token j comes from the
    position before it, as in decoding.

    Returns numpy arrays over the scored tokens: ``gap`` (reference best
    logit minus the reference logit of the served token, >= 0) and, for
    each control in ``controls``, ``control_gap[name]``: the same for the
    token that control puts first at that position."""
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
