"""Batched per-request token sampling for the serving engine.

One jitted executable samples every slot of the decode batch at once;
everything request-specific — temperature, top-k, seed, position — arrives
as plain per-slot operands, so the executable never recompiles when the
request mix changes (the same "reprogram, never re-synthesise" contract as
the decode step itself).  The only static input is ``k_cap``, a pow-2
upper bound on the batch's largest top-k: ``jax.lax.top_k`` needs a static
k, and thresholding against the top ``k_cap`` values replaces the old
full-vocab sort (O(V log V) per slot) with O(V · log k_cap) work — at most
O(log V) executables ever exist, one per pow-2 bucket actually requested.

Reproducibility: the PRNG key for a slot is
``fold_in(PRNGKey(seed), token_index)`` — a pure function of the
*request's* seed and how many tokens it has generated, independent of
which slot it landed in, what else is in the batch, or preemption/resume
history.  A seeded request therefore samples the same tokens in any
engine configuration.  Seeds arrive as uint32 (the engine folds wider
request ids / seeds down with ``fold_seed``, so rids >= 2^31 neither
overflow nor collide by truncation alone).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fold_seed(seed: int) -> int:
    """Fold an arbitrary python int into uint32 range (xor-fold of the
    high bits — the identity for seeds in [0, 2^32), so existing seeded
    requests keep their exact token streams).  A negative seed folds to
    the complement of ``-seed - 1``'s fold: its 32-bit two's complement
    down to -2^32."""
    s = int(seed)
    if s < 0:
        return ~fold_seed(~s) & 0xFFFFFFFF
    while s >> 32:
        s = (s >> 32) ^ (s & 0xFFFFFFFF)
    return s


def _sample_one(lg, t, k, s, idx, cap):
    """One token from one logit row — a pure function of (seed, token
    index, logits), which is what makes speculative verification exact:
    the verify path samples position ``j`` with the same ``index`` plain
    decode would have used, so identical logits yield identical tokens."""
    greedy = jnp.argmax(lg).astype(jnp.int32)
    # top-k: keep logits >= the k-th largest (k == 0 -> keep all);
    # the k-th largest comes from a static-size top_k, not a full sort
    kth = jax.lax.top_k(lg, cap)[0][jnp.clip(k, 1, cap) - 1]
    masked = jnp.where((k > 0) & (lg < kth), -jnp.inf, lg)
    key = jax.random.fold_in(jax.random.PRNGKey(s), idx)
    g = jax.random.gumbel(key, lg.shape, lg.dtype)
    sampled = jnp.argmax(masked / jnp.maximum(t, 1e-6) + g)
    return jnp.where(t > 0, sampled.astype(jnp.int32), greedy)


def sample_tokens(logits, temperature, top_k, seed, index, k_cap: int = 0):
    """Sample one token per slot.

    logits: (B, vocab) f32; temperature: (B,) f32 — ``<= 0`` means greedy
    (argmax, the default); top_k: (B,) int32 — ``0`` disables the top-k
    filter; seed: (B,) uint32 per-request PRNG seed; index: (B,) int32
    per-request token index (``len(req.out)``); k_cap: static bound on the
    batch's largest top_k (``0`` -> full vocab — the caller passes the
    pow-2 roundup of ``max(top_k)`` to keep the threshold scan cheap).
    Returns (B,) int32.
    """
    v = logits.shape[-1]
    cap = v if k_cap <= 0 else min(k_cap, v)
    return jax.vmap(
        lambda lg, t, k, s, idx: _sample_one(lg, t, k, s, idx, cap)
    )(logits, temperature, top_k, seed, index)


def verify_tokens(logits, temperature, top_k, seed, index0, k_cap: int = 0):
    """Sample all W verify positions of every slot in one executable.

    logits: (B, W, vocab) f32 from ``transformer.verify_step``; position j
    of slot b samples with token index ``index0[b] + j`` — the index plain
    decode would reach after accepting j tokens — and the request's own
    (temperature, top_k, seed), so the returned (B, W) int32 grid holds,
    at every j, *the* token sequential decoding of the draft prefix would
    emit.  The engine accepts draft token j+1 iff it equals entry j (and
    always emits entry ``n_accepted - 1`` as the bonus/correction token):
    token-identity with plain decode holds for greedy and seeded sampling
    alike, because :func:`_sample_one` is deterministic in
    (seed, index, logits).
    """
    v = logits.shape[-1]
    W = logits.shape[1]
    cap = v if k_cap <= 0 else min(k_cap, v)
    idx = index0[:, None] + jnp.arange(W, dtype=index0.dtype)

    def row(lg, t, k, s, idxs):
        return jax.vmap(
            lambda l, i: _sample_one(l, t, k, s, i, cap))(lg, idxs)

    return jax.vmap(row)(logits, temperature, top_k, seed, idx)
