"""Token-choice top-k Mixture-of-Experts FFNs.

Two layers.  The serving layer (:func:`apply_moe_serving`, configs with
``held_experts``: deepseek-v2-lite) is dropless: it routes over every
expert, computes only the experts this chip holds, with a grouped matmul
over the (token, expert) pairs sorted by expert, and a token's output never
depends on its batch-mates.  The training layer (:func:`apply_moe`,
grok-1, kimi-k2) is capacity-based:

GShard/GSPMD-style *grouped dense dispatch*: tokens are split into G groups;
each group dispatches into a per-group expert buffer of capacity C via a
(G, S_g, E, C) one-hot einsum.  Everything is dense einsums, which GSPMD
partitions perfectly (groups on the dp axes, experts on "model" = expert
parallelism with all-to-all routing inserted by XLA).  A scatter/gather
formulation was tried first and rejected: GSPMD replicates scatter operands,
costing ~190 GiB/device on grok-1 in a dry-run compile (a compile's
figure; PERF.md holds what was measured on the chip).

Capacity inflation is bounded: C = ceil(cf · S_g · K / E) per group, so the
buffer is cf·K·T token-slots globally.  Tokens beyond an expert's capacity
within their group are dropped (standard GShard semantics); priority is
earlier-rank choice first, then sequence order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.module import ParamSpec
from repro.parallel.incontext import constrain


def moe_spec(cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        # Router stays REPLICATED (K1): sharding its tiny (d, E) matrix over
        # "model" forced a (G,S,E) all-gather before top_k plus a ~2 GiB dx
        # all-reduce per layer — 8 s/step on kimi-k2 for a 2.7M-param matmul.
        "router": ParamSpec((d, e), ("embed", None), scale=0.02,
                            dtype=jnp.float32),
        # Expert weights: experts on "model" (EP), d_ff on "data" (K2) —
        # the FSDP gather of the d_model dim moved 4x more bytes than the
        # partial-sum all-reduce this layout pays on the down-projection.
        "w_in": ParamSpec((e, d, f), ("experts", None, "expert_ff")),
        "w_gate": ParamSpec((e, d, f), ("experts", None, "expert_ff")),
        "w_out": ParamSpec((e, f, d), ("experts", "expert_ff", None)),
    }


def _group_shape(T: int, target_group: int = 256) -> tuple[int, int]:
    """Split T tokens into (G, S_g) with T = G*S_g and S_g ~ target."""
    sg = min(target_group, T)
    while T % sg:
        sg -= 1
    return T // sg, sg


def router_dispatch(logits: jax.Array, K: int, capacity_factor: float,
                    softcap: float = 0.0):
    """logits: (G, S, E) f32.  Returns (dispatch (G,S,E,C) bf16,
    combine (G,S,E,C) f32, aux metrics)."""
    G, S, E = logits.shape
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    C = max(4, int(-(-capacity_factor * S * K // E)))
    C = min(C, S)
    gates = jax.nn.softmax(logits, axis=-1)               # (G,S,E)
    topw, topi = jax.lax.top_k(logits, K)                 # (G,S,K)
    topw = jax.nn.softmax(topw, axis=-1)

    running = jnp.zeros((G, 1, E), jnp.int32)             # used capacity
    dispatch = jnp.zeros((G, S, E, C), jnp.bfloat16)
    combine = jnp.zeros((G, S, E, C), jnp.float32)
    for k in range(K):
        mask_k = jax.nn.one_hot(topi[..., k], E, dtype=jnp.int32)   # (G,S,E)
        pos_k = running + jnp.cumsum(mask_k, axis=1) - mask_k       # (G,S,E)
        keep = (pos_k < C) & (mask_k > 0)
        oh = jax.nn.one_hot(jnp.where(keep, pos_k, C), C + 1,
                            dtype=jnp.bfloat16)[..., :C]            # (G,S,E,C)
        oh = oh * mask_k[..., None].astype(jnp.bfloat16)
        dispatch = dispatch + oh
        combine = combine + oh.astype(jnp.float32) * topw[..., k, None, None]
        running = running + jnp.sum(mask_k, axis=1, keepdims=True)
    frac = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32), axis=(0, 1, 2))
    aux = E * jnp.sum(frac * gates.mean((0, 1)))          # load-balance loss
    return dispatch, combine, aux


def apply_moe(p: dict, x: jax.Array, cfg: ModelConfig,
              return_aux: bool = False):
    """x: (B, S, D) -> (B, S, D).  Grouped dense top-k routing."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    G, Sg = _group_shape(T)
    xg = constrain(x.reshape(G, Sg, D), ("batch", None, None))

    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), p["router"])
    logits = constrain(logits, ("batch", None, None))
    dispatch, combine, aux = router_dispatch(
        logits, K, cfg.capacity_factor, cfg.logit_softcap)
    dispatch = constrain(dispatch, ("batch", None, "experts", None))

    buf = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)
    buf = constrain(buf, ("batch", "experts", None, None))
    h_in = jnp.einsum("gecd,edf->gecf", buf, p["w_in"].astype(x.dtype))
    h_gate = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(x.dtype))
    h = layers.act_fn(cfg.act)(h_gate) * h_in
    # named for the MoE remat policy (K3): saving h/out_buf stops the remat
    # pass from re-all-gathering every expert weight a second time.
    h = checkpoint_name(h, "moe_hidden")
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["w_out"].astype(x.dtype))
    out_buf = constrain(out_buf, ("batch", "experts", None, None))
    out_buf = checkpoint_name(out_buf, "moe_out")

    y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), out_buf)
    y = y.reshape(B, S, D)
    if return_aux:
        return y, aux
    return y


def aux_load_balance_loss(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Switch-style load-balancing auxiliary loss (mean fraction * prob)."""
    B, S, D = x.shape
    T = B * S
    G, Sg = _group_shape(T)
    xg = x.reshape(G, Sg, D)
    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), p["router"])
    _, _, aux = router_dispatch(logits, cfg.experts_per_token,
                                cfg.capacity_factor, cfg.logit_softcap)
    return aux


# ---------------------------------------------------------------------------
# dropless serving layer over the held experts
# ---------------------------------------------------------------------------


def serving_moe_spec(cfg: ModelConfig) -> dict:
    """Router over all ``num_experts``; weights of the held experts only
    (in ``held_experts`` order); shared experts as one gated MLP of
    ``n_shared_experts * expert_d_ff``."""
    d, f, n = cfg.d_model, cfg.expert_d_ff, len(cfg.held_experts)
    spec = {
        "router": ParamSpec((d, cfg.num_experts), ("embed", None)),
        "w_in": ParamSpec((n, d, f), ("experts", None, "expert_ff")),
        "w_gate": ParamSpec((n, d, f), ("experts", None, "expert_ff")),
        "w_out": ParamSpec((n, f, d), ("experts", "expert_ff", None)),
    }
    if cfg.n_shared_experts:
        spec["shared"] = layers.mlp_spec(d, f * cfg.n_shared_experts, cfg.act)
    return spec


def route(router, x, cfg: ModelConfig):
    """Softmax over every expert in float32, the top ``experts_per_token``
    with their probabilities (not renormalised) times ``routed_scale``.
    x: (T, D) -> (weights (T, K) f32, expert ids (T, K) int32)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                           cfg.experts_per_token)
    return w * cfg.routed_scale, idx


# Largest expert-weight tile the grouped matmul streams per grid step.
GMM_TILE_BYTES = 3 << 20
GMM_ROWS = 32    # rows of (token, expert) pairs per tile


def _gmm_tiling(k: int, n: int, itemsize: int) -> tuple:
    """(rows, k, n) tile of megablox's grouped matmul: k in 512-wide tiles
    where it divides (else whole), n whole, or halved while the weight
    tile exceeds :data:`GMM_TILE_BYTES` (halves stay 128-aligned)."""
    tk = 512 if k % 512 == 0 else k
    tn = n
    while tk * tn * itemsize > GMM_TILE_BYTES and tn % 256 == 0:
        tn //= 2
    return GMM_ROWS, tk, tn


def grouped_matmul(lhs, rhs, group_sizes, impl: str):
    """Rows of ``lhs`` (M, K), sorted by group, times their group's matrix
    of ``rhs`` (G, K, N); rows past ``sum(group_sizes)`` are left
    undefined.  impl="pallas": megablox's ``gmm`` kernel, which visits
    only the groups that have rows; else ``jax.lax.ragged_dot``."""
    if impl != "pallas":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    tiling = _gmm_tiling(rhs.shape[1], rhs.shape[2], rhs.dtype.itemsize)
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=tiling)


def apply_moe_serving(p: dict, x: jax.Array, cfg: ModelConfig,
                      impl: str = "xla", valid=None):
    """x: (B, S, D) -> (y (B, S, D), counts (n_held + 2,) int32).

    y = sum over the token's top-k experts that this chip holds of
    p_e * E_e(x), plus the shared experts.  Dropless: each (token, expert)
    pair is one row of the grouped matmul, sorted by held expert; pairs
    for experts held elsewhere, and every pair of a token that is not
    ``valid`` (B, S) (a free slot, a prefill chunk's pad), sort past the
    last group and are not computed.  ``counts``: the rows each held
    expert computed, then the pairs routed (valid tokens x k, held or
    not), then the held experts with a row (whose weights the layer
    reads)."""
    B, S, D = x.shape
    K, held = cfg.experts_per_token, cfg.held_experts
    n = len(held)
    xt = x.reshape(B * S, D)
    w, idx = route(p["router"], xt, cfg)
    local = np.full((cfg.num_experts,), n, np.int32)
    local[list(held)] = np.arange(n, dtype=np.int32)
    e = jnp.asarray(local)[idx]                               # (T, K)
    ok = (jnp.ones((B * S,), bool) if valid is None
          else valid.reshape(B * S))
    e = jnp.where(ok[:, None], e, n).reshape(-1)              # (T*K,)
    M = -(-e.shape[0] // GMM_ROWS) * GMM_ROWS
    e = jnp.pad(e, (0, M - e.shape[0]), constant_values=n)
    order = jnp.argsort(e, stable=True)
    e_sorted = e[order]
    tok = jnp.minimum(order // K, B * S - 1)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[e].add(1)[:n]
    rows = xt[tok]
    h = (layers.act_fn(cfg.act)(grouped_matmul(rows, p["w_gate"], sizes, impl))
         * grouped_matmul(rows, p["w_in"], sizes, impl))
    out = grouped_matmul(h, p["w_out"], sizes, impl)
    wt = jnp.pad(w.reshape(-1), (0, M - w.size))[order]
    out = jnp.where((e_sorted < n)[:, None],
                    out.astype(jnp.float32) * wt[:, None], 0.0)
    y = jnp.zeros((B * S, D), jnp.float32).at[tok].add(out)
    y = y.astype(x.dtype).reshape(B, S, D)
    if "shared" in p:
        y = y + layers.apply_mlp(p["shared"], x, cfg.act)
    counts = jnp.concatenate([sizes, jnp.stack([ok.sum() * K,
                                                 (sizes > 0).sum()]
                                                ).astype(jnp.int32)])
    return y, counts
