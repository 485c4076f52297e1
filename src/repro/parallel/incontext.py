"""In-graph sharding constraints from logical axis names.

``constrain(x, axes)`` applies ``with_sharding_constraint`` using the ambient
mesh (the ``jax.set_mesh`` context the launcher jits under) and the same
divisibility-aware rule resolution as parallel/sharding.py.  No-op when no
mesh is active (CPU smoke tests) so model code stays mesh-agnostic.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax

from repro.parallel import sharding as shd

# Active rule set for in-graph constraints; launchers that lower with
# non-default rules set this so model-internal constraints agree.
_ACTIVE_RULES: contextvars.ContextVar = contextvars.ContextVar(
    "repro_sharding_rules", default=None)


@contextlib.contextmanager
def use_rules(rules):
    tok = _ACTIVE_RULES.set(rules)
    try:
        yield
    finally:
        _ACTIVE_RULES.reset(tok)


def _ambient_mesh():
    """The mesh set by ``jax.set_mesh`` / ``jax.sharding.use_mesh``, or
    None outside any."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def constrain(x, axes, rules=None):
    """x: array; axes: logical axis name per dim (None = unsharded)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    rules = rules or _ACTIVE_RULES.get()
    spec = shd.spec_for_axes(mesh, axes, rules, tuple(x.shape))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _reshard2(x, fwd_axes, bwd_axes, rules):
    return constrain(x, fwd_axes, rules)


def _reshard2_fwd(x, fwd_axes, bwd_axes, rules):
    return constrain(x, fwd_axes, rules), None


def _reshard2_bwd(fwd_axes, bwd_axes, rules, _, g):
    return (constrain(g, bwd_axes, rules),)


_reshard2.defvjp(_reshard2_fwd, _reshard2_bwd)


def reshard_fwd_bwd(x, fwd_axes, bwd_axes, rules=None):
    """Constrain the primal to ``fwd_axes`` and its cotangent to
    ``bwd_axes``.  Used where the value and its gradient want different
    layouts (e.g. K/V replicated across "model" for context-parallel
    attention, but dK/dV reduce-scattered to sequence shards)."""
    return _reshard2(x, tuple(fwd_axes), tuple(bwd_axes), rules)


def heads_divide_model(num_heads: int) -> bool:
    """True when head-TP is exact on the ambient mesh (or no mesh active)."""
    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return True
    return num_heads % mesh.shape["model"] == 0


def constrain_residual(x, num_heads: int, rules=None):
    """Sequence-parallel residual stream for non-divisible-head archs: the
    hidden state lives (batch, seq@model, d) between blocks, so norms/FFN
    run 512-way and the attention q path needs no resharding at all (§Perf
    iteration Q3)."""
    if heads_divide_model(num_heads):
        return x
    return constrain(x, ("batch", "seq_tp", None), rules)


def constrain_attn_activations(q, k, v, num_heads: int, rules=None):
    """Pick the attention-region layout: head-TP when heads divide the model
    axis (no resharding, projections emit model-sharded heads); otherwise
    full-DP over every mesh axis (one all-to-all in, one out — 16x cheaper
    than replicated head compute)."""
    if heads_divide_model(num_heads):
        q = constrain(q, ("batch", None, "heads", None), rules)
        k = constrain(k, ("batch", None, "kv_heads", None), rules)
        v = constrain(v, ("batch", None, "kv_heads", None), rules)
        return q, k, v
    # Context parallelism: batch over dp, query sequence over "model"
    # (K/V replicated on "model"; GSPMD reduces dK/dV over the seq shards).
    # NOTE (§Perf iteration Q6, refuted): forcing the dK/dV cotangents to
    # reduce-scatter to seq shards via reshard_fwd_bwd DOUBLED collective
    # bytes (XLA all-reduced first, then resharded) — keep the default.
    q = constrain(q, ("batch", "seq_tp", None, None), rules)
    k = constrain(k, ("batch", None, None, None), rules)
    v = constrain(v, ("batch", None, None, None), rules)
    return q, k, v
