"""Lightweight functional parameter-definition system.

Models are pure functions over pytrees of jnp arrays.  Parameter trees are
*declared* as pytrees of :class:`ParamSpec` (shape + logical axis names +
initializer), then materialised with :func:`init_params`.  The parallel
machinery consumes the logical-axes tree (same structure) to build
``NamedSharding``s, and the dry-run consumes the shape tree to build
``jax.ShapeDtypeStruct`` stand-ins without allocating anything.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declaration of a single parameter tensor.

    Attributes:
      shape:  tensor shape.
      axes:   logical axis name per dim (None = replicated/unsharded dim).
      init:   "zeros" | "ones" | "normal" | "fan_in" | "embed" | "uniform".
      scale:  multiplier applied to the random initializer.
      dtype:  parameter dtype; None -> use the model-wide default.
    """

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "fan_in"
    scale: float = 1.0
    dtype: Any = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _leaves_with_path(tree: PyTree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def is_spec_tree_leaf(x) -> bool:
    return isinstance(x, ParamSpec)


def _materialise(spec: ParamSpec, key: jax.Array, dtype) -> jax.Array:
    shape = spec.shape
    if spec.init == "zeros":
        return jnp.zeros(shape, dtype)
    if spec.init == "ones":
        return jnp.ones(shape, dtype)
    if spec.init in ("normal", "embed"):
        return (spec.scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if spec.init == "fan_in":
        fan_in = shape[0] if len(shape) == 1 else int(np.prod(shape[:-1]))
        std = spec.scale / math.sqrt(max(fan_in, 1))
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if spec.init == "uniform":
        return (
            spec.scale * jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
        ).astype(dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def init_params(spec_tree: PyTree, key: jax.Array, default_dtype=jnp.float32,
                shardings: PyTree = None) -> PyTree:
    """Materialise a tree of ParamSpec into actual arrays.

    ``shardings``: optional tree (same structure) of ``jax.sharding``
    placements; each leaf is then drawn directly into its placement, so a
    tensor-parallel model never passes whole through one device.  The
    values do not depend on the placement."""
    leaves, treedef = _leaves_with_path(spec_tree)
    keys = jax.random.split(key, max(len(leaves), 1))
    places = (treedef.flatten_up_to(shardings) if shardings is not None
              else [None] * len(leaves))
    vals = []
    for (_, spec), k, place in zip(leaves, keys, places):
        dtype = spec.dtype or default_dtype
        if place is None and np.dtype(dtype) == np.float32:
            # an fp32 leaf is its own draw: op-by-op, bit-identical
            vals.append(_materialise(spec, k, dtype))
            continue
        # draw-and-cast as one executable: the fp32 draw stays a fused
        # temporary, so a bf16 model never holds a whole fp32 copy of a
        # leaf (qwen2-7b's stacked MLP leaf would be 7.6 GB)
        kw = {} if place is None else {"out_shardings": place}
        vals.append(jax.jit(_materialise, static_argnums=(0, 2), **kw)(
            spec, k, dtype))
    return jax.tree_util.tree_unflatten(treedef, vals)


def param_shapes(spec_tree: PyTree, default_dtype=jnp.float32) -> PyTree:
    """ShapeDtypeStruct tree (no allocation) — used by the dry-run."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or default_dtype),
        spec_tree,
        is_leaf=is_spec_tree_leaf,
    )


def logical_axes(spec_tree: PyTree) -> PyTree:
    """Tree of logical-axis tuples, same structure as the param tree."""
    return jax.tree_util.tree_map(
        lambda s: s.axes, spec_tree, is_leaf=is_spec_tree_leaf
    )


def stack_specs(spec_tree: PyTree, n: int, stack_axis_name: str | None = "layers") -> PyTree:
    """Prepend a stacking dim of size ``n`` to every spec (for lax.scan layers)."""

    def _stack(s: ParamSpec) -> ParamSpec:
        return ParamSpec(
            shape=(n,) + s.shape,
            axes=(stack_axis_name,) + s.axes,
            init=s.init,
            scale=s.scale,
            dtype=s.dtype,
        )

    return jax.tree_util.tree_map(_stack, spec_tree, is_leaf=is_spec_tree_leaf)


def count_params(spec_tree: PyTree) -> int:
    leaves, _ = _leaves_with_path(spec_tree)
    return sum(int(np.prod(s.shape)) for _, s in leaves)
