"""Production meshes.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis carries pure data parallelism (gradient reduction only), so pod count is
the elastic-scaling dimension (train/elastic.py).

Defined as functions so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS *before* jax initialises).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis auto-sharded (GSPMD)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def abstract_mesh(shape, axes):
    """Device-free ``AbstractMesh`` of the same axes (dry-run lowering)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Tiny mesh over however many (host) devices exist — used by tests."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def make_serving_mesh(tp: int = 1, dp: int = 1):
    """Inference mesh for ``ServingEngine(mesh=...)``: ``tp``-way tensor
    parallelism on the "model" axis (attention heads / kv heads / FFN
    hidden), ``dp`` replica groups on "data".  Requires ``tp * dp`` visible
    devices — on CPU force them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    n = jax.device_count()
    if tp * dp > n:
        raise ValueError(
            f"serving mesh ({dp}, {tp}) needs {tp * dp} devices but only "
            f"{n} are visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp * dp}")
    return make_mesh((dp, tp), ("data", "model"))
