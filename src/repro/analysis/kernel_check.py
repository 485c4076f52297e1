"""Pallas launch contract checker — FAMOUS's synthesis-time resource and
tiling validation (§IV-B) applied to every ``pallas_call``.

The FPGA design statically guarantees that tile sizes divide the matrix
dims it will serve and that the BRAM/URAM banks the tiles occupy fit the
device; violations are caught at synthesis, not on the board.  The Pallas
analogue used to be "crash at trace time with a shape error three layers
deep" — or worse, silently read garbage from an out-of-bounds block.  This
module validates, at launch time and against the *actual* operands:

* every ``BlockSpec`` block shape divides its array dim (no silent
  partial tiles);
* every block obeys Mosaic's tiling rule: its last two dims are
  multiples of (8, 128), or equal the array's dims — interpret mode never
  applies it, the TPU compiler refuses the kernel without it;
* every ``index_map`` takes exactly ``grid rank + num_scalar_prefetch``
  arguments and its outputs stay in bounds over the grid (full
  enumeration for small grids, corner sampling beyond
  :data:`GRID_ENUM_CAP` points);
* the launch's *output* grids cover their arrays completely (a partially
  written output is garbage in the uncovered blocks);
* the per-grid-step VMEM footprint estimated from block shapes + dtypes
  (input/output blocks double-buffered for the DMA pipeline, plus VMEM
  scratch) fits a configurable budget — the on-chip memory accounting of
  the paper, with ``REPRO_VMEM_BUDGET_BYTES`` standing in for the part's
  BRAM capacity.

Index maps that read scalar-prefetched operands (the page-table kernels)
are evaluated with the real host values when the launch is outside
``jax.jit``; under tracing the prefetched values are unknown, so those
specs get arity/divisibility checks only — recorded, never guessed.

Enablement: off by default (zero overhead in production), on via
``REPRO_KERNEL_CHECK=1``, :func:`enable`, or the :func:`checking` context
manager; the test suite switches it on globally in ``tests/conftest.py``.
All violations of one launch are aggregated into a single
:class:`KernelContractError`.
"""
from __future__ import annotations

import contextlib
import functools
import inspect
import itertools
import math
import os

import numpy as np

GRID_ENUM_CAP = 16384          # full index_map enumeration up to this many
DEFAULT_VMEM_BUDGET = 16 * 2 ** 20   # 16 MiB — one TPUv4 core's VMEM

_FORCED: bool | None = None    # tri-state override of the env switch


class KernelContractError(ValueError):
    """A Pallas launch violated its BlockSpec/grid/VMEM contract."""


def kernel_check_enabled() -> bool:
    if _FORCED is not None:
        return _FORCED
    return os.environ.get("REPRO_KERNEL_CHECK", "0").lower() \
        not in ("", "0", "false")


def enable() -> None:
    global _FORCED
    _FORCED = True


def disable() -> None:
    global _FORCED
    _FORCED = False


@contextlib.contextmanager
def checking(on: bool = True):
    """Scoped enable/disable, restoring the previous state on exit."""
    global _FORCED
    prev = _FORCED
    _FORCED = on
    try:
        yield
    finally:
        _FORCED = prev


def vmem_budget() -> int:
    return int(os.environ.get("REPRO_VMEM_BUDGET_BYTES",
                              DEFAULT_VMEM_BUDGET))


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _is_tracer(x) -> bool:
    import jax
    return isinstance(x, jax.core.Tracer)


def _mem_space_name(obj) -> str:
    ms = getattr(obj, "memory_space", None)
    return "" if ms is None else str(getattr(ms, "value", ms)).lower()


def _in_vmem(obj) -> bool:
    """Whether a spec or scratch shape lives in VMEM: VMEM itself or no
    stated space (the default).  SMEM scalars, semaphores and operands left
    in ANY (HBM, copied by the kernel's own DMAs) take none."""
    name = _mem_space_name(obj)
    return not name or "vmem" in name


def _kernel_name(kernel) -> str:
    if isinstance(kernel, functools.partial):
        kernel = kernel.func
    return getattr(kernel, "__name__", repr(kernel))


class _PrefetchProbe:
    """Stand-in handed to index_maps for a scalar-prefetch operand.

    Wraps the operand's host value when it is concrete; records whether
    the map actually indexed into it, so value-dependent checks can be
    skipped (not guessed) when the operand is a tracer.
    """

    def __init__(self, operand):
        self.touched = False
        self.concrete = not _is_tracer(operand)
        self._arr = np.asarray(operand) if self.concrete else None
        self._shape = tuple(getattr(operand, "shape", ()))

    def __getitem__(self, idx):
        self.touched = True
        if self.concrete:
            return self._arr[idx]
        return 0    # placeholder; the caller discards the result

    @property
    def shape(self):
        return self._shape


def _index_map_arity(index_map):
    """(required positional params, accepts extras) — defaulted trailing
    params (the ``lambda ..., group=group:`` closure idiom) are allowed on
    top of the grid+prefetch arguments."""
    try:
        sig = inspect.signature(index_map)
    except (TypeError, ValueError):    # builtins etc. — cannot introspect
        return None, True
    required = 0
    varargs = False
    for p in sig.parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            if p.default is p.empty:
                required += 1
        elif p.kind == p.VAR_POSITIONAL:
            varargs = True
    return required, varargs


def _grid_points(grid):
    """(iterator of grid index tuples, exhaustive?) — every point for
    small grids, the corners beyond :data:`GRID_ENUM_CAP`."""
    total = math.prod(grid) if grid else 1
    if total <= GRID_ENUM_CAP:
        return itertools.product(*[range(g) for g in grid]), True
    corners = itertools.product(*[(0, g - 1) if g > 1 else (0,)
                                  for g in grid])
    return corners, False


def _block_bytes(block_shape, shape, dtype) -> int:
    eff = [s if b is None else b for b, s in zip(block_shape, shape)] \
        if block_shape is not None else list(shape)
    return int(np.prod([max(int(e), 1) for e in eff], dtype=np.int64)
               * np.dtype(dtype).itemsize) if eff else \
        int(np.dtype(dtype).itemsize)


# --------------------------------------------------------------------------
# the checks
# --------------------------------------------------------------------------

def _check_spec(errors, *, role, i, spec, shape, grid, probes, exhaustive_pts,
                require_coverage):
    """Validate one BlockSpec against one array shape."""
    where = f"{role}[{i}] (shape {tuple(shape)})"
    block = getattr(spec, "block_shape", None)
    if block is None:      # whole-array spec: trivially divides and covers
        return
    block = tuple(block)
    if len(block) != len(shape):
        errors.append(f"{where}: block rank {len(block)} != array rank "
                      f"{len(shape)} (block {block})")
        return
    for d, (b, s) in enumerate(zip(block, shape)):
        if b is not None and (b <= 0 or s % b):
            errors.append(f"{where}: block dim {d} = {b} does not divide "
                          f"array dim {s} — partial tiles read/write out "
                          f"of bounds unless explicitly masked")
    for d, tile in ((len(block) - 2, 8), (len(block) - 1, 128)):
        if d < 0:
            continue
        b = 1 if block[d] is None else block[d]   # None: a squeezed dim
        if b % tile and b != shape[d]:
            errors.append(f"{where}: block dim {d} = {b} is neither a "
                          f"multiple of {tile} nor the array dim "
                          f"{shape[d]} — Mosaic tiles the last two block "
                          f"dims by (8, 128) and refuses the kernel")

    index_map = getattr(spec, "index_map", None)
    if index_map is None:
        return
    required, varargs = _index_map_arity(index_map)
    expected = len(grid) + len(probes)
    if required is not None and required != expected and not varargs:
        errors.append(f"{where}: index_map takes {required} required "
                      f"arg(s) but the launch provides {len(grid)} grid "
                      f"indices + {len(probes)} scalar-prefetch "
                      f"operand(s) = {expected}")
        return

    points, exhaustive = _grid_points(grid)
    exhaustive = exhaustive and exhaustive_pts
    seen: set = set()
    value_checked = True
    for pt in points:
        for pr in probes:
            pr.touched = False
        try:
            out = index_map(*pt, *probes)
        except Exception as e:    # noqa: BLE001 — any failure is a finding
            errors.append(f"{where}: index_map raised {type(e).__name__} "
                          f"at grid point {pt}: {e}")
            return
        out = tuple(out) if isinstance(out, tuple) else (out,)
        if len(out) != len(block):
            errors.append(f"{where}: index_map returns {len(out)} "
                          f"indices for a rank-{len(block)} block")
            return
        if any(pr.touched and not pr.concrete for pr in probes):
            # value depends on traced prefetch data: unverifiable here
            value_checked = False
            continue
        static = []
        for d, (c, b, s) in enumerate(zip(out, block, shape)):
            try:
                ci = int(c)
            except (TypeError, ValueError):
                value_checked = False
                static.append(None)
                continue
            static.append(ci)
            if b is None:
                continue
            if ci < 0 or (ci + 1) * b > s:
                errors.append(
                    f"{where}: index_map output {ci} at grid point {pt} "
                    f"puts block dim {d} out of bounds "
                    f"(needs ({ci}+1)*{b} <= {s})")
                return
        if None not in static:
            seen.add(tuple(static))

    if require_coverage and exhaustive and value_checked:
        needed = itertools.product(
            *[range(s // b) if b else range(1)
              for b, s in zip(block, shape)])
        missing = [p for p in needed if p not in seen]
        if missing:
            errors.append(
                f"{where}: grid does not cover the array — "
                f"{len(missing)} of {math.prod(max(s // b, 1) if b else 1 for b, s in zip(block, shape))} "
                f"output block(s) never written (first missing: "
                f"{missing[0]})")


def check_launch(*, name, grid, in_specs, out_specs, out_shape,
                 scratch_shapes=(), num_scalar_prefetch=0, args=()):
    """Validate one launch; raises :class:`KernelContractError` listing
    every violation.  ``args`` are the call's actual operands, scalar-
    prefetch operands first."""
    grid = (grid,) if isinstance(grid, int) else tuple(grid or ())
    outs = out_shape if isinstance(out_shape, (list, tuple)) else [out_shape]
    ospecs = list(out_specs) if isinstance(out_specs, (list, tuple)) \
        else [out_specs]
    scalar_args = list(args[:num_scalar_prefetch])
    operands = list(args[num_scalar_prefetch:])
    probes = [_PrefetchProbe(a) for a in scalar_args]

    errors: list = []
    in_specs = list(in_specs or ())
    if len(in_specs) != len(operands):
        errors.append(f"{len(in_specs)} in_spec(s) for {len(operands)} "
                      f"non-prefetch operand(s)")
    if len(ospecs) != len(outs):
        errors.append(f"{len(ospecs)} out_spec(s) for {len(outs)} "
                      f"out_shape(s)")

    vmem = 0
    pairs = [("in_specs", i, s, o.shape, getattr(o, "dtype", np.float32),
              False)
             for i, (s, o) in enumerate(zip(in_specs, operands))]
    pairs += [("out_specs", i, s, tuple(o.shape), o.dtype, True)
              for i, (s, o) in enumerate(zip(ospecs, outs))]
    for role, i, spec, shape, dtype, is_out in pairs:
        _check_spec(errors, role=role, i=i, spec=spec, shape=tuple(shape),
                    grid=grid, probes=probes, exhaustive_pts=True,
                    require_coverage=is_out)
        if _in_vmem(spec):
            # input/output blocks are double-buffered by the Pallas
            # pipeline: the live footprint is 2x the block
            vmem += 2 * _block_bytes(getattr(spec, "block_shape", None),
                                     shape, dtype)
    for sc in scratch_shapes or ():
        if _in_vmem(sc):
            vmem += _block_bytes(None, getattr(sc, "shape", ()),
                                 getattr(sc, "dtype", np.float32))

    budget = vmem_budget()
    if vmem > budget:
        errors.append(f"estimated per-step VMEM footprint {vmem} B "
                      f"(double-buffered blocks + scratch) exceeds the "
                      f"budget of {budget} B "
                      f"(REPRO_VMEM_BUDGET_BYTES)")

    if errors:
        raise KernelContractError(
            f"pallas kernel contract violation(s) in `{name}` "
            f"(grid {grid}):\n  - " + "\n  - ".join(errors))


def check_pallas_launch(kernel, call_kwargs: dict, args: tuple) -> None:
    """Entry point for :func:`repro.kernels.pallas_compat.pallas_call`:
    unpack a ``pl.pallas_call`` keyword set (either ``grid=...`` style or
    a ``grid_spec=PrefetchScalarGridSpec(...)``) and validate."""
    grid_spec = call_kwargs.get("grid_spec")
    if grid_spec is not None:
        grid = grid_spec.grid
        in_specs = grid_spec.in_specs
        out_specs = grid_spec.out_specs
        scratch = grid_spec.scratch_shapes
        npf = getattr(grid_spec, "num_scalar_prefetch", 0) or 0
    else:
        grid = call_kwargs.get("grid", ())
        in_specs = call_kwargs.get("in_specs", ())
        out_specs = call_kwargs.get("out_specs", ())
        scratch = call_kwargs.get("scratch_shapes", ())
        npf = 0
    check_launch(name=_kernel_name(kernel), grid=grid, in_specs=in_specs,
                 out_specs=out_specs, out_shape=call_kwargs.get("out_shape"),
                 scratch_shapes=scratch, num_scalar_prefetch=npf, args=args)
