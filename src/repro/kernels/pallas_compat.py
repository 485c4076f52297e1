"""The Pallas TPU names every kernel family uses, plus the contract-checked
``pallas_call`` entry point and the one interpret-mode policy.

Every kernel family (attention, qkv, decode, scan) imports
``CompilerParams``, the memory spaces and the scalar-prefetch grid spec from
here instead of reaching into ``pltpu`` directly, so the spelling lives in
one place.

All kernel families also launch through :func:`pallas_call` below rather
than ``pl.pallas_call`` directly: a drop-in wrapper that, when the
static-analysis hook is enabled (``REPRO_KERNEL_CHECK=1``, or globally in
the test suite), validates the launch's BlockSpec/grid/VMEM contract —
including Mosaic's (8, 128) block tiling rule — against the actual operands
before dispatching; see :mod:`repro.analysis.kernel_check`.  Disabled (the
default), the only overhead is one predicate call per launch.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams
SMEM = pltpu.MemorySpace.SMEM
ANY = pltpu.MemorySpace.ANY
# VMEM and SMEM are both memory spaces and (called with (shape, dtype))
# scratch-buffer constructors.
VMEM = pltpu.VMEM
# Manual DMA: kernels that place an operand in ANY (HBM) copy the parts they
# need with ``make_async_copy``, signalling a ``DMA_SEMAPHORES((n,))`` array.
DMA_SEMAPHORES = pltpu.SemaphoreType.DMA
make_async_copy = pltpu.make_async_copy

# Scalar-prefetch grid spec: prefetched int32 operands land in SMEM before
# the kernel runs and are visible to BlockSpec index_maps — the mechanism
# behind page-table-driven K/V gathers.
PrefetchScalarGridSpec = pltpu.PrefetchScalarGridSpec


def interpret_default() -> bool:
    """Whether a kernel launched with ``interpret=None`` runs in the Pallas
    interpreter: yes on the CPU backend (tests, host rehearsals), no on the
    TPU, where every kernel is compiled by Mosaic.  Any other backend has no
    Mosaic compiler and no business silently interpreting: it raises."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend {backend!r}")


def compiler_params(*dimension_semantics: str, **kwargs):
    """Build compiler params with the given per-grid-dim semantics.

    ``compiler_params("parallel", "arbitrary")`` is the common call; extra
    kwargs (``vmem_limit_bytes`` etc.) pass through unchanged.
    """
    return CompilerParams(dimension_semantics=tuple(dimension_semantics),
                          **kwargs)


def pallas_call(kernel, **kwargs):
    """Contract-checked ``pl.pallas_call``.

    Same signature and return value as ``pl.pallas_call``; when
    :func:`repro.analysis.kernel_check.kernel_check_enabled` is true, the
    returned callable first validates block divisibility, index_map
    arity/bounds, output-grid coverage and the estimated VMEM footprint
    against the concrete operands (raising
    :class:`~repro.analysis.kernel_check.KernelContractError` with every
    violation) before delegating to the real launch.
    """
    inner = pl.pallas_call(kernel, **kwargs)

    def checked(*args):
        from repro.analysis import kernel_check
        if kernel_check.kernel_check_enabled():
            kernel_check.check_pallas_launch(kernel, kwargs, args)
        return inner(*args)

    return checked
