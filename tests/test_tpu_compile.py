"""Ahead-of-time compiles of the serving main path's Pallas kernels for a
TPU v5e at qwen2-7b's published widths (KV 4, group 7, dh 128), of the
paged decode kernels at deepseek-7b's (KV 32, group 1), and of
deepseek-v2-lite's latent decode kernel (16 heads over 640-wide stored
latent rows) and held-expert grouped matmul, at the serving sizes of
``repro.kernels.catalog`` (page 16, 4 slots x 128 pages, chunk 32; bf16
and int8).

Interpret-mode tests never apply Mosaic's tiling rules; the TPU compiler
installed with jax does, for a chip that is described and not attached.
Each case must lower to a ``tpu_custom_call`` — the kernel compiled, not
interpreted or replaced.  The topology is described only inside the
module fixture below (never at import or collection), so test workers
that are not given this file never load the TPU library.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.catalog import latent_cases, main_path_cases

CASES = {c.name: c for c in main_path_cases(get_config("qwen2-7b"))}
# the paged decode kernel at deepseek-7b's widths too: MHA (32 kv heads,
# group 1), the other block-walk shape the serving benchmark runs
CASES.update({f"deepseek-7b/{c.name}": c
              for c in main_path_cases(get_config("deepseek-7b"))
              if c.name in ("paged_decode", "paged_decode_int8")})
CASES.update({f"deepseek-v2-lite/{c.name}": c
              for c in latent_cases(get_config("deepseek-v2-lite"))})


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    case = CASES[name]
    shapes = jax.eval_shape(case.make, jax.random.PRNGKey(0))
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in shapes]
    text = jax.jit(case.kernel).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
