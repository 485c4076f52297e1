"""Ahead-of-time compiles of the serving main path's Pallas kernels for a
TPU v5e at qwen2-7b's published widths (KV 4, group 7, dh 128), of the
paged decode kernels at deepseek-7b's (KV 32, group 1), and of
deepseek-v2-lite's latent decode kernel (16 heads over 640-wide stored
latent rows) and held-expert grouped matmul, at the serving sizes of
``repro.kernels.catalog`` (page 16, 4 slots x 128 pages, chunk 32; bf16
and int8).

The serving steps themselves (decode and chunked prefill, paged and
donated, as the engine jits them) are compiled at two pool sizes too, to
hold their temporaries flat as the pool grows.

Interpret-mode tests never apply Mosaic's tiling rules; the TPU compiler
installed with jax does, for a chip that is described and not attached.
Each case must lower to a ``tpu_custom_call`` — the kernel compiled, not
interpreted or replaced.  The topology is described only inside the
module fixture below (never at import or collection), so test workers
that are not given this file never load the TPU library.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.famous import FamousConfig
from repro.kernels import catalog, pallas_compat
from repro.kernels.catalog import latent_cases, main_path_cases
from repro.models import module, transformer

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


# ---------------------------------------------------------------------------
# The serving steps' memory: the layer scan carries the stacked page pools
# and writes only the new rows, so no step's temporaries grow with the pool.
# ---------------------------------------------------------------------------

# (model, layers, KV dtype): deepseek-7b's MHA widths (32 kv heads) in fp
# and int8, and deepseek-v2-lite's latent path (its dense head layer and
# two MoE layers), each at the pool sizes below
STEP_MODELS = {"deepseek-7b/fp": ("deepseek-7b", 2, "fp"),
               "deepseek-7b/int8": ("deepseek-7b", 2, "int8"),
               "deepseek-v2-lite/fp": ("deepseek-v2-lite", 3, "fp")}
STEP_PAGES = (256, 512)
STEP_SLOTS, STEP_MAX_SEQ = 8, 2048


def _step_memory(sharding, model, layers, kv_dtype, step, n_pages):
    """(memory analysis, stacked pool bytes) of ``step`` ("decode" or
    "prefill_chunk") compiled as the engine jits it (impl="pallas", paged
    caches donated) for the described chip."""
    cfg = dataclasses.replace(get_config(model), num_layers=layers)
    fcfg = FamousConfig(impl="pallas")

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), tree)

    def ints(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = placed(module.param_shapes(transformer.model_spec(cfg),
                                        jnp.bfloat16))
    caches = placed(transformer.make_caches(
        cfg, STEP_SLOTS, STEP_MAX_SEQ, jnp.bfloat16, shapes_only=True,
        page_size=catalog.PAGE, n_pages=n_pages, kv_dtype=kv_dtype))
    table = ints(STEP_SLOTS, STEP_MAX_SEQ // catalog.PAGE)
    if step == "decode":
        fn = functools.partial(transformer.decode_step, cfg=cfg, fcfg=fcfg)
        args = (params, ints(STEP_SLOTS), caches, ints(STEP_SLOTS))
        kw = {"active": ints(STEP_SLOTS, dtype=jnp.bool_),
              "page_table": table}
    else:
        fn = functools.partial(transformer.prefill_chunk, cfg=cfg, fcfg=fcfg)
        args = (params, ints(1, catalog.CHUNK), caches, ints(), ints(),
                ints())
        kw = {"page_table": table}
    compiled = jax.jit(fn, donate_argnums=2).lower(*args, **kw).compile()
    pool = sum(c.size * c.dtype.itemsize
               for c in jax.tree_util.tree_leaves(caches))
    return compiled.memory_analysis(), pool


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
@pytest.mark.parametrize("name", sorted(STEP_MODELS))
def test_step_temporaries_do_not_grow_with_the_pool(name, step, one_chip,
                                                    no_compile_cache,
                                                    monkeypatch):
    """Doubling the pool grows the step's temporaries by less than one
    layer's pool, and the donated caches alias the whole stacked pool.  A
    scan that slices each layer's pool in and stacks the new pools out
    grows them by more than the whole pool (fp decode: 335.5 MB for a
    134.2 MB pool)."""
    # the CPU backend would interpret the kernels; compile them instead
    monkeypatch.setattr(pallas_compat, "interpret_default", lambda: False)
    model, layers, kv_dtype = STEP_MODELS[name]
    (small, pool_small), (big, pool_big) = (
        _step_memory(one_chip, model, layers, kv_dtype, step, n)
        for n in STEP_PAGES)
    layer_pool = (pool_big - pool_small) / layers
    growth = big.temp_size_in_bytes - small.temp_size_in_bytes
    assert growth < layer_pool, (growth, layer_pool)
    assert small.alias_size_in_bytes >= pool_small
    assert big.alias_size_in_bytes >= pool_big
