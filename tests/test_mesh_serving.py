"""Mesh-sharded serving parity: a TP engine must be *token-identical* to
the unsharded baseline across KV dtypes, kernel impls and architectures.

The suite runs single-device by default (conftest sets no XLA_FLAGS), so
only the TP=1 bit-identity test executes; the TP>=2 matrix skips unless
the process was started with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
`== multi-device ==` stage in scripts/ci.sh does exactly that).

The attn config overrides the smoke shrink to 8 heads / 4 kv heads so
TP=4 genuinely shards the KV dim; the hybrid (recurrentgemma) config
keeps its 1 kv head, which exercises the spec_for_axes replicate-fallback
live (kv replicated, heads + FFN hidden still sharded).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.retrace_guard import retrace_guard
from repro.configs.base import get_config, shrink
from repro.core.famous import FamousConfig
from repro.launch.mesh import make_serving_mesh
from repro.models import module, transformer
from repro.serve.engine import Request, ServingEngine


def _need(tp):
    if jax.device_count() < tp:
        pytest.skip(f"needs {tp} devices (run under XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=8)")


_CFGS = {
    # shrink() default kv=2 would not divide TP=4 — force 8H/4KV
    "attn": dict(name="qwen2-7b", over=dict(num_heads=8, num_kv_heads=4,
                                            head_dim=8)),
    "hybrid": dict(name="recurrentgemma-2b", over={}),
}
_STATE: dict = {}


def _cfg_params(arch):
    if arch not in _STATE:
        spec = _CFGS[arch]
        cfg = shrink(get_config(spec["name"]), **spec["over"])
        params = module.init_params(transformer.model_spec(cfg),
                                    jax.random.PRNGKey(0), jnp.float32)
        _STATE[arch] = (cfg, params)
    return _STATE[arch]


def _reqs(cfg, sampled=False):
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(3):
        r = Request(rid=i, max_new=5,
                    tokens=list(rng.integers(0, cfg.vocab_size, 5 + 3 * i)))
        if sampled:
            r.temperature, r.top_k, r.seed = 0.8, 8, 123 + i
        reqs.append(r)
    return reqs


def _run(arch, mesh=None, impl="xla", kv_dtype="fp", sampled=False, **kw):
    cfg, params = _cfg_params(arch)
    with warnings.catch_warnings():
        # hybrid kv=1 on a TP mesh replicates with a RuntimeWarning — that
        # fallback path is intentional here, not a failure
        warnings.simplefilter("ignore", RuntimeWarning)
        eng = ServingEngine(params, cfg, FamousConfig(impl=impl), n_slots=2,
                            max_seq=32, chunk=8, kv_dtype=kv_dtype,
                            page_size=8, mesh=mesh, **kw)
        done = eng.run(_reqs(cfg, sampled))
    assert all(r.error is None for r in done), [r.error for r in done]
    return {r.rid: tuple(r.out) for r in done}, eng


_BASE: dict = {}


def _baseline(arch, impl="xla", kv_dtype="fp", sampled=False, **kw):
    key = (arch, impl, kv_dtype, sampled, tuple(sorted(kw)))
    if key not in _BASE:
        _BASE[key] = _run(arch, None, impl, kv_dtype, sampled, **kw)[0]
    return _BASE[key]


def test_tp1_mesh_bit_identical():
    """mesh on 1 device must change nothing: same tokens, bitwise-equal
    final caches, same census (runs in the plain single-device suite)."""
    base_outs, base_eng = _run("attn")
    outs, eng = _run("attn", mesh=make_serving_mesh(tp=1))
    assert outs == base_outs
    for a, b in zip(jax.tree_util.tree_leaves(base_eng.caches),
                    jax.tree_util.tree_leaves(eng.caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert eng.compilations == base_eng.compilations
    assert eng.cache_bytes_per_device() == base_eng.cache_bytes_per_device()


@pytest.mark.parametrize("arch", ["attn", "hybrid"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
@pytest.mark.parametrize("tp", [2, 4])
def test_tp_parity_matrix(tp, kv_dtype, impl, arch):
    _need(tp)
    base = _baseline(arch, impl, kv_dtype)
    outs, eng = _run(arch, make_serving_mesh(tp=tp), impl, kv_dtype)
    assert outs == base
    # census identical to the unsharded engine: sharding must not fork
    # executables (retrace_guard's O(1)-compilations contract)
    assert eng.compilations["prefill"] == 1
    assert eng.compilations["decode"] == 1


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_parity_seeded_sampling(tp):
    _need(tp)
    base = _baseline("attn", sampled=True)
    outs, _ = _run("attn", make_serving_mesh(tp=tp), sampled=True)
    assert outs == base


@pytest.mark.parametrize("tp", [2])
def test_tp_prefix_cache_and_speculative(tp):
    """The host-side allocator / prefix index / drafter are device-agnostic:
    with both on, a TP engine stays token-identical and the allocator
    invariants hold after the drain."""
    _need(tp)
    kw = dict(prefix_cache=True, speculative=True, draft_k=3)
    base = _baseline("attn", **kw)
    outs, eng = _run("attn", mesh=make_serving_mesh(tp=tp), **kw)
    assert outs == base
    eng.alloc.assert_invariants()
    assert eng.speculative_active


@pytest.mark.parametrize("tp", [2])
def test_tp_retrace_guard(tp):
    """A warmed sharded engine serves a fresh batch with zero new
    compilations — out_shardings must not introduce retraces."""
    _need(tp)
    cfg, params = _cfg_params("attn")
    eng = ServingEngine(params, cfg, FamousConfig(impl="xla"), n_slots=2,
                        max_seq=32, chunk=8, page_size=8,
                        mesh=make_serving_mesh(tp=tp))
    eng.run(_reqs(cfg))
    rng = np.random.default_rng(7)
    fresh = [Request(rid=100 + i, max_new=4,
                     tokens=list(rng.integers(0, cfg.vocab_size, 4 + i)))
             for i in range(3)]
    with retrace_guard(eng, label="warm TP engine"):
        eng.run(fresh)


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_cache_bytes_per_device_shrink(tp, kv_dtype):
    """The KV bytes resident per device must be exactly 1/TP of the
    unsharded engine's (the attn config's caches, int8 scale pools
    included, are all kv-head-sharded leaves, so the ratio is exact, not
    approximate)."""
    _need(tp)
    _, base_eng = _run("attn", kv_dtype=kv_dtype)
    _, eng = _run("attn", make_serving_mesh(tp=tp), kv_dtype=kv_dtype)
    assert eng.cache_bytes_per_device() * tp == base_eng.cache_bytes_per_device()


@pytest.mark.parametrize("kv_rule,want", [("model", "model"), (None, None)])
def test_pallas_head_axis_follows_rules(kv_rule, want):
    """The Pallas launches split heads over the mesh axis the engine's
    sharding rules give them; rules that split heads and kv heads
    differently are refused for impl="pallas" (runs single-device)."""
    from repro.parallel.sharding import SERVE_TP_RULES
    cfg, params = _cfg_params("attn")
    rules = {**SERVE_TP_RULES, "kv_heads": kv_rule}
    kw = dict(n_slots=2, max_seq=32, chunk=8, page_size=8,
              mesh=make_serving_mesh(tp=1),
              sharding_rules=rules)
    if want is None:
        with pytest.raises(ValueError, match="one mesh axis"):
            ServingEngine(params, cfg, FamousConfig(impl="pallas"), **kw)
    else:
        eng = ServingEngine(params, cfg, FamousConfig(impl="pallas"), **kw)
        assert eng.fcfg.head_axis == want


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_pallas_heads_on_rule_axis(kv_dtype):
    """Rules that put the heads on the "data" axis move the Pallas
    launches' split there too: still token-identical to one device."""
    _need(2)
    from repro.parallel.sharding import SERVE_TP_RULES
    rules = {**SERVE_TP_RULES, "batch": None, "heads": "data",
             "kv_heads": "data", "mlp": "data"}
    base = _baseline("attn", "pallas", kv_dtype)
    outs, eng = _run("attn", make_serving_mesh(tp=1, dp=2), "pallas",
                     kv_dtype, sharding_rules=rules)
    assert eng.fcfg.head_axis == "data"
    assert outs == base
    assert eng.cache_bytes_per_device() * 2 == \
        _run("attn", kv_dtype=kv_dtype)[1].cache_bytes_per_device()
