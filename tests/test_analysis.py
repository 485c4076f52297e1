"""Tests for repro.analysis: lint rules, the Pallas contract checker and
the retrace guard — each rule with a positive and a negative fixture, the
checker against both deliberately broken specs and the real kernels, and
the guard against fake censuses plus a live warmed engine."""
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.analysis import (KernelContractError, RetraceError, checking,
                            lint_paths, lint_source, retrace_guard)
from repro.analysis import kernel_check
from repro.analysis import lint


def _rules(src, path="x.py"):
    return [f.rule for f in lint_source(textwrap.dedent(src), path)]


# --------------------------------------------------------------------------
# RA001: host sync in loop
# --------------------------------------------------------------------------

def test_ra001_int_on_device_value_in_loop():
    src = """
    import jax.numpy as jnp

    def f():
        vals = jnp.arange(8)
        out = []
        for i in range(8):
            out.append(int(vals[i]))
        return out
    """
    assert _rules(src) == ["RA001"]


def test_ra001_comprehension_counts_as_loop():
    src = """
    import jax.numpy as jnp

    def f(xs):
        d = jnp.cumsum(xs)
        return [float(d[i]) for i in range(4)]
    """
    assert _rules(src) == ["RA001"]


def test_ra001_device_class_attr():
    src = """
    import jax.numpy as jnp

    class C:
        def __init__(self):
            self.state = jnp.zeros((4,))

        def pull(self):
            return [int(self.state[i]) for i in range(4)]
    """
    assert _rules(src) == ["RA001"]


def test_ra001_negative_host_numpy():
    src = """
    import numpy as np

    def f():
        vals = np.arange(8)
        return [int(vals[i]) for i in range(8)]
    """
    assert _rules(src) == []


def test_ra001_negative_hoisted_pull():
    src = """
    import numpy as np
    import jax.numpy as jnp

    def f():
        vals = jnp.arange(8)
        host = np.asarray(vals)    # the one blessed sync
        return [int(host[i]) for i in range(8)]
    """
    assert _rules(src) == []


# --------------------------------------------------------------------------
# RA002: eager scatter in loop
# --------------------------------------------------------------------------

def test_ra002_scatter_in_loop():
    src = """
    import jax.numpy as jnp

    def f(x):
        for i in range(4):
            x = x.at[i].set(i)
        return x
    """
    assert _rules(src) == ["RA002"]


def test_ra002_negative_outside_loop():
    src = """
    def f(x, i):
        return x.at[i].set(0)
    """
    assert _rules(src) == []


# --------------------------------------------------------------------------
# RA003: jax.jit without static declarations
# --------------------------------------------------------------------------

def test_ra003_jit_of_str_param():
    src = """
    import jax

    def f(x, mode="fast"):
        return x

    def build():
        return jax.jit(f)
    """
    assert _rules(src) == ["RA003"]


def test_ra003_negative_with_static_argnames():
    src = """
    import jax

    def f(x, mode="fast"):
        return x

    def build():
        return jax.jit(f, static_argnames=("mode",))
    """
    assert _rules(src) == []


def test_ra003_negative_no_static_params():
    src = """
    import jax

    def f(x, scale=1.0):
        return x * scale

    def build():
        return jax.jit(f)
    """
    assert _rules(src) == []


# --------------------------------------------------------------------------
# RA004: scheduler purity
# --------------------------------------------------------------------------

def test_ra004_scheduler_must_not_import_jax():
    src = "import jax.numpy as jnp\n"
    assert _rules(src, path="serve/scheduler.py") == ["RA004"]
    # the identical source is fine anywhere else
    assert _rules(src, path="serve/engine.py") == []


def test_ra004_is_never_baselined():
    findings = lint_source("import jax\n", "serve/scheduler.py")
    baseline = {f.fingerprint for f in findings}
    new, _stale = lint.compare_to_baseline(findings, baseline)
    assert [f.rule for f in new] == ["RA004"]


# --------------------------------------------------------------------------
# baseline mechanics + the repo-is-clean gate
# --------------------------------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    findings = lint_source(textwrap.dedent("""
    import jax.numpy as jnp

    def f(x):
        for i in range(4):
            x = x.at[i].set(i)
        return x
    """), "m.py")
    path = str(tmp_path / "baseline.txt")
    lint.write_baseline(findings, path)
    baseline = lint.load_baseline(path)
    new, stale = lint.compare_to_baseline(findings, baseline)
    assert not new and not stale
    # fixing the finding turns the entry stale
    new, stale = lint.compare_to_baseline([], baseline)
    assert not new and len(stale) == 1


def test_repo_lints_clean():
    root = os.path.dirname(os.path.dirname(os.path.abspath(lint.__file__)))
    findings = lint_paths(root)
    new, stale = lint.compare_to_baseline(findings, lint.load_baseline())
    assert not new, "new lint findings:\n" + "\n".join(str(f) for f in new)
    assert not stale, f"stale baseline entries: {stale}"


# --------------------------------------------------------------------------
# kernel contract checker: broken specs
# --------------------------------------------------------------------------

def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_kernel_check_non_dividing_block():
    with pytest.raises(KernelContractError, match="does not divide"):
        kernel_check.check_launch(
            name="bad", grid=(2,),
            in_specs=[pl.BlockSpec((5, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=_sds((16, 8)),
            args=(np.zeros((16, 8), np.float32),))


def test_kernel_check_wrong_index_map_arity():
    with pytest.raises(KernelContractError, match="index_map takes"):
        kernel_check.check_launch(
            name="bad", grid=(2,),
            in_specs=[pl.BlockSpec((8, 8), lambda i, j: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=_sds((16, 8)),
            args=(np.zeros((16, 8), np.float32),))


def test_kernel_check_out_of_bounds_index_map():
    with pytest.raises(KernelContractError, match="out of bounds"):
        kernel_check.check_launch(
            name="bad", grid=(2,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i + 1, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=_sds((16, 8)),
            args=(np.zeros((16, 8), np.float32),))


def test_kernel_check_uncovered_output():
    with pytest.raises(KernelContractError, match="never written"):
        kernel_check.check_launch(
            name="bad", grid=(2,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=_sds((32, 8)),
            args=(np.zeros((32, 8), np.float32),))


def test_kernel_check_vmem_budget(monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", "64")
    with pytest.raises(KernelContractError, match="VMEM footprint"):
        kernel_check.check_launch(
            name="bad", grid=(2,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=_sds((16, 8)),
            args=(np.zeros((16, 8), np.float32),))


def test_kernel_check_counts_no_vmem_for_operands_left_in_hbm(monkeypatch):
    """An operand in ANY (HBM, copied by the kernel's own DMAs), SMEM
    scratch and DMA semaphores take no VMEM; VMEM scratch still counts."""
    from repro.kernels import pallas_compat as pc
    pool = np.zeros((4, 1024, 16, 128), np.float32)          # 32 MiB
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", str(2 ** 20))
    launch = dict(
        name="manual_dma", grid=(2,),
        in_specs=[pl.BlockSpec(memory_space=pc.ANY)],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=_sds((16, 128)), args=(pool,))
    kernel_check.check_launch(
        scratch_shapes=[pc.VMEM((2, 32, 16, 128), jnp.bfloat16),
                        pc.SMEM((1,), jnp.int32), pc.DMA_SEMAPHORES((2,))],
        **launch)
    with pytest.raises(KernelContractError, match="VMEM footprint"):
        kernel_check.check_launch(
            scratch_shapes=[pc.VMEM((2, 128, 16, 128), jnp.float32)],
            **launch)


def test_kernel_check_mosaic_second_minor_tiling():
    """A size-1 block on the second-minor dim of a larger array (the old
    token-major paged K/V pools) is refused by Mosaic."""
    with pytest.raises(KernelContractError, match="multiple of 8 nor"):
        kernel_check.check_launch(
            name="bad", grid=(16,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
            out_shape=_sds((16, 128)),
            args=(np.zeros((16, 128), np.float32),))


def test_kernel_check_mosaic_minor_tiling():
    """A lane dim that is neither a multiple of 128 nor the whole array
    dim is refused by Mosaic."""
    with pytest.raises(KernelContractError, match="multiple of 128 nor"):
        kernel_check.check_launch(
            name="bad", grid=(4,),
            in_specs=[pl.BlockSpec((8, 64), lambda i: (0, i))],
            out_specs=pl.BlockSpec((8, 64), lambda i: (0, i)),
            out_shape=_sds((8, 256)),
            args=(np.zeros((8, 256), np.float32),))


def test_kernel_check_aggregates_all_violations():
    with pytest.raises(KernelContractError) as ei:
        kernel_check.check_launch(
            name="bad", grid=(2,),
            in_specs=[pl.BlockSpec((5, 8), lambda i, j: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i + 1, 0)),
            out_shape=_sds((16, 8)),
            args=(np.zeros((16, 8), np.float32),))
    msg = str(ei.value)
    assert "does not divide" in msg
    assert "index_map takes" in msg
    assert "out of bounds" in msg


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def test_compat_shim_rejects_bad_launch():
    """A broken spec through the pallas_compat entry point fails before
    dispatch when checking is on."""
    from repro.kernels import pallas_compat as pc
    call = pc.pallas_call(
        _copy_kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec((5, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
        out_shape=_sds((16, 8)),
        interpret=True)
    with checking(True), pytest.raises(KernelContractError):
        call(jnp.zeros((16, 8), jnp.float32))


def test_compat_shim_good_launch_roundtrips():
    from repro.kernels import pallas_compat as pc
    x = jnp.asarray(np.random.default_rng(0).standard_normal((16, 8)),
                    jnp.float32)
    call = pc.pallas_call(
        _copy_kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
        out_shape=_sds((16, 8)),
        interpret=True)
    with checking(True):
        np.testing.assert_allclose(np.asarray(call(x)), np.asarray(x))


def test_checking_toggle_restores_state():
    before = kernel_check.kernel_check_enabled()
    with checking(not before):
        assert kernel_check.kernel_check_enabled() is (not before)
    assert kernel_check.kernel_check_enabled() is before


# --------------------------------------------------------------------------
# kernel contract checker: the real kernels pass
# --------------------------------------------------------------------------

def test_existing_kernels_pass_contract_check(rng):
    from repro.kernels.attention.mha import mha_forward
    from repro.kernels.decode.decode_attn import paged_decode_attention

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    with checking(True):
        out = mha_forward(arr(2, 16, 8), arr(2, 16, 8), arr(2, 16, 8),
                          block_q=8, block_k=8, interpret=True)
        assert out.shape == (2, 16, 8)
        pt = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
        out = paged_decode_attention(
            arr(2, 1, 2, 8), arr(2, 1, 9, 4, 8), arr(2, 1, 9, 4, 8), pt,
            jnp.array([5, 9], jnp.int32), 1, interpret=True)
        assert out.shape == (2, 1, 2, 8)


def test_paged_kernel_passes_under_jit(rng):
    """Scalar-prefetch operands are tracers under jit: the checker must
    skip (not guess) value-dependent checks and still pass."""
    from repro.kernels.decode.decode_attn import paged_decode_attention

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    fn = jax.jit(lambda q, k, v, pt, ln, layer: paged_decode_attention(
        q, k, v, pt, ln, layer, interpret=True))
    pt = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
    with checking(True):
        out = fn(arr(2, 1, 2, 8), arr(2, 1, 9, 4, 8), arr(2, 1, 9, 4, 8), pt,
                 jnp.array([5, 9], jnp.int32), jnp.int32(1))
    assert out.shape == (2, 1, 2, 8)


# --------------------------------------------------------------------------
# retrace guard
# --------------------------------------------------------------------------

class _Fake:
    def __init__(self):
        self.compilations = {"prefill": 1, "decode": 1}


def test_retrace_guard_fails_on_growth():
    f = _Fake()
    with pytest.raises(RetraceError, match="decode: 1 -> 2"):
        with retrace_guard(f, label="fake"):
            f.compilations["decode"] += 1


def test_retrace_guard_passes_when_quiet():
    f = _Fake()
    with retrace_guard(f):
        f.compilations["decode"] += 0


def test_retrace_guard_allow_tolerates_known_compiles():
    f = _Fake()
    with retrace_guard(f, allow=1):
        f.compilations["decode"] += 1


def test_retrace_guard_int_census():
    class C:
        compilations = 0

    c = C()
    with pytest.raises(RetraceError):
        with retrace_guard(c):
            c.compilations = 2


def test_retrace_guard_engine_cold_vs_warm():
    """The live invariant: a cold engine compiles inside the guard and
    fails; the same engine, warmed, serves a fresh batch guarded clean."""
    from repro.configs.base import get_config, shrink
    from repro.core.famous import FamousConfig
    from repro.models import module, transformer
    from repro.serve.engine import Request, ServingEngine

    cfg = shrink(get_config("qwen2-7b"))
    params = module.init_params(transformer.model_spec(cfg),
                                jax.random.PRNGKey(0), jnp.float32)
    eng = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                        n_slots=2, max_seq=32, chunk=8)

    def reqs(rid0):
        return [Request(rid=rid0 + i, max_new=3,
                        tokens=[1, 2, 3, 4, 5 + i]) for i in range(2)]

    with pytest.raises(RetraceError):
        with retrace_guard(eng, label="cold engine"):
            eng.run(reqs(0))
    with retrace_guard(eng, label="warm engine"):
        eng.run(reqs(10))
