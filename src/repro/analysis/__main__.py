"""``python -m repro.analysis`` — run every static-analysis pass.

Passes (any can be skipped; exit status is nonzero if any ran and
failed):

1. **lint** — AST rules over ``src/repro`` compared against the
   checked-in baseline (``analysis/lint_baseline.txt``): new findings
   fail, stale baseline entries are reported.  ``--update-baseline``
   rewrites the baseline to the current findings instead of failing.
2. **kernel-check** — every Pallas kernel family launched once at tiny
   shapes in interpret mode with the contract checker enabled: BlockSpec
   divisibility, index_map arity/bounds, output-grid coverage and the
   VMEM budget are validated against live launches, not just fixtures.
3. **retrace** — tiny warmed serving engines — plain, speculative (verify
   executable), int8 paged, and (when >= 2 devices are visible) TP=2
   mesh-sharded — must each serve a fresh batch under
   :func:`repro.analysis.retrace_guard.retrace_guard` with zero new
   compilations (the O(1)-executables invariant from PR 3).

``scripts/ci.sh`` runs this before the test suite.
"""
from __future__ import annotations

import argparse
import sys


def run_lint(update_baseline: bool) -> int:
    import os

    from repro.analysis import lint

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = lint.lint_paths(root)
    if update_baseline:
        lint.write_baseline(findings)
        print(f"lint: baseline rewritten with {len(findings)} finding(s) "
              f"-> {lint.BASELINE_FILE}")
        return 0
    new, stale = lint.compare_to_baseline(findings, lint.load_baseline())
    for f in new:
        print(f"lint: NEW {f}")
    for fp in stale:
        print(f"lint: stale baseline entry (fixed? remove it): {fp}")
    n_base = len(findings) - len(new)
    print(f"lint: {len(findings)} finding(s): {len(new)} new, "
          f"{n_base} baselined, {len(stale)} stale baseline entr(ies)")
    return 1 if new else 0


def run_kernel_check() -> int:
    """Launch each kernel family once, tiny, with checking on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import kernel_check
    from repro.kernels.attention.mha import mha_backward, mha_forward
    from repro.kernels.decode.chunk_prefill import (paged_chunk_prefill,
                                                    paged_chunk_prefill_int8)
    from repro.kernels.decode.decode_attn import (
        decode_attention, paged_decode_attention,
        paged_decode_attention_int8)
    from repro.kernels.qkv.qkv_proj import matmul_tiled
    from repro.kernels.scan.linear_scan import rglru_scan, wkv6_scan

    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def qarr(*shape):
        return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)

    def sarr(*shape):
        return jnp.asarray(rng.uniform(1e-3, 2e-2, shape), jnp.float32)

    failures = 0
    with kernel_check.checking(True):
        launches = []
        q, k, v = arr(2, 16, 8), arr(2, 16, 8), arr(2, 16, 8)
        launches.append(("attention/mha_forward", lambda: mha_forward(
            q, k, v, block_q=8, block_k=8, interpret=True,
            return_lse=True)))
        out, lse = mha_forward(q, k, v, block_q=8, block_k=8,
                               interpret=True, return_lse=True)
        launches.append(("attention/mha_backward", lambda: mha_backward(
            q, k, v, out, lse, arr(2, 16, 8), block_q=8, block_k=8,
            interpret=True)))
        launches.append(("qkv/matmul_tiled", lambda: matmul_tiled(
            arr(16, 256), arr(256, 256), block_t=8, block_f=128,
            block_d=128, interpret=True)))
        launches.append(("decode/decode_attention", lambda: decode_attention(
            arr(2, 2, 8), arr(2, 16, 8), arr(2, 16, 8),
            jnp.array([5, 9], jnp.int32), block_k=8, interpret=True)))
        pt = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
        launches.append(("decode/paged_decode_attention",
                         lambda: paged_decode_attention(
                             arr(2, 1, 2, 8), arr(2, 1, 9, 4, 8),
                             arr(2, 1, 9, 4, 8), pt,
                             jnp.array([5, 9], jnp.int32), 1,
                             interpret=True)))
        launches.append(("decode/paged_decode_attention_int8",
                         lambda: paged_decode_attention_int8(
                             arr(2, 1, 2, 8), qarr(2, 1, 9, 4, 8),
                             qarr(2, 1, 9, 4, 8), sarr(2, 1, 9, 1, 4), sarr(2, 1, 9, 1, 4),
                             pt, jnp.array([5, 9], jnp.int32), 1,
                             interpret=True)))
        launches.append(("decode/paged_chunk_prefill",
                         lambda: paged_chunk_prefill(
                             arr(2, 1, 8, 8), arr(2, 1, 9, 4, 8),
                             arr(2, 1, 9, 4, 8), pt, 4, 1, chunk=4,
                             interpret=True)))
        launches.append(("decode/paged_chunk_prefill_int8",
                         lambda: paged_chunk_prefill_int8(
                             arr(2, 1, 8, 8), qarr(2, 1, 9, 4, 8),
                             qarr(2, 1, 9, 4, 8), sarr(2, 1, 9, 1, 4), sarr(2, 1, 9, 1, 4),
                             pt, 4, 1, chunk=4, interpret=True)))
        launches.append(("scan/rglru_scan", lambda: rglru_scan(
            arr(2, 16, 256), arr(2, 16, 256), block_r=128, block_s=8,
            interpret=True)))
        launches.append(("scan/wkv6_scan", lambda: wkv6_scan(
            arr(2, 8, 8), arr(2, 8, 8), arr(2, 8, 8),
            -jnp.abs(arr(2, 8, 8)), arr(2, 8), chunk=8, interpret=True)))
        for name, launch in launches:
            try:
                jax.block_until_ready(launch())
                print(f"kernel-check: ok {name}")
            except kernel_check.KernelContractError as e:
                print(f"kernel-check: FAIL {name}: {e}")
                failures += 1
    return 1 if failures else 0


def run_retrace() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.retrace_guard import RetraceError, retrace_guard
    from repro.configs.base import get_config, shrink
    from repro.core.famous import FamousConfig
    from repro.models import module, transformer
    from repro.serve.engine import Request, ServingEngine

    cfg = shrink(get_config("qwen2-7b"))
    params = module.init_params(transformer.model_spec(cfg),
                                jax.random.PRNGKey(0), jnp.float32)
    engine = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                           n_slots=2, max_seq=32, chunk=8)
    rng = np.random.default_rng(0)

    def reqs(rid0):
        return [Request(rid=rid0 + i, max_new=3,
                        tokens=list(rng.integers(0, cfg.vocab_size, 5 + i)))
                for i in range(3)]

    engine.run(reqs(0))              # warmup compiles the two executables
    try:
        with retrace_guard(engine, label="warm decode loop"):
            engine.run(reqs(10))
    except RetraceError as e:
        print(f"retrace: FAIL {e}")
        return 1
    print(f"retrace: ok — warm engine served a fresh batch with zero new "
          f"compilations (census {engine.compilations})")
    # speculative engine: the verify executable replaces decode; a warm
    # engine must serve a fresh mixed workload (varying prompts, so draft
    # lengths 0..draft_k all occur) with zero new compilations
    spec = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                         n_slots=2, max_seq=32, chunk=8,
                         speculative=True, draft_k=3)
    spec.run(reqs(20))
    try:
        with retrace_guard(spec, label="warm speculative decode loop"):
            spec.run(reqs(30))
    except RetraceError as e:
        print(f"retrace: FAIL {e}")
        return 1
    print(f"retrace: ok — warm speculative engine served a fresh batch with "
          f"zero new compilations (census {spec.compilations})")
    # int8 paged engine: quantize-on-write and the scale-pool operands ride
    # the same executables — kv_dtype is a cache-structure choice, not a
    # static jit argument, so the census must stay O(1) here too
    q8 = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                       n_slots=2, max_seq=32, chunk=8, page_size=8,
                       kv_dtype="int8")
    q8.run(reqs(40))
    try:
        with retrace_guard(q8, label="warm int8 paged decode loop"):
            q8.run(reqs(50))
    except RetraceError as e:
        print(f"retrace: FAIL {e}")
        return 1
    print(f"retrace: ok — warm int8 paged engine served a fresh batch with "
          f"zero new compilations (census {q8.compilations})")
    # mesh-sharded engine: out_shardings and the device_put placement must
    # not fork executables — a warm TP=2 engine serves a fresh batch with
    # zero new compilations too.  Needs >= 2 devices; the ci.sh
    # `== multi-device ==` stage runs this module under
    # XLA_FLAGS=--xla_force_host_platform_device_count=8.
    if jax.device_count() >= 2:
        from repro.launch.mesh import make_serving_mesh
        tp2 = ServingEngine(params, cfg, FamousConfig(impl="xla"),
                            n_slots=2, max_seq=32, chunk=8,
                            mesh=make_serving_mesh(tp=2))
        tp2.run(reqs(60))
        try:
            with retrace_guard(tp2, label="warm TP=2 sharded decode loop"):
                tp2.run(reqs(70))
        except RetraceError as e:
            print(f"retrace: FAIL {e}")
            return 1
        print(f"retrace: ok — warm TP=2 sharded engine served a fresh batch "
              f"with zero new compilations (census {tp2.compilations})")
    else:
        print("retrace: note — TP=2 sharded pass skipped (1 visible device; "
              "the ci.sh multi-device stage forces 8)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repro static-analysis passes (lint, kernel contract "
                    "check, retrace guard)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the lint baseline instead of failing on "
                         "new findings")
    ap.add_argument("--no-lint", action="store_true")
    ap.add_argument("--no-kernel-check", action="store_true")
    ap.add_argument("--no-retrace", action="store_true")
    args = ap.parse_args(argv)

    status = 0
    if not args.no_lint:
        print("== repro.analysis: lint ==")
        status |= run_lint(args.update_baseline)
    if not args.no_kernel_check:
        print("== repro.analysis: kernel contract check ==")
        status |= run_kernel_check()
    if not args.no_retrace:
        print("== repro.analysis: retrace guard ==")
        status |= run_retrace()
    print("repro.analysis: " + ("FAILED" if status else "clean"))
    return status


if __name__ == "__main__":
    sys.exit(main())
