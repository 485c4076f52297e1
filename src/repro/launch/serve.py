"""Serving launcher: ``python -m repro.launch.serve --arch <id> --size
{published,shrink}``.

:func:`build_engine` is the one place a serving engine is put together —
config at the requested size, seeded random params, kernel impl and
dtypes — and both this CLI and ``chip_smoke.py`` call it.  ``--size`` is
always explicit: ``published`` serves the architecture at its published
widths, ``shrink`` a reduced same-family config for CPU smoke runs.  The
platform decides the rest: on a TPU the engine runs the Pallas kernels
with bf16 params and KV; elsewhere the XLA path in float32.
``--trace-out DIR`` captures a ``jax.profiler`` trace of the served run,
with a Perfetto file holding the engine's ``engine.*`` spans beside the
device's ops.
"""
from __future__ import annotations

import argparse
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RunConfig, get_config, shrink
from repro.core.famous import FamousConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import module, transformer
from repro.obs.runtime import Observer
from repro.obs.trace import now
from repro.parallel import sharding as shardlib
from repro.serve.engine import Request, ServingEngine

SIZES = ("published", "shrink")


def platform_defaults() -> tuple[str, object]:
    """(kernel impl, param/KV dtype) for the backend JAX runs on: the
    compiled Pallas kernels in bf16 on a TPU, the XLA path in float32
    elsewhere (Pallas would only interpret there)."""
    if jax.default_backend() == "tpu":
        return "pallas", jnp.bfloat16
    return "xla", jnp.float32


def profiler_span(name: str, **args):
    """One span on the ``jax.profiler`` timeline: the engine's step as a
    step marker (``StepTraceAnnotation``), any other span a
    ``TraceAnnotation``.  The factory an Observer is given, so that
    ``repro.obs`` stays jax-free."""
    if name == "engine.step":
        return jax.profiler.StepTraceAnnotation(name, **args)
    return jax.profiler.TraceAnnotation(name, **args)


def build_engine(arch: str, *, size: str, seed: int = 0, impl=None,
                 params=None, mesh=None, observer=None,
                 **engine_kw) -> ServingEngine:
    """A :class:`ServingEngine` for ``arch`` at ``size`` ("published" or
    "shrink").

    The param/KV dtype comes from :func:`platform_defaults`, and so does
    ``impl`` unless given (the XLA path serves as the Pallas path's
    reference on the chip).  ``params`` (from another engine's ``.params``)
    shares weights between engines; otherwise they are drawn from ``seed``,
    directly into their tensor-parallel placement when ``mesh`` is given.
    Every other keyword goes to :class:`ServingEngine` unchanged."""
    assert size in SIZES, size
    cfg = get_config(arch)
    if size == "shrink":
        cfg = shrink(cfg)
    if cfg.is_encoder_only:
        raise ValueError(f"{cfg.name} is encoder-only: no decode serving")
    d_impl, dtype = platform_defaults()
    impl = impl or d_impl
    if params is None:
        spec = transformer.model_spec(cfg)
        places = None
        if mesh is not None:
            places = shardlib.tree_shardings(
                mesh, transformer.param_axes(cfg),
                engine_kw.get("sharding_rules") or shardlib.SERVE_TP_RULES,
                module.param_shapes(spec, dtype))
        params = module.init_params(spec, jax.random.PRNGKey(seed), dtype,
                                    shardings=places)
    return ServingEngine(params, cfg, FamousConfig(impl=impl), dtype=dtype,
                         mesh=mesh, observer=observer, **engine_kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="famous-bert")
    ap.add_argument("--size", choices=SIZES, required=True,
                    help="published: the architecture at its published "
                         "widths; shrink: a reduced same-family config for "
                         "CPU smoke runs")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-mode", choices=("chunked", "monolithic"),
                    default="chunked",
                    help="chunked: fixed-shape prefill chunks interleaved "
                         "with decode under the token budget (O(1) "
                         "executables); monolithic: whole-prompt prefill at "
                         "admission (legacy comparison baseline)")
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk length (must divide max-seq)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="per-step token budget for the scheduler; "
                         "0 = slots + chunk (one chunk per step while "
                         "decoding). Larger = faster TTFT for long prompts, "
                         "burstier decode (see docs/serving.md)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for every request "
                         "(0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k filter (0 = full vocab)")
    ap.add_argument("--cache-kind", choices=("contiguous", "paged"),
                    default="contiguous",
                    help="KV-cache layout: per-slot max_seq stripes, or a "
                         "shared page pool with per-slot page tables")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="pool pages incl. the null page (paged mode); "
                         "0 = full contiguous-equivalent capacity — pass "
                         "less to oversubscribe")
    ap.add_argument("--kv-dtype", choices=("fp", "int8"), default="fp",
                    help="KV-cache element type (paged only): int8 stores "
                         "quantized pages plus per-token-per-kv-head fp32 "
                         "scale pools and dequantizes inside the attention "
                         "kernels — ~3.2x the live-token capacity per byte "
                         "at dh=16, lossy (bounded logit drift; see "
                         "docs/serving.md)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share identical full prompt blocks across "
                         "requests via refcounted pages (paged + chunked "
                         "only; recurrent/hybrid archs fall back to cold "
                         "prefill)")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-model-free speculative decoding: a host-side "
                         "prompt-lookup drafter proposes tokens, one batched "
                         "verify step accepts the longest matching prefix "
                         "(token-identical to plain decode; recurrent/hybrid "
                         "archs fall back to plain decode)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="max draft tokens per verify step (the verify "
                         "executable's fixed width is draft-k + 1)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor parallelism: shard attention heads / kv "
                         "heads / FFN hidden over a 'model' mesh axis of "
                         "this size (needs tp visible devices; on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count="
                         "N). 1 = unsharded single-device baseline")
    ap.add_argument("--mesh-shape", default="",
                    help="explicit 'data,model' mesh shape, e.g. '1,2' "
                         "(overrides --tp; the data axis is reserved for "
                         "engine replicas)")
    ap.add_argument("--metrics", action="store_true",
                    help="attach an Observer and print the Prometheus text "
                         "exposition after the run (host-pure counters/"
                         "gauges/histograms; see docs/observability.md)")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="also write the exposition to PATH (implies "
                         "--metrics)")
    ap.add_argument("--trace-out", default="", metavar="DIR",
                    help="capture a jax.profiler trace of the served run "
                         "into DIR, with a Perfetto file "
                         "(perfetto_trace.json.gz, ui.perfetto.dev) holding "
                         "the engine.* spans beside the device's ops "
                         "(implies --metrics)")
    args = ap.parse_args()

    if args.mesh_shape:
        dp, tp = (int(x) for x in args.mesh_shape.split(","))
    else:
        dp, tp = 1, args.tp
    run = RunConfig(arch=args.arch, tp=tp, dp=dp)
    mesh = run.make_mesh()   # None when tp == dp == 1

    use_compile_cache()
    if args.trace_out:
        obs = Observer(span=profiler_span)
    elif args.metrics or args.metrics_out:
        obs = Observer()
    else:
        obs = None
    engine = build_engine(args.arch, size=args.size, seed=args.seed,
                          mesh=mesh, observer=obs,
                          n_slots=args.slots, max_seq=args.max_seq,
                          cache_kind=args.cache_kind,
                          page_size=args.page_size,
                          n_pages=args.n_pages or None,
                          prefill_mode=args.prefill_mode,
                          chunk=args.chunk,
                          token_budget=args.token_budget,
                          prefix_cache=args.prefix_cache,
                          speculative=args.speculative,
                          draft_k=args.draft_k,
                          kv_dtype=args.kv_dtype)
    cfg = engine.cfg
    if mesh is not None:
        print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} over "
              f"{mesh.devices.size} of {jax.device_count()} devices; "
              f"kv/state cache {engine.cache_bytes_per_device()} "
              f"bytes/device")
    if engine.paged:
        cache_bytes = sum(b.size * b.dtype.itemsize for b in
                          jax.tree_util.tree_leaves(engine.caches))
        pc = engine.pcfg
        print(f"kv cache: paged dtype={engine.kv_dtype}, "
              f"{pc.n_pages} pages x {pc.page_size} tokens "
              f"({cache_bytes // pc.n_pages} bytes/page incl. scales), "
              f"live-token capacity={(pc.n_pages - 1) * pc.page_size}")
    rng = np.random.default_rng(args.seed)
    # --prefix-cache demo: every request shares a "system prompt" head, the
    # workload prefix caching exists for (otherwise prompts are disjoint)
    shared = (list(rng.integers(0, cfg.vocab_size, size=args.max_seq // 4))
              if args.prefix_cache else [])
    tail_hi = max(5, min(32, args.max_seq - len(shared) - args.max_new))
    reqs = [Request(rid=i,
                    tokens=shared + list(rng.integers(0, cfg.vocab_size,
                                                      size=rng.integers(4, tail_hi))),
                    max_new=args.max_new,
                    temperature=args.temperature, top_k=args.top_k,
                    seed=args.seed + i)
            for i in range(args.requests)]
    tracing = contextlib.nullcontext()
    if args.trace_out:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the engine's spans, not every call
        tracing = jax.profiler.trace(args.trace_out,
                                     create_perfetto_trace=True,
                                     profiler_options=opts)
    with tracing:
        t0 = now()
        done = engine.run(reqs)
        dt = now() - t0
    tok = sum(len(r.out) for r in done)
    census = engine.compilations
    print(f"served {len(done)} requests, {tok} tokens in {dt:.2f}s "
          f"({tok/dt:.1f} tok/s), executables: prefill={census['prefill']} "
          f"decode={census['decode']} verify={census['verify']} "
          f"clear={census['clear']} "
          f"(mode={args.prefill_mode}, cache={engine.cache_kind})")
    if args.speculative:
        print(f"speculative: active={engine.speculative_active}, "
              f"draft_k={args.draft_k}, "
              f"{engine.spec_accepted}/{engine.spec_drafted} drafts accepted "
              f"(rate {engine.acceptance_rate:.2f}), "
              f"{engine.accepted_per_step:.2f} tokens/verify-step over "
              f"{engine.spec_steps} steps")
    if engine.paged:
        print(f"page pool: {engine.pcfg.n_pages} pages x "
              f"{engine.pcfg.page_size} tokens, "
              f"{engine.alloc.free_pages} free after drain")
    if args.prefix_cache:
        print(f"prefix cache: active={engine.prefix_cache_active}, "
              f"{engine.prefix_hit_pages} pages / "
              f"{engine.prefix_hit_tokens} tokens reused, "
              f"{engine.alloc.cached_free_pages} pages warm on the LRU")
    for r in done[:3]:
        f = engine.sched.fairness(r.rid)
        ttft = (r.t_first - r.t_submit) * 1e3 if r.t_first else float("nan")
        print(f"  req {r.rid}: prompt[:6]={r.tokens[:6]} -> out={r.out} "
              f"(ttft={ttft:.0f}ms, prefill_toks={f.get('prefill_tokens', 0)},"
              f" preemptions={f.get('preemptions', 0)})")
    if args.trace_out:
        print(f"trace: jax.profiler trace -> {args.trace_out}")
    if obs is not None:
        text = obs.prometheus_text()
        if args.metrics_out:
            with open(args.metrics_out, "w", encoding="utf-8") as fh:
                fh.write(text)
            print(f"metrics: exposition -> {args.metrics_out}")
        if args.metrics:
            print("== metrics (prometheus text exposition) ==")
            print(text, end="")


if __name__ == "__main__":
    main()
