import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell with
ShapeDtypeStruct stand-ins (zero allocation), prove the sharding is coherent,
and extract memory/cost/collective data for EXPERIMENTS.md §Dry-run/§Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh pod1
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, get_config, list_configs, supported_cells
from repro.core.famous import FamousConfig
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.parallel import sharding as shd
from repro.parallel.incontext import use_rules
from repro.roofline import analysis as roofline
from repro.roofline import hlo_cost
from repro.train import step as step_lib

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def train_cfgs(cfg):
    """Per-arch training precision policy (DESIGN.md §7 memory note)."""
    big = cfg.param_count() > 60e9
    return step_lib.TrainConfig(
        param_dtype=jnp.bfloat16 if big else jnp.float32,
        optimizer=step_lib.adamw.AdamWConfig(
            moment_dtype=jnp.bfloat16 if big else jnp.float32),
        remat=True,
    )


def lower_train(cfg, shape, mesh, rules=None, fcfg=None, tcfg=None):
    tcfg = tcfg or train_cfgs(cfg)
    fcfg = fcfg or FamousConfig(impl="xla")
    train_step = step_lib.make_train_step(cfg, fcfg, tcfg)
    state_shapes = step_lib.state_shapes(cfg, tcfg)
    state_sh = shd.tree_shardings(mesh, step_lib.state_logical_axes(cfg),
                                  rules, state_shapes)
    in_specs = specs_lib.train_input_specs(cfg, shape)
    batch_sh = {k: shd.batch_sharding(mesh, v.ndim, rules, v.shape)
                for k, v in in_specs.items()}
    metrics_sh = {k: shd.replicated(mesh)
                  for k in ("loss", "grad_norm", "lr_scale")}
    with jax.set_mesh(mesh), use_rules(rules):
        jitted = jax.jit(train_step,
                         in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, metrics_sh),
                         donate_argnums=0)
        return jitted.lower(state_shapes, in_specs)


def lower_serve(cfg, shape, mesh, rules=None, fcfg=None, dtype=jnp.bfloat16):
    fcfg = fcfg or FamousConfig(impl="xla")
    param_dtype = jnp.bfloat16
    spec = transformer.model_spec(cfg)
    from repro.models import module
    params_shapes = module.param_shapes(spec, param_dtype)
    params_sh = shd.tree_shardings(mesh, module.logical_axes(spec), rules,
                                   params_shapes)
    dec_specs = specs_lib.decode_input_specs(cfg, shape, dtype)
    cache_sh = shd.tree_shardings(mesh, transformer.cache_axes(cfg), rules,
                                  dec_specs["caches"])
    tok_sh = shd.batch_sharding(mesh, dec_specs["tokens"].ndim, rules,
                                dec_specs["tokens"].shape)
    len_sh = shd.batch_sharding(mesh, 1, rules, dec_specs["cache_len"].shape)
    pt_sh = shd.replicated(mesh)    # the engine's host-built page table
    logits_sh = shd.sharding_for_axes(
        mesh, ("batch", "vocab"), rules,
        (shape.global_batch, cfg.vocab_size))

    def serve_step(params, tokens, caches, cache_len, page_table):
        return transformer.decode_step(params, tokens, caches, cache_len,
                                       cfg, fcfg, page_table=page_table)

    with jax.set_mesh(mesh), use_rules(rules):
        jitted = jax.jit(serve_step,
                         in_shardings=(params_sh, tok_sh, cache_sh, len_sh,
                                       pt_sh),
                         out_shardings=((logits_sh, cache_sh)),
                         donate_argnums=2)
        return jitted.lower(params_shapes, dec_specs["tokens"],
                            dec_specs["caches"], dec_specs["cache_len"],
                            dec_specs["page_table"])


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str = OUT_DIR,
             rules=None, tag: str = "", fcfg=None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2" if multi_pod else "pod1"
    chips = 512 if multi_pod else 256
    t0 = time.time()
    if shape.kind in ("train", "prefill"):
        # prefill cells lower the train-style full forward (inference-prefill
        # is the forward pass; its cost profile is what the roofline needs).
        if shape.kind == "train":
            lowered = lower_train(cfg, shape, mesh, rules, fcfg=fcfg)
        else:
            lowered = lower_prefill(cfg, shape, mesh, rules, fcfg=fcfg)
    else:
        lowered = lower_serve(cfg, shape, mesh, rules, fcfg=fcfg)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = hlo_cost.cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    mem_per_dev = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    rf = roofline.analyse(
        arch, shape_name, mesh_name, cost=cost, hlo_text=hlo, chips=chips,
        model_flops_total=roofline.model_flops(cfg, shape),
        memory_per_device=mem_per_dev)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "ok": True,
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_bytes": mem_per_dev,
            "per_device_gib": round(mem_per_dev / 2**30, 3),
            "fits_16gib": bool(mem_per_dev <= 16 * 2**30),
        },
        "cost": {k: v for k, v in cost.items()
                 if not k.startswith("utilization")},
        "roofline": rf.row(),
    }
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_name}{('__' + tag) if tag else ''}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1, default=str)
    return result


def lower_prefill(cfg, shape, mesh, rules=None, fcfg=None,
                  dtype=jnp.bfloat16):
    """Inference prefill: the full-sequence forward to last-token logits."""
    fcfg = fcfg or FamousConfig(impl="xla")
    from repro.models import module
    spec = transformer.model_spec(cfg)
    params_shapes = module.param_shapes(spec, jnp.bfloat16)
    params_sh = shd.tree_shardings(mesh, module.logical_axes(spec), rules,
                                   params_shapes)
    in_specs = specs_lib.train_input_specs(cfg, shape, dtype)
    in_sh = shd.batch_sharding(mesh, in_specs["inputs"].ndim, rules,
                               in_specs["inputs"].shape)
    logits_sh = shd.sharding_for_axes(
        mesh, ("batch", "vocab"), rules,
        (shape.global_batch, cfg.vocab_size))

    def prefill_step(params, inputs):
        x = transformer.forward(params, inputs, cfg, fcfg, remat=False,
                                return_hidden=True)
        return transformer.logits_fn(params, x[:, -1:], cfg)[:, 0]

    with jax.set_mesh(mesh), use_rules(rules):
        jitted = jax.jit(prefill_step,
                         in_shardings=(params_sh, in_sh),
                         out_shardings=logits_sh)
        return jitted.lower(params_shapes, in_specs["inputs"])


def all_cells() -> list[tuple[str, str]]:
    cells = []
    for arch in list_configs():
        if arch == "famous-bert":
            continue  # paper topology exercised by benchmarks, not the grid
        for s in supported_cells(arch):
            cells.append((arch, s))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["pod1", "pod2", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.list:
        for a, s in all_cells():
            print(f"{a} {s}")
        return

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "pod2" if mp else "pod1"
            fname = os.path.join(args.out, f"{arch}__{shape}__{mesh_name}.json")
            if args.skip_existing and os.path.exists(fname):
                print(f"SKIP {arch} {shape} {mesh_name}")
                continue
            try:
                r = run_cell(arch, shape, mp, args.out)
                rr = r["roofline"]
                print(f"OK   {arch:22s} {shape:12s} {mesh_name} "
                      f"compile={r['t_compile_s']:>6.1f}s "
                      f"mem/dev={r['memory']['per_device_gib']:>7.3f}GiB "
                      f"dom={rr['dominant']:10s} "
                      f"frac={rr['roofline_fraction']:.3f}", flush=True)
            except Exception as e:
                failures.append((arch, shape, mesh_name, repr(e)))
                print(f"FAIL {arch} {shape} {mesh_name}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print(" ", *f)
        raise SystemExit(1)
    print("\nALL CELLS PASSED")


if __name__ == "__main__":
    main()
