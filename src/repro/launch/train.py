"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Wires the full production path end to end: config -> mesh -> shardings ->
deterministic sharded data pipeline -> jitted train step -> fault-tolerant
Trainer with async checkpointing and SIGTERM-preemption handling.  On this
CPU container it runs reduced configs (use ``--smoke``); on a real cluster
the same file runs the full configs (the mesh/sharding logic is identical —
proven by the dry-run).
"""
from __future__ import annotations

import argparse
import functools
import signal

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, SMOKE_SHAPES, ShapeConfig, get_config, shrink
from repro.core.famous import FamousConfig
from repro.data import pipeline
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.parallel import sharding as shd
from repro.train import step as step_lib
from repro.train import trainer as trainer_lib


def build(arch: str, shape: ShapeConfig, *, smoke: bool, mesh=None,
          tcfg: step_lib.TrainConfig | None = None,
          fcfg: FamousConfig | None = None, seed: int = 0):
    cfg = get_config(arch)
    if smoke:
        cfg = shrink(cfg)
    fcfg = fcfg or FamousConfig(impl="xla")
    tcfg = tcfg or step_lib.TrainConfig(
        compute_dtype=jnp.float32 if smoke else jnp.bfloat16)
    mesh = mesh or (make_smoke_mesh() if smoke else make_production_mesh())

    state_axes = step_lib.state_logical_axes(cfg)
    state_shapes = step_lib.state_shapes(cfg, tcfg)
    state_sh = shd.tree_shardings(mesh, state_axes, None, state_shapes)
    train_step = step_lib.make_train_step(cfg, fcfg, tcfg)

    with jax.set_mesh(mesh):
        state = jax.jit(
            functools.partial(step_lib.init_state, cfg, tcfg),
            out_shardings=state_sh)(jax.random.PRNGKey(seed))
        jitted = jax.jit(train_step, in_shardings=(state_sh, None),
                         out_shardings=(state_sh, None), donate_argnums=0)

    batch_sharding = shd.batch_sharding(
        mesh, 2, None, (shape.global_batch, shape.seq_len))

    def batch_fn(step: int):
        return pipeline.make_global_batch(cfg, shape, seed, step,
                                          batch_sharding)

    return cfg, mesh, state, jitted, batch_fn, state_sh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="famous-bert")
    ap.add_argument("--shape", default="smoke_train")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    shape = {**SHAPES, **SMOKE_SHAPES}[args.shape]
    cfg, mesh, state, jitted, batch_fn, state_sh = build(
        args.arch, shape, smoke=args.smoke, seed=args.seed)

    tcfg = trainer_lib.TrainerConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir)
    tr = trainer_lib.Trainer(jitted, state, batch_fn, tcfg,
                             state_shardings=state_sh)
    signal.signal(signal.SIGTERM, lambda *_: tr.request_stop())

    with jax.set_mesh(mesh):
        tr.run()
    for m in tr.metrics_log[-5:]:
        print({k: round(v, 4) if isinstance(v, float) else v
               for k, v in m.items()})
    print(f"done: arch={cfg.name} steps={int(tr.state['step'])} "
          f"restarts={tr.restarts} stragglers={len(tr.straggler_events)}")


if __name__ == "__main__":
    main()
