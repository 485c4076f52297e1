"""End-to-end training driver: train a ~100M-parameter LM for a few hundred
steps through the full production path (sharded state, deterministic
pipeline, fault-tolerant trainer, async checkpoints).

Default runs a ~20M model for 200 steps so it finishes quickly on this
1-core CPU container; pass ``--m100`` for the full ~100M × 300-step run
(same code path, ~40x more FLOPs).

    PYTHONPATH=src python examples/train_lm.py [--m100] [--steps N]
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.train import build
from repro.train import trainer as trainer_lib


def lm_config(m100: bool) -> ModelConfig:
    if m100:  # ~103M params
        return ModelConfig(name="lm100m", family="dense", num_layers=12,
                           d_model=768, num_heads=12, num_kv_heads=12,
                           d_ff=2048, vocab_size=32768, tie_embeddings=True)
    return ModelConfig(name="lm20m", family="dense", num_layers=6,
                       d_model=384, num_heads=6, num_kv_heads=6,
                       d_ff=1024, vocab_size=16384, tie_embeddings=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m100", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = lm_config(args.m100)
    shape = ShapeConfig("train_ex", args.seq, args.batch, "train")

    import repro.configs.base as base
    base._REGISTRY.setdefault(cfg.name, cfg)
    from repro.launch.mesh import make_smoke_mesh
    from repro.train.step import TrainConfig
    _, mesh, state, jitted, batch_fn, state_sh = build(
        cfg.name, shape, smoke=False, mesh=make_smoke_mesh(), seed=0,
        tcfg=TrainConfig(compute_dtype=jnp.float32))
    print(f"model: {cfg.name} ({cfg.param_count()/1e6:.1f}M params), "
          f"{args.steps} steps of {args.batch}x{args.seq} tokens")

    tr = trainer_lib.Trainer(
        jitted, state, batch_fn,
        trainer_lib.TrainerConfig(total_steps=args.steps, ckpt_every=50,
                                  ckpt_dir=args.ckpt_dir))
    with jax.set_mesh(mesh):
        tr.run()
    log = tr.metrics_log
    for m in log[:: max(len(log) // 10, 1)]:
        print(f"  step {m['step']:>4d}  loss {m['loss']:.4f}  "
              f"gnorm {m['grad_norm']:.3f}  {m['dt']*1e3:.0f} ms")
    print(f"final loss: {log[-1]['loss']:.4f} (start {log[0]['loss']:.4f})")
    assert log[-1]["loss"] < log[0]["loss"]


if __name__ == "__main__":
    main()
