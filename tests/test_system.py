"""End-to-end system behaviour: the full launcher path (config -> mesh ->
sharded state -> deterministic pipeline -> fault-tolerant trainer) trains a
real (reduced) model and produces a decreasing loss; the serving launcher
path generates tokens; the dry-run machinery lowers a production cell."""
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SMOKE_SHAPES
from repro.launch.train import build
from repro.train import trainer as trainer_lib

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.slow
def test_end_to_end_training_loss_decreases(tmp_path):
    cfg, mesh, state, jitted, batch_fn, state_sh = build(
        "famous-bert", SMOKE_SHAPES["smoke_train"], smoke=True)
    tr = trainer_lib.Trainer(
        jitted, state, batch_fn,
        trainer_lib.TrainerConfig(total_steps=20, ckpt_every=10,
                                  ckpt_dir=str(tmp_path / "e2e")))
    with jax.set_mesh(mesh):
        tr.run()
    losses = [m["loss"] for m in tr.metrics_log]
    assert len(losses) == 20
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    assert all(np.isfinite(l) for l in losses)


@pytest.mark.slow
def test_dryrun_cli_single_cell(tmp_path):
    """The dry-run entry point lowers+compiles a production cell in a fresh
    process (512 placeholder devices must not leak into this test runner)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "deepseek-7b", "--shape", "prefill_32k", "--mesh", "pod1",
         "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=1200)
    assert "ALL CELLS PASSED" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
    assert len(jax.devices()) == 1  # flag did not leak


def test_serve_cli_smoke():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "deepseek-7b",
         "--size", "shrink", "--requests", "3", "--max-new", "3"],
        capture_output=True, text=True, env=env, timeout=900)
    assert "served 3 requests" in out.stdout, out.stdout + out.stderr[-2000:]
