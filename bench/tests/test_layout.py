"""BENCHMARK.json names every piece of a cell, and the harness finds each
by that name alone; the command refuses to measure without a TPU."""
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_is_found_by_name(w):
    cell = harness.load_cell(w["name"])
    assert cell.chips == w["chips"] == 1
    assert cell.config["name"] == w["config"]
    assert {"loop", "prompt", "output", "ramp_s"} <= set(cell.mix)
    assert {"s_pad", "b_pad", "n_score", "limit"} <= set(cell.check)
    assert cell.check["s_pad"] >= cell.config["engine"]["max_seq"]
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    for m in cell.per_layer:
        assert m["moves"] in names, (m["name"], m["moves"])
    assert callable(cell.reference.score)
    assert callable(cell.weights.serving_params)


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_cut(c):
    assert c["file"] == f"bench/configs/{c['name']}.json"
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["reduced"] == c["reduced"]
    assert conf["source"] == c["source"]
    for key in c["reduced"]:
        assert key in conf["published"] and conf[key] != conf["published"][key]
        assert not key.endswith(("_dim", "_rank", "_size"))


def test_per_layer_metrics_name_a_layer_and_what_they_move():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["layer"] and m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            assert m["source"] == "device_trace"


def _refusal(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-7b.chat",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _refusal(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr and "cpu" in p.stderr


def test_run_refuses_with_only_the_benchmark(tmp_path):
    """A directory holding BENCHMARK.json and bench/ but not the program."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _refusal(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
