"""A qwen2-shaped decoder at a size the CPU serves in seconds, and mixes
shaped like the benchmark's, for the harness's CPU tests."""
from __future__ import annotations

import json
import pathlib

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny", "arch": "qwen2-7b",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "qkv_bias": True,
    "weights": "bench/weights.py", "reference": "bench/reference.py",
    "engine": {"n_slots": 4, "max_seq": 128, "page_size": 16, "chunk": 16,
               "token_budget": 0, "prefix_cache": True, "n_pages": 33},
}
MIXES = {
    "open": {"loop": "open", "rate_per_s": 40.0, "ramp_s": 0.3, "block": 8, "order_seed": 3,
             "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                        "min": 8, "max": 64},
             "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                        "min": 4, "max": 24}},
    "closed": {"loop": "closed", "clients": 4, "ramp_s": 0.3, "block": 8, "order_seed": 3,
               "prompt": {"dist": "uniform", "min": 20, "max": 60},
               "output": {"dist": "uniform", "min": 4, "max": 12}},
    "long": {"loop": "closed", "clients": 4, "ramp_s": 0.3, "block": 8, "order_seed": 3,
             "prompt": {"dist": "uniform", "min": 20, "max": 60},
             "output": {"dist": "uniform", "min": 24, "max": 48}},
    "docs": {"loop": "closed", "clients": 4, "ramp_s": 0.3, "block": 8, "order_seed": 3,
             "documents": {"count": 2, "length": 48},
             "prompt": {"dist": "uniform", "min": 4, "max": 12},
             "output": {"dist": "uniform", "min": 4, "max": 12}},
}
CHECK = {"s_pad": 128, "b_pad": 4, "n_score": 64, "limit": 1e-3}


def cell(mix: str = "open", **check) -> harness.Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.Cell(name=f"tiny.{mix}", chips=1, config=dict(CONFIG),
                        mix=MIXES[mix], check=dict(CHECK, **check),
                        end_to_end=spec["end_to_end"],
                        per_layer=spec["per_layer"])
