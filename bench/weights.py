"""Seeded random weights for a dense decoder, made by the benchmark.

Every value is a pure function of (seed, tensor, layer, element index):
a counter-based hash of the element's flat index, mapped to a uniform draw
of the tensor's standard deviation.  That gives three properties the
benchmark needs:

* the serving program's whole parameter tree is made on the device in one
  jitted call, in the type it is served in, with no full-size float32
  temporary (hash, scale and cast fuse into one elementwise loop);
* the plain reference (``bench/reference.py``) draws any single layer, or
  only the embedding rows a prompt uses, and gets bit-identical values
  without taking anything from the program;
* the seed is a traced operand, so every seed runs the same executable.

A model is described by the dict ``model_dims(config_file)`` returns.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# tensor id -> stable integer folded into the hash (never renumber)
TENSOR_IDS = {
    "embed": 1, "wq": 2, "wk": 3, "wv": 4, "wo": 5, "bq": 6, "bk": 7,
    "bv": 8, "ln1": 9, "ln2": 10, "w_gate": 11, "w_up": 12, "w_down": 13,
    "final_norm": 14, "lm_head": 15,
}
LAYER_TENSORS = ("ln1", "wq", "wk", "wv", "bq", "bk", "bv", "wo", "ln2",
                 "w_gate", "w_up", "w_down")
BIAS_STD = 0.1      # q/k/v biases, where the architecture has them
NORM_STD = 0.1      # norm scales are 1 + a uniform draw of this std


def model_dims(conf: dict) -> dict:
    """The widths a dense decoder needs, from a configuration file's keys
    (Hugging Face ``config.json`` names)."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return {
        "d": d, "h": h, "kv": conf["num_key_value_heads"],
        "dh": conf.get("head_dim") or d // h,
        "ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
        "layers": conf["num_hidden_layers"],
        "qkv_bias": bool(conf.get("qkv_bias", False)),
        "rope_theta": float(conf["rope_theta"]),
        "eps": float(conf["rms_norm_eps"]),
    }


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: its
    registered ``arch`` with the file's widths and depth."""
    import dataclasses
    from repro.configs.base import get_config
    m = model_dims(conf)
    return dataclasses.replace(
        get_config(conf["arch"]), num_layers=m["layers"], d_model=m["d"],
        num_heads=m["h"], num_kv_heads=m["kv"], head_dim=m["dh"],
        d_ff=m["ff"], vocab_size=m["vocab"], attention_bias=m["qkv_bias"],
        rope_theta=m["rope_theta"])


def tensor_spec(m: dict, name: str):
    """(per-layer shape, std, mean) of one tensor; None when the model has
    no such tensor."""
    d, h, kv, dh, ff, v = m["d"], m["h"], m["kv"], m["dh"], m["ff"], m["vocab"]
    if name in ("bq", "bk", "bv") and not m["qkv_bias"]:
        return None
    return {
        "embed": ((v, d), 1.0, 0.0),
        "wq": ((d, h, dh), 1 / math.sqrt(d), 0.0),
        "wk": ((d, kv, dh), 1 / math.sqrt(d), 0.0),
        "wv": ((d, kv, dh), 1 / math.sqrt(d), 0.0),
        "wo": ((h, dh, d), 1 / math.sqrt(h * dh), 0.0),
        "bq": ((h, dh), BIAS_STD, 0.0),
        "bk": ((kv, dh), BIAS_STD, 0.0),
        "bv": ((kv, dh), BIAS_STD, 0.0),
        "ln1": ((d,), NORM_STD, 1.0),
        "ln2": ((d,), NORM_STD, 1.0),
        "w_gate": ((d, ff), 1 / math.sqrt(d), 0.0),
        "w_up": ((d, ff), 1 / math.sqrt(d), 0.0),
        "w_down": ((ff, d), 1 / math.sqrt(ff), 0.0),
        "final_norm": ((d,), NORM_STD, 1.0),
        "lm_head": ((d, v), 1 / math.sqrt(d), 0.0),
    }[name]


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (the hash's key)."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def _u32(x: int):
    return jnp.uint32(x & 0xFFFFFFFF)


def _mix(x):
    """murmur3's 32-bit finaliser: a bijection that mixes every bit."""
    x = x ^ (x >> 16)
    x = x * _u32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * _u32(0xC2B2AE35)
    return x ^ (x >> 16)


def _key(seeds, name: str, layer):
    k = _mix(seeds[0] ^ _u32(TENSOR_IDS[name] * 0x9E3779B9))
    k = _mix(k ^ seeds[1])
    return _mix(k ^ (jnp.asarray(layer, jnp.uint32) * _u32(0x85EBCA77)
                     + _u32(0x165667B1)))


def _flat_index(shape, lead: int = 0):
    """uint32 flat (row-major) index over the trailing ``shape`` dims of an
    array with ``lead`` leading dims, as broadcast iotas."""
    full = (1,) * lead + tuple(shape)
    idx = jnp.zeros((1,) * len(full), jnp.uint32)
    stride = 1
    for ax in range(len(full) - 1, lead - 1, -1):
        io = jax.lax.broadcasted_iota(jnp.uint32, full, ax)
        idx = idx + io * _u32(stride)
        stride *= full[ax]
    return idx


def _values(key, idx, std: float, mean: float, dtype):
    h = _mix(_mix(idx ^ key) + key)
    u = ((h >> 8).astype(jnp.int32) - (1 << 23)).astype(jnp.float32) \
        * jnp.float32(2.0 ** -23)                           # [-1, 1)
    return (u * jnp.float32(std * math.sqrt(3.0)) + jnp.float32(mean)
            ).astype(dtype)


def draw(seeds, m: dict, name: str, dtype, layer=0):
    """One tensor (one layer's, for a per-layer tensor)."""
    shape, std, mean = tensor_spec(m, name)
    return _values(_key(seeds, name, layer), _flat_index(shape), std, mean,
                   dtype)


def draw_stacked(seeds, m: dict, name: str, dtype, n_layers: int):
    """A per-layer tensor for layers 0..n-1, stacked on a leading axis;
    slice l equals ``draw(..., layer=l)`` bit for bit."""
    shape, std, mean = tensor_spec(m, name)
    layer = jax.lax.broadcasted_iota(jnp.uint32, (n_layers,) + (1,) * len(shape), 0)
    idx = _flat_index(shape, lead=1)
    return _values(_key(seeds, name, layer), idx, std, mean, dtype)


def draw_rows(seeds, m: dict, name: str, rows, dtype):
    """Rows ``rows`` (int array, any shape) of a 2-D global tensor such as
    the embedding, without drawing the rest."""
    (_, width), std, mean = tensor_spec(m, name)
    col = jax.lax.broadcasted_iota(jnp.uint32, rows.shape + (width,),
                                   rows.ndim)
    idx = rows.astype(jnp.uint32)[..., None] * _u32(width) + col
    return _values(_key(seeds, name, 0), idx, std, mean, dtype)


def serving_params(m: dict, dtype):
    """A jitted ``seeds -> params`` for the serving program's parameter
    tree of an all-global-attention dense decoder (``repro.models``
    layout: embed, a stacked ``blocks.pos0``, final_norm, lm_head)."""
    L = m["layers"]

    def make(seeds):
        st = lambda n: draw_stacked(seeds, m, n, dtype, L)   # noqa: E731
        attn = {"wq": st("wq"), "wk": st("wk"), "wv": st("wv"),
                "wo": st("wo")}
        if m["qkv_bias"]:
            attn.update(bq=st("bq"), bk=st("bk"), bv=st("bv"))
        return {
            "embed": {"embedding": draw(seeds, m, "embed", dtype)},
            "blocks": {"pos0": {
                "ln1": {"scale": st("ln1")},
                "attn": attn,
                "ln2": {"scale": st("ln2")},
                "ffn": {"w_in": st("w_up"), "w_gate": st("w_gate"),
                        "w_out": st("w_down")},
            }},
            "final_norm": {"scale": draw(seeds, m, "final_norm", dtype)},
            "lm_head": {"w": draw(seeds, m, "lm_head", dtype)},
        }

    return jax.jit(make)
