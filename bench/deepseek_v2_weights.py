"""Seeded random weights for DeepSeek-V2's block layout (latent attention,
a leading dense layer, then layers of routed and shared experts), made by
the benchmark with ``bench/weights.py``'s counter hash.

Every value is a pure function of (seed, tensor, layer, element index), as
there, so the serving program's tree is made on the device in one jitted
call and the reference (``bench/deepseek_v2_reference.py``) draws any one
layer, or any one expert of a layer, bit for bit.  An expert's values are
those of its rows in the layer's whole ``(n_routed, ...)`` tensor: the
chip's share draws only the experts it holds, and every share of the
deployment draws the same values for an expert.

Tensor ids continue ``bench/weights.py``'s table; a name that table has
keeps its id.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import weights as W

TENSOR_IDS = {
    **W.TENSOR_IDS, "wkv_a": 16, "kv_norm": 17, "wkv_b": 18, "router": 19,
    "e_gate": 20, "e_up": 21, "e_down": 22, "s_gate": 23, "s_up": 24,
    "s_down": 25,
}
ATTN_TENSORS = ("ln1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln2")
DENSE_TENSORS = ("w_gate", "w_up", "w_down")
EXPERT_TENSORS = ("e_gate", "e_up", "e_down")
SHARED_TENSORS = ("router", "s_gate", "s_up", "s_down")
seed_words = W.seed_words


def model_dims(conf: dict) -> dict:
    """The widths the model needs, from its configuration file (Hugging
    Face ``config.json`` names; the router's width is the published
    ``n_routed_experts``, the held experts are ``held_experts``)."""
    rs = conf["rope_scaling"]
    held = tuple(conf["held_experts"])
    assert len(held) == conf["n_routed_experts"], held
    return {
        "d": conf["hidden_size"], "h": conf["num_attention_heads"],
        "vocab": conf["vocab_size"], "layers": conf["num_hidden_layers"],
        "dense_layers": conf["first_k_dense_replace"],
        "ff": conf["intermediate_size"],
        "moe_ff": conf["moe_intermediate_size"],
        "experts": conf["published"]["n_routed_experts"],
        "held": held, "top_k": conf["num_experts_per_tok"],
        "shared": conf["n_shared_experts"],
        "routed_scale": float(conf["routed_scaling_factor"]),
        "rank": conf["kv_lora_rank"], "nope": conf["qk_nope_head_dim"],
        "rope": conf["qk_rope_head_dim"], "dv": conf["v_head_dim"],
        "rope_theta": float(conf["rope_theta"]),
        "yarn_factor": float(rs["factor"]),
        "yarn_original": int(rs["original_max_position_embeddings"]),
        "yarn_beta_fast": float(rs["beta_fast"]),
        "yarn_beta_slow": float(rs["beta_slow"]),
        "yarn_mscale_all_dim": float(rs["mscale_all_dim"]),
        "eps": float(conf["rms_norm_eps"]),
    }


def program_config(conf: dict):
    """The program's ``ModelConfig``: its registered ``arch`` with the
    file's widths, depth, experts and YaRN settings."""
    import dataclasses
    from repro.configs.base import get_config
    m = model_dims(conf)
    return dataclasses.replace(
        get_config(conf["arch"]), num_layers=m["layers"], d_model=m["d"],
        num_heads=m["h"], head_dim=m["nope"] + m["rope"], d_ff=m["ff"],
        vocab_size=m["vocab"], rope_theta=m["rope_theta"],
        kv_lora_rank=m["rank"], qk_nope_head_dim=m["nope"],
        qk_rope_head_dim=m["rope"], v_head_dim=m["dv"],
        yarn_factor=m["yarn_factor"], yarn_original_max_pos=m["yarn_original"],
        yarn_beta_fast=m["yarn_beta_fast"], yarn_beta_slow=m["yarn_beta_slow"],
        yarn_mscale_all_dim=m["yarn_mscale_all_dim"],
        num_experts=m["experts"], experts_per_token=m["top_k"],
        moe_d_ff=m["moe_ff"], n_shared_experts=m["shared"],
        routed_scale=m["routed_scale"], held_experts=m["held"],
        first_dense_layers=m["dense_layers"])


def tensor_spec(m: dict, name: str):
    """(per-layer shape, std, mean) of one tensor; an expert tensor's
    shape is that of all ``experts`` of a layer."""
    d, h, v, ff = m["d"], m["h"], m["vocab"], m["ff"]
    r, qk, dv = m["rank"], m["nope"] + m["rope"], m["dv"]
    e, f, fs = m["experts"], m["moe_ff"], m["moe_ff"] * m["shared"]
    norm = W.NORM_STD
    return {
        "embed": ((v, d), 1.0, 0.0),
        "ln1": ((d,), norm, 1.0), "ln2": ((d,), norm, 1.0),
        "wq": ((d, h, qk), 1 / math.sqrt(d), 0.0),
        "wkv_a": ((d, r + m["rope"]), 1 / math.sqrt(d), 0.0),
        "kv_norm": ((r,), norm, 1.0),
        "wkv_b": ((r, h, m["nope"] + dv), 1 / math.sqrt(r), 0.0),
        "wo": ((h, dv, d), 1 / math.sqrt(h * dv), 0.0),
        "w_gate": ((d, ff), 1 / math.sqrt(d), 0.0),
        "w_up": ((d, ff), 1 / math.sqrt(d), 0.0),
        "w_down": ((ff, d), 1 / math.sqrt(ff), 0.0),
        "router": ((d, e), 1 / math.sqrt(d), 0.0),
        "e_gate": ((e, d, f), 1 / math.sqrt(d), 0.0),
        "e_up": ((e, d, f), 1 / math.sqrt(d), 0.0),
        "e_down": ((e, f, d), 1 / math.sqrt(f), 0.0),
        "s_gate": ((d, fs), 1 / math.sqrt(d), 0.0),
        "s_up": ((d, fs), 1 / math.sqrt(d), 0.0),
        "s_down": ((fs, d), 1 / math.sqrt(fs), 0.0),
        "final_norm": ((d,), norm, 1.0),
        "lm_head": ((d, v), 1 / math.sqrt(d), 0.0),
    }[name]


def _key(seeds, name: str, layer):
    """``bench/weights.py``'s key, over this module's tensor ids."""
    k = W._mix(seeds[0] ^ W._u32(TENSOR_IDS[name] * 0x9E3779B9))
    k = W._mix(k ^ seeds[1])
    return W._mix(k ^ (jnp.asarray(layer, jnp.uint32) * W._u32(0x85EBCA77)
                       + W._u32(0x165667B1)))


def draw(seeds, m: dict, name: str, dtype, layer=0):
    """One tensor of one layer (all experts, for an expert tensor)."""
    shape, std, mean = tensor_spec(m, name)
    return W._values(_key(seeds, name, layer), W._flat_index(shape), std,
                     mean, dtype)


def draw_expert(seeds, m: dict, name: str, dtype, layer, expert):
    """Expert ``expert``'s slice of an expert tensor of one layer."""
    shape, std, mean = tensor_spec(m, name)
    inner = shape[1:]
    idx = (W._flat_index(inner)
           + jnp.asarray(expert, jnp.uint32) * W._u32(math.prod(inner)))
    return W._values(_key(seeds, name, layer), idx, std, mean, dtype)


def draw_rows(seeds, m: dict, name: str, rows, dtype):
    """Rows ``rows`` of a 2-D global tensor (the embedding)."""
    (_, width), std, mean = tensor_spec(m, name)
    col = jax.lax.broadcasted_iota(jnp.uint32, rows.shape + (width,),
                                   rows.ndim)
    idx = rows.astype(jnp.uint32)[..., None] * W._u32(width) + col
    return W._values(_key(seeds, name, 0), idx, std, mean, dtype)


def _stacked(seeds, m, name, dtype, first: int, n: int, experts=None):
    """Layers first..first+n-1 of a tensor on a leading axis; with
    ``experts``, only those experts' slices, on a second axis."""
    shape, std, mean = tensor_spec(m, name)
    if experts is None:
        inner, lead, off = shape, 1, 0
    else:
        inner, lead = shape[1:], 2
        off = (jnp.asarray(experts, jnp.uint32).reshape(
            (1, len(experts)) + (1,) * len(inner)) * W._u32(math.prod(inner)))
    layer = first + jax.lax.broadcasted_iota(
        jnp.uint32, (n,) + (1,) * (lead - 1 + len(inner)), 0)
    idx = W._flat_index(inner, lead=lead) + off
    return W._values(_key(seeds, name, layer), idx, std, mean, dtype)


def serving_params(m: dict, dtype):
    """A jitted ``seeds -> params`` for the program's parameter tree
    (``repro.models`` layout: embed, ``head<i>`` dense layers, a stacked
    ``blocks.pos0`` of MoE layers, final_norm, lm_head)."""
    L, D = m["layers"], m["dense_layers"]

    def attn_layer(get):
        return {"ln1": {"scale": get("ln1")},
                "attn": {n: get(n) for n in ("wq", "wkv_a", "kv_norm",
                                             "wkv_b", "wo")},
                "ln2": {"scale": get("ln2")}}

    def make(seeds):
        params = {
            "embed": {"embedding": draw(seeds, m, "embed", dtype)},
            "final_norm": {"scale": draw(seeds, m, "final_norm", dtype)},
            "lm_head": {"w": draw(seeds, m, "lm_head", dtype)},
        }
        for i in range(D):
            get = lambda n, i=i: draw(seeds, m, n, dtype, i)   # noqa: E731
            params[f"head{i}"] = dict(attn_layer(get), ffn={
                "w_in": get("w_up"), "w_gate": get("w_gate"),
                "w_out": get("w_down")})
        st = lambda n, ex=None: _stacked(seeds, m, n, dtype, D, L - D,   # noqa: E731
                                         ex)
        held = m["held"]
        ffn = {"router": st("router"), "w_in": st("e_up", held),
               "w_gate": st("e_gate", held), "w_out": st("e_down", held)}
        if m["shared"]:
            ffn["shared"] = {"w_in": st("s_up"), "w_gate": st("s_gate"),
                             "w_out": st("s_down")}
        params["blocks"] = {"pos0": dict(attn_layer(st), ffn=ffn)}
        return params

    return jax.jit(make)
