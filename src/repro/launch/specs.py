"""ShapeDtypeStruct stand-ins for every model input — the dry-run never
allocates.  One entry point: ``input_specs(arch, shape_name)``.

train  -> {"inputs": (B, S) int32 | (B, S, D) bf16 stub-frontend embeddings,
           "targets": (B, S) int32}
prefill-> {"inputs": ...} (same as train inputs)
decode -> {"tokens": (B,) int32 | (B, D) embeddings, "cache_len": (B,) int32,
           "page_table": (B, S / PAGE) int32} plus the cache tree from
           transformer.make_caches(shapes_only=True): page pools of
           1 + B * S / PAGE pages, as the serving engine sizes them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig, get_config, SHAPES
from repro.models import transformer
from repro.serve.paged import PagedCacheConfig

PAGE = 16   # tokens per page, the serving engine's default


def train_input_specs(cfg: ModelConfig, shape: ShapeConfig,
                      dtype=jnp.bfloat16) -> dict:
    B, S = shape.global_batch, shape.seq_len
    if cfg.frontend:
        inputs = jax.ShapeDtypeStruct((B, S, cfg.d_model), dtype)
    else:
        inputs = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return {"inputs": inputs, "targets": jax.ShapeDtypeStruct((B, S), jnp.int32)}


def decode_input_specs(cfg: ModelConfig, shape: ShapeConfig,
                       dtype=jnp.bfloat16) -> dict:
    B, S = shape.global_batch, shape.seq_len
    if cfg.frontend:
        tokens = jax.ShapeDtypeStruct((B, cfg.d_model), dtype)
    else:
        tokens = jax.ShapeDtypeStruct((B,), jnp.int32)
    return {
        "tokens": tokens,
        "cache_len": jax.ShapeDtypeStruct((B,), jnp.int32),
        "page_table": jax.ShapeDtypeStruct((B, S // PAGE), jnp.int32),
        "caches": transformer.make_caches(
            cfg, B, S, dtype, shapes_only=True, page_size=PAGE,
            n_pages=PagedCacheConfig.default_pool(B, S, PAGE)),
    }


def input_specs(arch: str, shape_name: str, dtype=jnp.bfloat16) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind in ("train", "prefill"):
        return train_input_specs(cfg, shape, dtype)
    return decode_input_specs(cfg, shape, dtype)
