"""Generic block-stack model covering all assigned architectures.

The layer stack is ``pattern_unit`` repeated ``num_units`` times via
``jax.lax.scan`` over stacked parameters (keeps the HLO size O(unit), not
O(layers) — essential for the 64-layer/1T-param dry-runs), plus an explicit
tail for patterns that do not divide the layer count (recurrentgemma's 26 = 8
× (R,R,A) + (R,R)).

Entry points:
  * ``forward``        — full-sequence logits (training / encoder, and the
    cache-free oracle the serving steps are tested against).
  * ``prefill_chunk``  — one fixed-shape prompt chunk of one slot into the
    serving caches (the only way a prompt enters them).
  * ``decode_step`` / ``verify_step`` — one token (or a draft) per slot
    against the serving caches.

Serving caches: global (``ATTN``) and latent (``MLA``) layers keep their
rows in shared page pools addressed through the page table each step
receives; window (``LOCAL_ATTN``) rings and recurrent state are per-slot
buffers.  ``clear_slot`` is the slot-granular cache surgery the serving
engine needs, which keeps knowledge of the cache tree's structure out of
serve/engine.py.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL_ATTN, MLA, RGLRU, RWKV6, ModelConfig
from repro.core.famous import FamousConfig
from repro.models import attention, layers, mla, moe, rglru, rwkv6
from repro.models.module import ParamSpec, stack_specs
from repro.parallel.incontext import constrain_residual

# ---------------------------------------------------------------------------
# parameter spec
# ---------------------------------------------------------------------------


def _ffn_spec(cfg: ModelConfig, dense: bool = False):
    if cfg.num_experts and not dense:
        return (moe.serving_moe_spec(cfg) if cfg.held_experts
                else moe.moe_spec(cfg))
    gated = cfg.act in ("silu", "gelu") and cfg.norm == "rmsnorm"
    return layers.mlp_spec(cfg.d_model, cfg.d_ff, cfg.act, gated=gated)


def block_spec(kind: str, cfg: ModelConfig, dense: bool = False) -> dict:
    """One layer; ``dense``: a leading layer, whose FFN is the dense MLP."""
    d = cfg.d_model
    if kind in (ATTN, LOCAL_ATTN, MLA):
        return {
            "ln1": layers.norm_spec(d, cfg.norm),
            "attn": (mla.mla_spec(cfg) if kind == MLA
                     else attention.attn_spec(cfg)),
            "ln2": layers.norm_spec(d, cfg.norm),
            "ffn": _ffn_spec(cfg, dense),
        }
    if kind == RGLRU:
        return {
            "ln1": layers.norm_spec(d, cfg.norm),
            "rec": rglru.rglru_spec(cfg),
            "ln2": layers.norm_spec(d, cfg.norm),
            "ffn": _ffn_spec(cfg),
        }
    if kind == RWKV6:
        return {
            "ln1": layers.norm_spec(d, cfg.norm),
            "tm": rwkv6.rwkv6_spec(cfg),
            "ln2": layers.norm_spec(d, cfg.norm),
            "cm": rwkv6.channel_mix_spec(cfg),
        }
    raise ValueError(kind)


def model_spec(cfg: ModelConfig) -> dict:
    unit = {f"pos{i}": block_spec(k, cfg) for i, k in enumerate(cfg.pattern_unit)}
    spec: dict[str, Any] = {
        "embed": layers.embed_spec(cfg.vocab_size, cfg.d_model),
        "blocks": stack_specs(unit, cfg.num_units),
        "final_norm": layers.norm_spec(cfg.d_model, cfg.norm),
    }
    for i, k in enumerate(cfg.tail_layers):
        spec[f"tail{i}"] = block_spec(k, cfg)
    for i, k in enumerate(cfg.head_layers):
        spec[f"head{i}"] = block_spec(k, cfg, dense=True)
    if not cfg.tie_embeddings:
        spec["lm_head"] = {
            "w": ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                           scale=0.02)
        }
    return spec


# ---------------------------------------------------------------------------
# block application (full sequence)
# ---------------------------------------------------------------------------


def _apply_ffn(p, x, cfg: ModelConfig):
    if "router" in p:
        if cfg.held_experts:
            return moe.apply_moe_serving(p, x, cfg)[0]
        return moe.apply_moe(p, x, cfg)
    return layers.apply_mlp(p, x, cfg.act)


def _mla_block(p, x, cfg: ModelConfig, fcfg: FamousConfig, attend,
               valid=None, counts=None):
    """An MLA layer: ``attend(normed x) -> (out, cache)``, then the FFN
    (the held-expert layer, told which tokens are ``valid``).  The layer's
    expert row counts are appended to ``counts`` when it is a list."""
    n = functools.partial(layers.apply_norm, kind=cfg.norm)
    a, cache = attend(n(p["ln1"], x))
    x = x + a
    h = n(p["ln2"], x)
    if "router" in p["ffn"]:
        y, c = moe.apply_moe_serving(p["ffn"], h, cfg, fcfg.impl, valid)
        if counts is not None:
            counts.append(c)
    else:
        y = layers.apply_mlp(p["ffn"], h, cfg.act)
    return x + y, cache


def _apply_heads(params, x, caches, cfg: ModelConfig, block_fn):
    """The leading dense layers, in order: ``block_fn(kind, p, x, cache)
    -> (x, cache)``; returns (x, their new caches by key)."""
    new = {}
    for i, kind in enumerate(cfg.head_layers):
        key = f"head{i}"
        x, new[key] = block_fn(kind, params[key], x,
                               None if caches is None else caches[key])
    return x, new


def apply_block(kind: str, p: dict, x: jax.Array, cfg: ModelConfig,
                fcfg: FamousConfig, q_offset: int = 0) -> jax.Array:
    n = functools.partial(layers.apply_norm, kind=cfg.norm)
    if kind == MLA:
        return _mla_block(p, x, cfg, fcfg, lambda h: (mla.apply_mla(
            p["attn"], h, cfg, fcfg, q_offset=q_offset), None))[0]
    if kind in (ATTN, LOCAL_ATTN):
        window = cfg.window if kind == LOCAL_ATTN else 0
        x = constrain_residual(x, cfg.num_heads)
        x = x + attention.apply_attn(p["attn"], n(p["ln1"], x), cfg, fcfg,
                                     window=window, q_offset=q_offset)
        x = constrain_residual(x, cfg.num_heads)
        h = constrain_residual(n(p["ln2"], x), cfg.num_heads)
        return x + constrain_residual(_apply_ffn(p["ffn"], h, cfg),
                                      cfg.num_heads)
    if kind == RGLRU:
        x = x + rglru.apply_rglru(p["rec"], n(p["ln1"], x), cfg)
        return x + _apply_ffn(p["ffn"], n(p["ln2"], x), cfg)
    if kind == RWKV6:
        x = x + rwkv6.apply_rwkv_time_mix(p["tm"], n(p["ln1"], x), cfg)
        y, _ = rwkv6.apply_channel_mix(p["cm"], n(p["ln2"], x), cfg)
        return x + y
    raise ValueError(kind)


def _embed_inputs(params, inputs, cfg: ModelConfig, dtype):
    if jnp.issubdtype(inputs.dtype, jnp.integer):
        return layers.embed_lookup(params["embed"], inputs, dtype)
    return inputs.astype(dtype)  # frontend stub: precomputed embeddings


def _remat_policy(cfg: ModelConfig):
    """§Perf iteration K3 (REFUTED, kept for the record): saving the MoE
    expert-FFN intermediates under save_only_these_names did not remove the
    backward's expert-weight all-gathers (XLA re-gathers for dbuf/dW anyway)
    and cost +36 GiB/device of saved activations — policy disabled."""
    return None


def forward(params: dict, inputs: jax.Array, cfg: ModelConfig,
            fcfg: FamousConfig = FamousConfig(), *, remat: bool = True,
            return_hidden: bool = False, compute_dtype=None) -> jax.Array:
    """inputs: int tokens (B, S) or float embeddings (B, S, D) for stub
    frontends.  Returns float32 logits (B, S, vocab) — or the final hidden
    states (B, S, D) when ``return_hidden`` (the chunked-CE loss computes
    logits tile-by-tile to avoid materialising the full logit tensor)."""
    x = _embed_inputs(params, inputs, cfg,
                      compute_dtype or params["final_norm"]["scale"].dtype)
    x, _ = _apply_heads(params, x, None, cfg, lambda kind, p, x, _: (
        apply_block(kind, p, x, cfg, fcfg), None))

    def unit_body(x, unit_params):
        for i, kind in enumerate(cfg.pattern_unit):
            x = apply_block(kind, unit_params[f"pos{i}"], x, cfg, fcfg)
        return x

    body = (jax.checkpoint(unit_body, policy=_remat_policy(cfg))
            if remat else unit_body)
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x, params["blocks"])
    for i, kind in enumerate(cfg.tail_layers):
        x = apply_block(kind, params[f"tail{i}"], x, cfg, fcfg)
    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    if return_hidden:
        return x
    return logits_fn(params, x, cfg)


def logits_fn(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return layers.unembed_logits(params["embed"], x)
    return jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                      params["lm_head"]["w"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# serving: caches and the decode step
# ---------------------------------------------------------------------------


def _block_cache(kind: str, cfg: ModelConfig, batch: int, max_seq: int, dtype,
                 shapes_only: bool, page_size: int, n_pages: int,
                 kv_dtype: str):
    if kind == MLA:
        if kv_dtype != "fp":
            raise ValueError("latent attention (MLA) caches hold fp rows; "
                             f"kv_dtype={kv_dtype!r} is not supported")
        fn = mla.paged_cache_shape if shapes_only else mla.make_paged_cache
        return fn(cfg, n_pages, page_size, dtype)
    if kind == ATTN:
        # global-attention layers share a page pool; sliding-window and
        # recurrent layers are O(window)/O(1) per slot and keep per-slot
        # buffers
        fn = (attention.paged_attn_cache_shape if shapes_only
              else attention.make_paged_attn_cache)
        return fn(cfg, n_pages, page_size, dtype, kv_dtype)
    if kind == LOCAL_ATTN:
        fn = attention.ring_cache_shape if shapes_only else attention.make_ring_cache
        return fn(cfg, batch, max_seq, dtype)
    if kind == RGLRU:
        fn = rglru.rglru_cache_shape if shapes_only else rglru.make_rglru_cache
        return fn(cfg, batch, dtype)
    if kind == RWKV6:
        fn = rwkv6.rwkv_cache_shape if shapes_only else rwkv6.make_rwkv_cache
        return fn(cfg, batch, dtype)
    raise ValueError(kind)


def _stack_cache_tree(unit_caches: dict, n: int, shapes_only: bool):
    """Replicate a unit's cache tree n times along a leading scan dim."""
    if shapes_only:
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), unit_caches)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy(), unit_caches)


def make_caches(cfg: ModelConfig, batch: int, max_seq: int, dtype,
                shapes_only: bool = False, *, page_size: int, n_pages: int,
                kv_dtype: str = "fp") -> dict:
    """Build the per-layer serving caches.

    Global-attention and latent layers share ``(kv, n_pages, page_size,
    dh)`` page pools (``(1, n_pages, page_size, row)`` for latent rows)
    addressed through the page table the serving steps receive at call
    time; memory then scales with live tokens, not ``batch x max_seq``
    (see serve/paged.py).  Window rings ``(batch, min(window, max_seq),
    kv, dh)`` and recurrent state stay per slot.  kv_dtype="int8" stores
    the attention pools as int8 with fp32 per-token scale pools riding the
    same page ids (see attention.make_paged_attn_cache); writes quantize,
    kernels dequantize in VMEM.
    """
    assert kv_dtype in ("fp", "int8"), kv_dtype
    assert page_size > 0 and n_pages > 0, (page_size, n_pages)
    unit = {f"pos{i}": _block_cache(k, cfg, batch, max_seq, dtype, shapes_only,
                                    page_size, n_pages, kv_dtype)
            for i, k in enumerate(cfg.pattern_unit)}
    caches: dict[str, Any] = {
        "blocks": _stack_cache_tree(unit, cfg.num_units, shapes_only)}
    for name, kinds in (("tail", cfg.tail_layers), ("head", cfg.head_layers)):
        for i, k in enumerate(kinds):
            caches[f"{name}{i}"] = _block_cache(
                k, cfg, batch, max_seq, dtype, shapes_only, page_size,
                n_pages, kv_dtype)
    return caches


def param_axes(cfg: ModelConfig) -> dict:
    """Logical-axis tree matching ``init_params(model_spec(cfg), ...)``.

    Every ParamSpec already declares its axes (``stack_specs`` prepends
    "layers" for the scanned stack), so this is just the spec tree with
    shapes dropped — the mesh-placement twin of :func:`cache_axes`, used by
    the serving engine to shard params and caches consistently."""
    from repro.models import module
    return module.logical_axes(model_spec(cfg))


def cache_axes(cfg: ModelConfig, kv_dtype: str = "fp") -> dict:
    """Logical-axis tree matching ``make_caches(cfg, ..., kv_dtype=)``:
    page pools have no slot axis and (for int8) add the scale-pool leaves;
    rings and recurrent state expose their "batch" axis."""
    def block_axes(kind):
        if kind == MLA:
            return mla.PAGED_CACHE_AXES
        if kind == ATTN:
            return (attention.PAGED_ATTN_CACHE_AXES_INT8
                    if kv_dtype == "int8"
                    else attention.PAGED_ATTN_CACHE_AXES)
        if kind == LOCAL_ATTN:
            return attention.RING_CACHE_AXES
        if kind == RGLRU:
            return rglru.RGLRU_CACHE_AXES
        return rwkv6.RWKV_CACHE_AXES

    unit = {f"pos{i}": block_axes(k) for i, k in enumerate(cfg.pattern_unit)}
    stacked = jax.tree_util.tree_map(
        lambda ax: (None,) + tuple(ax), unit,
        is_leaf=lambda x: isinstance(x, tuple))
    axes: dict[str, Any] = {"blocks": stacked}
    for i, k in enumerate(cfg.tail_layers):
        axes[f"tail{i}"] = block_axes(k)
    for i, k in enumerate(cfg.head_layers):
        axes[f"head{i}"] = block_axes(k)
    return axes


def _pooled(kind: str) -> bool:
    """Whether a layer of ``kind`` keeps its cache in a shared page pool:
    global and latent attention.  The serving steps carry pools through the
    layer scan whole and address them by layer; every other cache is per
    slot."""
    return kind in (ATTN, MLA)


def _unstacked(block_fn):
    """``block_fn(kind, p, x, cache, layer, sink)`` for a head or tail
    layer, whose cache has no layer dim: a pool rides as a stack of one
    (a reshape, no copy) at layer 0."""
    def fn(kind, p, x, cache, sink=None):
        if not _pooled(kind):
            return block_fn(kind, p, x, cache, None, sink)
        x, new = block_fn(kind, p, x,
                          jax.tree_util.tree_map(lambda a: a[None], cache), 0,
                          sink)
        return x, jax.tree_util.tree_map(lambda a: a[0], new)
    return fn


def _scan_units(block_fn, x, params, caches, cfg: ModelConfig):
    """Run the scanned unit stack of a serving step:
    ``block_fn(kind, p, x, cache, layer, sink) -> (x, new cache)`` for each
    layer of each unit.  Returns (x, new block caches, the sum of each
    unit's ``sink`` stacked over units, or None where nothing was sunk).

    Page pools (:func:`_pooled`) ride the loop's carry whole, stacked
    (units, ...) as :func:`make_caches` builds them: a layer writes its new
    rows into the stack in place and its kernels read it at the scanned
    ``layer`` index, so no iteration slices a pool in or stacks one out
    (the caches are donated, so the carry is the input buffer).  Per-slot
    caches (window rings, recurrent state) scan as xs/ys: a layer's slice
    of them is read whole anyway, and ``layer`` is None for them."""
    blocks = caches["blocks"]
    pooled = {f"pos{i}" for i, k in enumerate(cfg.pattern_unit)
              if _pooled(k)}
    pools = {k: v for k, v in blocks.items() if k in pooled}
    per_slot = {k: v for k, v in blocks.items() if k not in pooled}

    def body(carry, xs):
        x, pools = carry
        unit_params, layer, unit_cache = xs
        new, sink = {}, []
        for i, kind in enumerate(cfg.pattern_unit):
            key = f"pos{i}"
            if key in pooled:
                x, pools[key] = block_fn(kind, unit_params[key], x,
                                         pools[key], layer, sink)
            else:
                x, new[key] = block_fn(kind, unit_params[key], x,
                                       unit_cache[key], None, sink)
        return (x, pools), (new, sum(sink) if sink else None)

    (x, pools), (per_slot, sunk) = jax.lax.scan(
        body, (x, pools),
        (params["blocks"], jnp.arange(cfg.num_units), per_slot))
    return x, {**pools, **per_slot}, sunk


def _apply_block_decode(kind, p, x, cache, layer, cache_len, cfg, fcfg,
                        page_table, active=None, counts=None):
    n = functools.partial(layers.apply_norm, kind=cfg.norm)
    if kind == MLA:
        valid = None if active is None else active[:, None]
        return _mla_block(p, x, cfg, fcfg, lambda h: mla.apply_mla_decode_paged(
            p["attn"], h, cache, layer, page_table, cache_len, cfg, fcfg),
            valid, counts)
    if kind == ATTN:
        a, cache = attention.apply_attn_decode_paged(
            p["attn"], n(p["ln1"], x), cache, layer, page_table, cache_len,
            cfg, fcfg)
        x = x + a
        return x + _apply_ffn(p["ffn"], n(p["ln2"], x), cfg), cache
    if kind == LOCAL_ATTN:
        a, cache = attention.apply_attn_decode(p["attn"], n(p["ln1"], x),
                                               cache, cache_len, cfg, fcfg)
        x = x + a
        return x + _apply_ffn(p["ffn"], n(p["ln2"], x), cfg), cache
    if kind == RGLRU:
        a, cache = rglru.apply_rglru_decode(p["rec"], n(p["ln1"], x), cfg=cfg,
                                            cache=cache)
        x = x + a
        return x + _apply_ffn(p["ffn"], n(p["ln2"], x), cfg), cache
    if kind == RWKV6:
        a, c_tm = rwkv6.apply_rwkv_time_mix_decode(
            p["tm"], n(p["ln1"], x), {k: cache[k] for k in ("s", "x_tm")}, cfg)
        x = x + a
        h = n(p["ln2"], x)
        y, _ = rwkv6.apply_channel_mix(p["cm"], h, cfg,
                                       cache_x=cache["x_cm"])
        cache = {"s": c_tm["s"], "x_tm": c_tm["x_tm"], "x_cm": h[:, -1]}
        return x + y, cache
    raise ValueError(kind)


def _mask_state_update(kind: str, new_cache, old_cache, active):
    """Keep recurrent state frozen for non-``active`` slots.

    The decode executable runs over *every* slot each step (fixed batch);
    slots that are free or mid-chunked-prefill produce junk.  Junk K/V
    writes are harmless (masked by cache_len / overwritten by the next
    chunk), but recurrent state (RG-LRU h/conv, RWKV s/x_tm/x_cm) is read
    unconditionally and carried across prefill chunks — a junk update
    between chunks would corrupt it, so it only commits where ``active``.
    """
    if active is None or kind in (ATTN, LOCAL_ATTN, MLA):
        return new_cache
    return jax.tree_util.tree_map(
        lambda nc, oc: jnp.where(
            active.reshape((active.shape[0],) + (1,) * (nc.ndim - 1)),
            nc, oc.astype(nc.dtype)),
        new_cache, old_cache)


def decode_step(params, tokens, caches, cache_len, cfg: ModelConfig,
                fcfg: FamousConfig = FamousConfig(), compute_dtype=None, *,
                page_table, active=None, moe_counts: bool = False):
    """tokens: (B,) int32 (or (B, D) embeddings); cache_len: (B,).
    page_table: (B, pages_per_slot) int32, addressing the global and
    latent layers' page pools (see :func:`make_caches`).  active: optional
    (B,) bool — slots not decoding this step (free, or mid-chunked-prefill)
    keep their recurrent state untouched, and their tokens take no held
    expert's rows.  Returns (logits (B, vocab), new caches), and with
    ``moe_counts`` also the held-expert layers' row counts summed over
    layers (``moe.apply_moe_serving``'s ``counts``)."""
    dtype = compute_dtype or params["final_norm"]["scale"].dtype
    inputs = tokens[:, None] if tokens.ndim == 1 else tokens[:, None, :]
    x = _embed_inputs(params, inputs, cfg, dtype)
    counts = [] if moe_counts else None

    def block(kind, p, x, cache, layer, sink):
        x, new = _apply_block_decode(kind, p, x, cache, layer, cache_len, cfg,
                                     fcfg, page_table, active,
                                     sink if moe_counts else None)
        return x, _mask_state_update(kind, new, cache, active)

    one = _unstacked(block)
    x, head_caches = _apply_heads(params, x, caches, cfg, one)
    x, new_block_caches, unit_counts = _scan_units(block, x, params, caches,
                                                   cfg)
    new_caches = {"blocks": new_block_caches, **head_caches}
    for i, kind in enumerate(cfg.tail_layers):
        x, new_caches[f"tail{i}"] = one(kind, params[f"tail{i}"], x,
                                        caches[f"tail{i}"], counts)
    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    logits = logits_fn(params, x, cfg)[:, 0]
    if not moe_counts:
        return logits, new_caches
    return logits, new_caches, sum(counts, jnp.sum(unit_counts, axis=0))


def _apply_block_verify(kind, p, x, cache, layer, cache_len, cfg, fcfg,
                        page_table):
    n = functools.partial(layers.apply_norm, kind=cfg.norm)
    if kind != ATTN:
        # Sliding-window rings write position p at slot p % ring — a verify
        # batch would destroy the oldest W entries before knowing how many
        # tokens survive, and recurrent state (RG-LRU, RWKV) cannot be
        # rolled back to an intermediate position.  The engine must fall
        # back to plain decode for these stacks (speculative_active False).
        raise ValueError(
            f"verify_step only supports global-attention layers, got {kind}")
    a, cache = attention.apply_attn_verify_paged(
        p["attn"], n(p["ln1"], x), cache, layer, page_table, cache_len,
        cfg, fcfg)
    x = x + a
    return x + _apply_ffn(p["ffn"], n(p["ln2"], x), cfg), cache


def verify_step(params, tokens, caches, cache_len, cfg: ModelConfig,
                fcfg: FamousConfig = FamousConfig(), compute_dtype=None, *,
                page_table):
    """Speculative verify: decode W tokens per slot in ONE forward.

    tokens: (B, W) int32 — row b is ``[last_token, draft_1..draft_{W-1}]``
    at absolute positions ``cache_len[b] + j`` (pad rows past a short
    draft are ignored by the caller); cache_len: (B,) valid cache entries
    BEFORE the first token, a runtime operand — one executable serves
    every draft-length mix; page_table: (B, pages_per_slot) int32.
    Returns (logits (B, W, vocab), new caches):
    ``logits[b, j]`` is the next-token distribution after consuming
    ``tokens[b, :j+1]``, exactly what j+1 sequential ``decode_step`` calls
    would produce (causal attention makes the parallel and sequential
    activations identical), so the engine can accept the longest draft
    prefix the model agrees with and remain token-identical to plain
    decode.  ``W == 1`` degenerates to ``decode_step`` (without the
    recurrent/ring support — only all-ATTN stacks verify; see
    ``_apply_block_verify``).
    """
    dtype = compute_dtype or params["final_norm"]["scale"].dtype
    x = _embed_inputs(params, tokens, cfg, dtype)

    def block(kind, p, x, cache, layer, sink):
        return _apply_block_verify(kind, p, x, cache, layer, cache_len, cfg,
                                   fcfg, page_table)

    x, new_block_caches, _ = _scan_units(block, x, params, caches, cfg)
    new_caches = {"blocks": new_block_caches}
    one = _unstacked(block)
    for i, kind in enumerate(cfg.tail_layers):
        x, new_caches[f"tail{i}"] = one(kind, params[f"tail{i}"], x,
                                        caches[f"tail{i}"])
    x = layers.apply_norm(params["final_norm"], x, cfg.norm)
    return logits_fn(params, x, cfg), new_caches


# ---------------------------------------------------------------------------
# serving: chunked prefill (the Scheduler/Runtime hot path)
# ---------------------------------------------------------------------------


def _read_slot_state(cache, slot, offset):
    """Slot row of a per-slot state tree, zeroed when ``offset == 0`` so a
    reused slot cannot leak the previous occupant's recurrent state into a
    fresh sequence (chunk 0 starts from zero state; later chunks carry)."""
    def read(buf):
        row = jax.lax.dynamic_slice(buf, (slot,) + (0,) * (buf.ndim - 1),
                                    (1,) + buf.shape[1:])
        return jnp.where(offset > 0, row, jnp.zeros_like(row))

    return jax.tree_util.tree_map(read, cache)


def _write_slot_state(cache, sub, slot):
    return jax.tree_util.tree_map(
        lambda d, s: jax.lax.dynamic_update_slice(
            d, s.astype(d.dtype), (slot,) + (0,) * (d.ndim - 1)),
        cache, sub)


def _apply_block_chunk(kind, p, x, cache, layer, slot, offset, n_valid, cfg,
                       fcfg, page_table):
    n = functools.partial(layers.apply_norm, kind=cfg.norm)
    if kind == MLA:
        valid = (jnp.arange(x.shape[1]) < n_valid)[None]
        return _mla_block(p, x, cfg, fcfg, lambda h: mla.apply_mla_chunk_paged(
            p["attn"], h, cache, layer, page_table, slot, offset, cfg, fcfg),
            valid)
    if kind == ATTN:
        a, cache = attention.apply_attn_chunk_paged(
            p["attn"], n(p["ln1"], x), cache, layer, page_table, slot, offset,
            cfg, fcfg)
        x = x + a
        return x + _apply_ffn(p["ffn"], n(p["ln2"], x), cfg), cache
    if kind == LOCAL_ATTN:
        a, cache = attention.apply_attn_chunk(
            p["attn"], n(p["ln1"], x), cache, slot, offset, n_valid, cfg,
            fcfg)
        x = x + a
        return x + _apply_ffn(p["ffn"], n(p["ln2"], x), cfg), cache
    if kind == RGLRU:
        sub = _read_slot_state(cache, slot, offset)
        a, sub = rglru.apply_rglru_chunk(p["rec"], n(p["ln1"], x), cfg, sub,
                                         n_valid)
        x = x + a
        return (x + _apply_ffn(p["ffn"], n(p["ln2"], x), cfg),
                _write_slot_state(cache, sub, slot))
    if kind == RWKV6:
        sub = _read_slot_state(cache, slot, offset)
        a, c_tm = rwkv6.apply_rwkv_time_mix_chunk(
            p["tm"], n(p["ln1"], x), {k: sub[k] for k in ("s", "x_tm")}, cfg,
            n_valid)
        x = x + a
        h = n(p["ln2"], x)
        y, _ = rwkv6.apply_channel_mix(p["cm"], h, cfg, cache_x=sub["x_cm"])
        x_cm = jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)[:, 0]
        sub = {"s": c_tm["s"], "x_tm": c_tm["x_tm"], "x_cm": x_cm}
        return x + y, _write_slot_state(cache, sub, slot)
    raise ValueError(kind)


def prefill_chunk(params, tokens, caches, slot, offset, n_valid,
                  cfg: ModelConfig, fcfg: FamousConfig = FamousConfig(), *,
                  page_table, compute_dtype=None):
    """One fixed-shape prefill chunk for a single slot of the batched caches.

    tokens: (1, C) int32 at absolute positions [offset, offset+C); only the
    first ``n_valid`` are real (the pad tail's state updates are masked to
    the identity, and its junk K/V is never read).  Writes K/V or latent
    rows into the slot's pages of the page pools, the window ring, and
    recurrent state for ``slot`` directly into the batched caches, and
    carries recurrent state across chunks (``offset == 0`` starts from zero
    state).  ``slot``, ``offset`` and ``n_valid`` are runtime scalars: ONE
    executable serves every (slot, prompt length, chunk index) triple.
    Returns the new caches only — prefill logits are dead weight
    (generation restarts by decoding the last prompt token), so the LM
    head is never computed.
    """
    dtype = compute_dtype or params["final_norm"]["scale"].dtype
    x = _embed_inputs(params, tokens, cfg, dtype)

    def block(kind, p, x, cache, layer, sink):
        return _apply_block_chunk(kind, p, x, cache, layer, slot, offset,
                                  n_valid, cfg, fcfg, page_table)

    one = _unstacked(block)
    x, head_caches = _apply_heads(params, x, caches, cfg, one)
    x, new_block_caches, _ = _scan_units(block, x, params, caches, cfg)
    new_caches = {"blocks": new_block_caches, **head_caches}
    for i, kind in enumerate(cfg.tail_layers):
        x, new_caches[f"tail{i}"] = one(kind, params[f"tail{i}"], x,
                                        caches[f"tail{i}"])
    return new_caches


# ---------------------------------------------------------------------------
# serving: slot-granular cache surgery (used by serve/engine.py)
# ---------------------------------------------------------------------------


def clear_slot(caches, slot, cfg: ModelConfig) -> dict:
    """Zero slot ``slot``'s per-slot cache state (stale-state hygiene for
    length-1 admissions that skip prefill).  Page pools are left alone:
    the slot's pages were already freed and any stale page content is
    unreachable (the page table row is null and reads are
    cache_len-masked)."""
    def zero_tree(tree, axis):
        def z(buf):
            idx = (slice(None),) * axis + (slot,)
            return buf.at[idx].set(jnp.zeros((), buf.dtype))
        return jax.tree_util.tree_map(z, tree)

    out: dict[str, Any] = {"blocks": {}}
    for i, kind in enumerate(cfg.pattern_unit):
        key = f"pos{i}"
        if _pooled(kind):
            out["blocks"][key] = caches["blocks"][key]
        else:
            out["blocks"][key] = zero_tree(caches["blocks"][key], 1)
    for key, kind in ([(f"tail{i}", k) for i, k in enumerate(cfg.tail_layers)]
                      + [(f"head{i}", k)
                         for i, k in enumerate(cfg.head_layers)]):
        out[key] = caches[key] if _pooled(kind) else zero_tree(caches[key], 0)
    return out
