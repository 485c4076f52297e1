"""Operations and bytes that the algorithm needs, counted from shapes.

These are the numerators of the roofline shares and of ``step_mfu``: what
the work requires, not what the program happens to do.  A paged decode
call needs the live cache (each slot's ``cache_len`` entries), not the
``max_seq`` pages its grid walks; a projection needs its weights once.
"""
from __future__ import annotations


def paged_decode_call(live_lens, *, kv_heads: int, group: int, dh: int,
                      kv_bytes: int = 2, act_bytes: int = 2) -> tuple:
    """(flops, bytes) of one paged-decode attention call of one layer.

    ``live_lens``: the number of cache entries each live slot attends over
    (its position + 1).  Per entry and kv head: one K and one V row read,
    ``group`` query heads each doing a dh-long dot with K and a dh-long
    multiply-add with V.  Plus the query read and the output write."""
    n = sum(int(x) for x in live_lens)
    slots = len(live_lens)
    flops = 4 * n * kv_heads * group * dh
    bytes_ = (2 * n * kv_heads * dh * kv_bytes
              + 2 * slots * kv_heads * group * dh * act_bytes)
    return flops, bytes_


def qkv_matmul_call(tokens: int, *, d_model: int, heads: int, kv_heads: int,
                    dh: int, w_bytes: int = 2, act_bytes: int = 2) -> tuple:
    """(flops, bytes) of one fused Q/K/V projection of ``tokens`` rows:
    read x and the fused weight once, write q, k and v."""
    f = (heads + 2 * kv_heads) * dh
    flops = 2 * tokens * d_model * f
    bytes_ = (tokens * d_model * act_bytes + d_model * f * w_bytes
              + tokens * f * act_bytes)
    return flops, bytes_


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer (projections and
    the gated MLP)."""
    d, h, kv, dh, ff = m["d"], m["h"], m["kv"], m["dh"], m["ff"]
    return d * (h + 2 * kv) * dh + h * dh * d + 3 * d * ff


def token_flops(m: dict, context: int, lm_head: bool) -> int:
    """Model FLOPs of one token that attends over ``context`` entries
    (itself included): two per weight it multiplies through, four per
    attended entry and head dim for QK^T and PV, and the LM head where the
    token's logits are needed (decode; prefill skips it)."""
    flops = m["layers"] * (2 * layer_matmul_params(m)
                           + 4 * context * m["h"] * m["dh"])
    if lm_head:
        flops += 2 * m["d"] * m["vocab"]
    return flops


def prefill_flops(m: dict, start: int, n: int) -> int:
    """Model FLOPs of prefilling positions start..start+n-1 (causal: the
    token at position p attends over p + 1 entries); no LM head."""
    ctx = n * start + n * (n + 1) // 2
    return (n * m["layers"] * 2 * layer_matmul_params(m)
            + m["layers"] * 4 * ctx * m["h"] * m["dh"])


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """Least time the chip needs: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
