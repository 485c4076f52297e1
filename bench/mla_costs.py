"""Operations and bytes of the latent-attention (MLA) decode kernel,
counted from shapes: what one call needs, not what the program does.

Per live cache entry (a slot's position + 1 entries) and layer, the
kernel reads one latent row ``[c_kv; k_pe]`` (``rank + rope`` wide; the
stored row's lane padding is not charged) and every query head does a
``rank + rope``-long dot against it and a ``rank``-long multiply-add of
its latent part.  Plus each slot's query read and latent output written.
"""
from __future__ import annotations


def mla_decode_call(live_lens, *, heads: int, rank: int, rope: int,
                    row_bytes: int = 2, act_bytes: int = 2) -> tuple:
    """(flops, bytes) of one latent decode call of one layer."""
    n = sum(int(x) for x in live_lens)
    slots = len(live_lens)
    flops = 2 * n * heads * ((rank + rope) + rank)
    bytes_ = (n * (rank + rope) * row_bytes
              + slots * heads * ((rank + rope) + rank) * act_bytes)
    return flops, bytes_
