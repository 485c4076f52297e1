import functools
import os
import sys

# NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device;
# only launch/dryrun.py sets the 512-device placeholder flag.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The Pallas contract checker is ON for the whole suite (every
# pc.pallas_call launch is validated) unless explicitly disabled with
# REPRO_KERNEL_CHECK=0.  See repro.analysis.kernel_check.
if os.environ.get("REPRO_KERNEL_CHECK", "1") != "0":
    from repro.analysis import kernel_check
    kernel_check.enable()

from repro.core.famous import FamousConfig  # noqa: E402
from repro.models import transformer  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")


@functools.lru_cache(maxsize=None)
def _jit_forward(cfg, impl: str):
    return jax.jit(functools.partial(transformer.forward, cfg=cfg,
                                     fcfg=FamousConfig(impl=impl),
                                     remat=False))


def _greedy_forward(params, cfg, tokens, max_new, length=128, impl="xla"):
    """Greedy generation with no cache at all: the full-sequence forward
    over the growing sequence, argmax at its last position.  The sequence
    rides zero-padded to ``length`` (one executable); causality keeps the
    pad out of every position read."""
    seq = [int(t) for t in tokens]
    assert len(seq) + max_new <= length
    for _ in range(max_new):
        buf = np.zeros((1, length), np.int32)
        buf[0, :len(seq)] = seq
        logits = _jit_forward(cfg, impl)(params, jnp.asarray(buf))
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    return seq[len(tokens):]


@pytest.fixture
def greedy_reference():
    """:func:`_greedy_forward`, the serving engine's cache-free oracle:
    ``fn(params, cfg, prompt, max_new) -> greedy tokens``."""
    return _greedy_forward


def identity_table(n_slots: int, pages_per_slot: int):
    """The page table giving slot b pages ``1 + b * n_p .. (b + 1) * n_p``
    (page 0 is the null page)."""
    return (1 + jnp.arange(n_slots * pages_per_slot, dtype=jnp.int32)
            ).reshape(n_slots, pages_per_slot)


def _paged_prefill(params, cfg, tokens, *, max_seq: int, page_size: int = 8,
                   chunk: int = 8, fcfg=None, page_table=None,
                   kv_dtype: str = "fp"):
    """Serving caches holding ``tokens`` (B, P), prefilled the way the
    engine prefills: paged pools under ``page_table`` (default
    :func:`identity_table`; a pool of ``1 + B * max_seq / page_size``
    pages), each row entering through ``transformer.prefill_chunk``, one
    slot at a time, in ``chunk``-token chunks.  Returns (caches,
    page_table); the caches hold P entries per slot, so decoding continues
    at ``cache_len = P``."""
    B, P = tokens.shape
    n_p = max_seq // page_size
    pt = identity_table(B, n_p) if page_table is None else page_table
    caches = transformer.make_caches(
        cfg, B, max_seq, params["final_norm"]["scale"].dtype,
        page_size=page_size, n_pages=1 + B * n_p, kv_dtype=kv_dtype)
    step = jax.jit(functools.partial(transformer.prefill_chunk, cfg=cfg,
                                     fcfg=fcfg or FamousConfig()))
    toks = np.zeros((B, -(-P // chunk) * chunk), np.int32)
    toks[:, :P] = np.asarray(tokens)
    for b in range(B):
        for start in range(0, P, chunk):
            caches = step(params, jnp.asarray(toks[b:b + 1,
                                                   start:start + chunk]),
                          caches, jnp.int32(b), jnp.int32(start),
                          jnp.int32(min(chunk, P - start)), page_table=pt)
    return caches, pt


@pytest.fixture
def paged_prefill():
    """:func:`_paged_prefill`: serving caches prefilled with a batch of
    prompts, for tests that decode from them against an oracle."""
    return _paged_prefill


@pytest.fixture
def identity_layout():
    """``fn(pools, page_table)``: a stack of pools (L, KV, n_pages, ps, w)
    laid out again under :func:`identity_table` — each slot's table pages,
    in table order, copied to its identity pages (the null page zero)."""
    def fn(pools, page_table):
        B, n_p = page_table.shape
        L, KV, _, ps, w = pools.shape
        rows = pools[:, :, page_table]          # (L, KV, B, n_p, ps, w)
        out = jnp.zeros((L, KV, 1 + B * n_p, ps, w), pools.dtype)
        return out.at[:, :, 1:].set(rows.reshape(L, KV, B * n_p, ps, w))
    return fn
