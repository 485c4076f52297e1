"""``correct`` has to come out false when the timed path is broken
underneath a whole run (the look for a chip is all that is skipped), and
the fp8 control has to fail the limit that sound runs pass."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2 ** 31 + 99


def _state_unchanged(eng):
    """Every prefill chunk returns the caches it was given."""
    eng._prefill_chunk_exec = jax.jit(
        lambda params, toks, caches, *a, **k: caches)


def _half_batch(eng):
    """The decode step computes the first half of its slots and hands
    their logits to the second half."""
    decode = eng._decode
    half = eng.n_slots // 2

    def fn(*a, **k):
        logits, caches = decode(*a, **k)
        return logits.at[half:].set(logits[:half]), caches

    eng._decode = jax.jit(fn)


def _token_altered(eng):
    """Every third decode step moves each slot's best logit to the next
    token id, so each request of three tokens or more gets one token that
    the model did not produce."""
    decode = eng._decode
    roll = jax.jit(lambda x: jnp.roll(x, 1, axis=-1))
    calls = [0]

    class Altered:
        """The decode step, with the census of the executable it wraps."""

        def __call__(self, *a, **k):
            logits, caches = decode(*a, **k)
            calls[0] += 1
            return (roll(logits) if calls[0] % 3 == 0 else logits), caches

        def _cache_size(self):
            return decode._cache_size()

    eng._decode = Altered()


def _run(mix, hook=None, controls=(), **check):
    return harness.run(tiny.cell(mix, **check), SEED, 3.0, False,
                       time.perf_counter(), engine_hook=hook,
                       kind="TPU v5 lite", controls=controls)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_timed_path_is_not_correct(fault):
    # judge 2 requests of 4 slots, the chip's share (4 of 16): the sample
    # takes one from each half of the slots
    res = _run("closed", fault, b_pad=2, n_score=64)
    chk = res["checks"]["logit_gap"]
    assert chk["tokens"] > 0
    assert chk["value"] > chk["limit"]
    assert res["correct"] is False


def test_sound_run_is_correct_and_the_control_is_not():
    # the float32 program reads 0 against the float32 reference on the
    # CPU; the fp8 control, scored on the same 256 tokens, reads well
    # above the limit
    res = _run("long", controls=("fp8",), b_pad=8, n_score=256)
    assert res["correct"] is True
    sound = res["checks"]["logit_gap"]
    ctl = res["checks"]["control_fp8_logit_gap"]
    assert sound["value"] <= sound["limit"] < ctl["value"]
    assert ctl["correct"] is False
    assert ctl["tokens"] == sound["tokens"] > 0
