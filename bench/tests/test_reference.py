"""The plain float32 reference against the program's own prefill-then-
decode logits, on the CPU at a shrunk qwen2 shape: they agree, and they
stop agreeing once the reference drops a layer."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.tests import tiny

SEED = 2 ** 32 + 77


@pytest.fixture(scope="module")
def served():
    """Two prompts through the engine (chunked prefill into the paged
    pool, then decode through it), with the logits of every decode step."""
    from repro.serve.engine import Request
    cell = tiny.cell("closed")
    eng = harness.build_engine(cell, SEED)
    got = []
    decode = eng._decode

    def spy(*a, **k):
        logits, caches = decode(*a, **k)
        got.append((np.asarray(k["active"]), np.asarray(logits)))
        return logits, caches

    eng._decode = spy
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=rng.integers(0, 256, n).tolist(),
                    max_new=6) for i, n in enumerate((37, 20))]
    eng.run(reqs)
    return cell, reqs, got


def _ref_rows(cell, reqs, dims):
    s_pad = 64
    toks = np.zeros((len(reqs), s_pad), np.int32)
    rows = []
    for b, r in enumerate(reqs):
        seq = r.tokens + r.out[:-1]
        toks[b, :len(seq)] = seq
        rows += [b * s_pad + len(r.tokens) - 1 + j for j in range(len(r.out))]
    return np.asarray(reference.logits_at(dims, SEED, toks, np.array(rows),
                                          jnp.float32))


def _program_rows(reqs, got):
    # both requests are admitted at once, into slots 0 and 1 (slot = rid);
    # token j of a request comes from its slot's j-th active decode step
    by_slot = {r.rid: [lg[r.rid] for act, lg in got if act[r.rid]]
               for r in reqs}
    return np.stack([by_slot[r.rid][j] for r in reqs
                     for j in range(len(r.out))])


def test_reference_matches_prefill_then_decode(served):
    cell, reqs, got = served
    ref = _ref_rows(cell, reqs, cell.dims)
    prog = _program_rows(reqs, got)
    scale = np.abs(ref).max()
    assert np.abs(prog - ref).max() <= 1e-4 * scale
    served_tokens = [t for r in reqs for t in r.out]
    assert (ref.argmax(-1) == served_tokens).all()


def test_reference_without_a_layer_disagrees(served):
    cell, reqs, got = served
    short = dict(cell.dims, layers=cell.dims["layers"] - 1)
    ref = _ref_rows(cell, reqs, short)
    prog = _program_rows(reqs, got)
    assert np.abs(prog - ref).max() > 0.05 * np.abs(ref).max()


def test_score_reads_the_served_token_gap():
    """A token other than the reference's best shows by its gap."""
    cell = tiny.cell("closed")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 256, 30).tolist()
    rows = np.array([29])
    toks = np.zeros((1, 64), np.int32)
    toks[0, :30] = prompt
    ref = np.asarray(reference.logits_at(cell.dims, SEED, toks, rows,
                                         jnp.float32))[0]
    best, other = int(ref.argmax()), int(ref.argmin())
    c = cell.check
    kw = dict(s_pad=c["s_pad"], b_pad=c["b_pad"], n_score=c["n_score"],
              dtype=jnp.float32)
    good = reference.score(cell.dims, SEED, [(prompt, [best])], **kw)
    bad = reference.score(cell.dims, SEED, [(prompt, [other])], **kw)
    assert good["n"] == bad["n"] == 1
    assert good["gap"][0] == 0.0
    assert bad["gap"][0] == pytest.approx(ref.max() - ref.min(), rel=1e-5)
