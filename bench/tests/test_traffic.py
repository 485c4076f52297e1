"""The traffic generator: one seed gives one schedule, every mix keeps
to its stated bounds, and seeds differ in order and token ids only."""
import json
import pathlib

import pytest

from bench import traffic

MIXES = sorted((pathlib.Path(__file__).resolve().parents[1] / "traffic")
               .glob("*.json"))
BIG_SEED = 2 ** 31 + 987_654_321


def _take(mix, seed, n, vocab=1000):
    t = traffic.Traffic(mix, seed, vocab)
    return t, [t.next() for _ in range(n)]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_schedule(path):
    mix = traffic.load(path)
    ta, a = _take(mix, BIG_SEED, 40)
    tb, b = _take(mix, BIG_SEED, 40)
    assert [(i.tokens, i.max_new, i.offset) for i in a] == \
        [(i.tokens, i.max_new, i.offset) for i in b]
    assert ta.documents == tb.documents


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_lengths_within_the_mix_bounds(path):
    mix = traffic.load(path)
    t, items = _take(mix, 7, 200)
    docs = mix.get("documents")
    for it in items:
        n = len(it.tokens)
        if docs:
            assert any(it.tokens[:docs["length"]] == d for d in t.documents)
            n -= docs["length"]
        assert mix["prompt"]["min"] <= n <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= it.max_new <= mix["output"]["max"]
        assert all(0 <= tok < 1000 for tok in it.tokens)
    if mix["loop"] == "open":
        offs = [it.offset for it in items]
        assert all(b > a for a, b in zip(offs, offs[1:]))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_seeds_differ_in_token_ids_only(path):
    """Every seed serves the same sizes at the same times; only the
    token ids differ."""
    mix = traffic.load(path)
    ta, a = _take(mix, 1, 64)
    tb, b = _take(mix, BIG_SEED, 64)
    assert [(len(i.tokens), i.max_new, i.offset) for i in a] == \
        [(len(i.tokens), i.max_new, i.offset) for i in b]
    assert [i.tokens for i in a] != [i.tokens for i in b]
    if mix.get("documents"):
        # the same document, by index, starts the same request
        pick = lambda t, items: [  # noqa: E731
            [k for k, d in enumerate(t.documents)
             if it.tokens[:len(d)] == d] for it in items]
        assert pick(ta, a) == pick(tb, b)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_each_block_holds_the_quantiles(path):
    """A block of ``block`` requests holds every quantile (i + 0.5) /
    block of the length distributions once."""
    mix = traffic.load(path)
    blk = mix["block"]
    _, a = _take(mix, 5, 2 * blk)
    want = sorted(traffic._quantile(mix["output"], (i + 0.5) / blk)
                  for i in range(blk))
    for k in range(2):
        assert sorted(i.max_new for i in a[k * blk:(k + 1) * blk]) == want


def test_chat_gaps_are_poisson_at_the_stated_rate():
    mix = json.loads((pathlib.Path(__file__).resolve().parents[1]
                      / "traffic" / "chat.json").read_text())
    _, items = _take(mix, 3, 16 * 20)
    rate = len(items) / items[-1].offset
    assert rate == pytest.approx(mix["rate_per_s"], rel=0.05)


def test_shared_documents_are_primed_as_whole_pages():
    mix = {"loop": "closed", "clients": 2, "ramp_s": 0, "block": 4,
           "order_seed": 1, "documents": {"count": 3, "length": 64},
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "output": {"dist": "uniform", "min": 2, "max": 4}}
    t = traffic.Traffic(mix, 5, 1000)
    prime = t.priming(page_size=16)
    assert len(prime) == 3
    for p, d in zip(prime, t.documents):
        assert p.tokens[:64] == d and len(p.tokens) == 64 + 17
        assert p.max_new == 1


def test_unknown_distribution_is_refused():
    mix = {"loop": "closed", "clients": 1, "ramp_s": 0, "order_seed": 1,
           "prompt": {"dist": "zipf", "min": 1, "max": 2},
           "output": {"dist": "uniform", "min": 1, "max": 2}}
    with pytest.raises(ValueError):
        traffic.Traffic(mix, 1, 100).next()
