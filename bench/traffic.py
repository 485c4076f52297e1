"""The one traffic generator: reads a mix file (``bench/traffic/<mix>.json``)
and makes the requests of a run from ``--seed``.

A mix file holds parameters only:

    loop        "open" (arrivals on a schedule) or "closed" (clients that
                each send their next request when the last one completes)
    rate_per_s  open loop: mean Poisson arrival rate
    clients     closed loop: number of clients
    ramp_s      seconds of traffic before the measured window opens
    block       stratification block (see below)
    prompt      {"dist": "lognormal", "median", "sigma", "min", "max"} or
                {"dist": "uniform", "min", "max"}: prompt length in tokens
                (with documents: the length of the question after one)
    output      the same, for the number of tokens to generate
    documents   optional {"count", "length"}: shared documents that every
                request starts with, prefilled during set-up

    order_seed  the seed of the order of sizes, gaps and documents

Every run serves the same work: lengths and inter-arrival gaps are the
quantiles (i + 0.5) / block of their distribution, shuffled within
consecutive blocks of ``block`` requests in an order drawn from the mix's
own ``order_seed``.  ``--seed`` draws the token ids (and, elsewhere, the
weights), so two seeds differ in what the tokens are, never in how many
there are or when they arrive: a window of a few tens of requests then
measures the system, not the order its seed dealt.  Prompts are unique
random token ids; every request decodes greedily.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def load(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _quantile(dist: dict, p: float) -> int:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * _NORMAL.inv_cdf(p))
        return int(min(max(round(x), lo), hi))
    if dist["dist"] == "uniform":
        return lo + min(int(p * (hi - lo + 1)), hi - lo)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & (2 ** 64 - 1), stream]))


def _stratified(values_of_p, block: int, rng):
    """Endless stream: each block is the ``block`` quantiles of a
    distribution in an order drawn from ``rng``."""
    ps = (np.arange(block) + 0.5) / block

    def blocks():
        while True:
            for p in ps[rng.permutation(block)]:
                yield values_of_p(float(p))

    return blocks()


@dataclasses.dataclass
class Item:
    """One request of the mix: its prompt, how many tokens it asks for,
    and (open loop) the seconds after the ramp's start at which it is due."""
    tokens: list
    max_new: int
    offset: float = 0.0


class Traffic:
    """The seeded requests of one run of one mix."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.vocab = vocab
        self.block = int(mix.get("block", 16))
        order = int(mix["order_seed"])
        self._tok = _rng(seed, 0)
        self._prompt = _stratified(lambda p: _quantile(mix["prompt"], p),
                                   self.block, _rng(order, 1))
        self._output = _stratified(lambda p: _quantile(mix["output"], p),
                                   self.block, _rng(order, 2))
        docs = mix.get("documents")
        self.documents = ([self._tokens(docs["length"])
                           for _ in range(docs["count"])] if docs else [])
        self._doc = (_stratified(
            lambda p: int(p * len(self.documents)), len(self.documents),
            _rng(order, 3)) if docs else None)
        if self.loop == "open":
            rate = float(mix["rate_per_s"])
            self._gap = _stratified(lambda p: -math.log(1.0 - p) / rate,
                                    self.block, _rng(order, 4))
            self._t = 0.0

    @property
    def ramp_s(self) -> float:
        return float(self.mix["ramp_s"])

    @property
    def clients(self) -> int:
        return int(self.mix["clients"])

    def _tokens(self, n: int) -> list:
        return self._tok.integers(0, self.vocab, size=n).tolist()

    def next(self) -> Item:
        """The next request: in arrival order (open loop), or in the order
        the clients send them (closed loop)."""
        tokens = self._tokens(next(self._prompt))
        if self._doc is not None:
            tokens = self.documents[next(self._doc)] + tokens
        item = Item(tokens=tokens, max_new=next(self._output))
        if self.loop == "open":
            self._t += next(self._gap)
            item.offset = self._t
        return item

    def priming(self, page_size: int) -> list:
        """Set-up requests that publish each shared document in the prefix
        cache: the document plus one page and one token of unique tail (so
        every document page is a full prompt block before the last token),
        one output token."""
        return [Item(tokens=d + self._tokens(page_size + 1), max_new=1)
                for d in self.documents]

    def warmup(self, chunk: int) -> Item:
        """A request that runs every executable the window uses: two
        prefill chunks and two decode steps."""
        return Item(tokens=self._tokens(2 * chunk + 1), max_new=2)
