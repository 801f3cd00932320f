"""Seeded traffic: the requests of one run, from a mix file and ``--seed``.

Every seed gets the same multiset of prompt lengths, output lengths and
gaps between arrivals: each ``block`` of consecutive requests holds the
stratified quantiles ``(i + 0.5) / block``, ``i < block``, of the mix's
distributions. The seed draws their order within each block and the
prompt tokens. So two seeds offer the same work, spread alike over the
window, in another order, and the spread between runs is the system's,
not the draw's.

Prompt lengths come from a fixed set of buckets, because the prefill
compiles once per distinct length; the harness warms exactly
:func:`prompt_buckets` before the window opens.

Mix keys: ``loop`` (``open``: Poisson arrivals at the cell's
``rate_per_s``; ``closed``: the cell's ``clients`` each send the next
request when the last one finished, no think time), ``prompt`` and
``output`` (``dist``: ``lognormal`` with ``median``/``sigma``, or
``uniform_steps`` with ``step``; ``min``/``max`` clip; ``round_up``
rounds a length up to a multiple), ``block``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Optional

import numpy as np

NOISE = 0.02  # share of prompt tokens replaced by uniform draws
_NORMAL = NormalDist()


@dataclass(frozen=True)
class Planned:
    rid: int
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _seed_words(seed: int) -> list[int]:
    seed %= 2**64  # any whole number, negative or past 32 bits
    return [seed & 0xFFFFFFFF, seed >> 32]


def seeded_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(_seed_words(seed) + list(stream))


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` stratified lengths of one distribution, ascending."""
    us = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(u)) for u in us])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    elif kind == "uniform_steps":
        steps = np.arange(dist["min"], dist["max"] + 1, dist["step"])
        x = steps[np.minimum((us * len(steps)).astype(int), len(steps) - 1)]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = np.clip(x, dist["min"], dist["max"])
    if dist.get("round_up"):
        r = dist["round_up"]
        x = np.ceil(x / r) * r
    return x.astype(np.int64)


def prompt_buckets(mix: dict) -> list[int]:
    """Every prompt length the mix can produce."""
    d = mix["prompt"]
    if d["dist"] == "uniform_steps":
        return list(range(d["min"], d["max"] + 1, d["step"]))
    r = d.get("round_up")
    if not r:
        raise ValueError("a prompt distribution needs round_up or uniform_steps: "
                         "each distinct length compiles a prefill")
    return list(range(math.ceil(d["min"] / r) * r, math.ceil(d["max"] / r) * r + 1, r))


def prompt_tokens(vocab: int, length: int, seed: int, rid: int) -> np.ndarray:
    """Structured prompt tokens: an arithmetic progression mod ``vocab``
    with a per-request step, plus uniform noise (the synthetic corpus of
    ``repro.data.dataset.SyntheticCorpus``, copied)."""
    rng = seeded_rng(seed, 3, rid)
    step = rng.integers(1, min(16, vocab))
    x0 = rng.integers(0, vocab)
    toks = (x0 + step * np.arange(length)) % vocab
    flip = rng.random(length) < NOISE
    toks[flip] = rng.integers(0, vocab, size=int(flip.sum()))
    return toks.astype(np.int32)


class Traffic:
    """The requests of one run, generated on demand in blocks.

    Open loop: ``n = ceil(rate * seconds)`` requests, due at the
    cumulative sums of exponential gaps stratified per block (the last
    may fall just past the window and is never sent). Closed loop: an
    endless sequence; ``driver.drive`` takes the next one when a client is free.
    """

    def __init__(self, mix: dict, vocab: int, seed: int, *,
                 rate: Optional[float] = None, seconds: Optional[float] = None):
        self.mix, self.vocab, self.seed = mix, vocab, seed
        self.open = mix["loop"] == "open"
        self.block = int(mix["block"])
        if self.open:
            if not rate or not seconds:
                raise ValueError("an open-loop mix needs a rate and a window")
            self.n: Optional[int] = math.ceil(rate * seconds)
            gaps = -np.log1p(-(np.arange(self.block) + 0.5) / self.block) / rate
            blocks = [seeded_rng(seed, 2, b).permutation(gaps) for b in range(-(-self.n // self.block))]
            self.dues = np.cumsum(np.concatenate(blocks)[:self.n])
        elif mix["loop"] == "closed":
            self.n = None
        else:
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self._blocks: dict[int, tuple[np.ndarray, np.ndarray]] = {}

    def _lengths(self, b: int) -> tuple[np.ndarray, np.ndarray]:
        if b not in self._blocks:
            p = quantile_lengths(self.mix["prompt"], self.block)
            o = quantile_lengths(self.mix["output"], self.block)
            self._blocks[b] = (seeded_rng(self.seed, 0, b).permutation(p),
                               seeded_rng(self.seed, 1, b).permutation(o))
        return self._blocks[b]

    def __getitem__(self, i: int) -> Planned:
        if self.n is not None and not 0 <= i < self.n:
            raise IndexError(i)
        p, o = self._lengths(i // self.block)
        j = i % self.block
        return Planned(i, prompt_tokens(self.vocab, int(p[j]), self.seed, i), int(o[j]))

    def __len__(self) -> int:
        if self.n is None:
            raise TypeError("a closed-loop sequence has no length")
        return self.n
