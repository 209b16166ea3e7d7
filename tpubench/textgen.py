"""Seeded MS MARCO-shaped text and arrivals.

Every seed gets the same multiset of lengths and inter-arrival gaps, in
another order: lengths and gaps are the quantiles of their distribution,
shuffled by the seed.  Only the word content and the order depend on the
seed, so two seeds do the same amount of work.

Words are ``w<rank>`` tokens drawn from a Zipf law over a fixed
vocabulary; the program's tokenizer maps each word to one token.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose)."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed), tag])


def lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the quantiles of a clipped, rounded lognormal.

    ``spec``: ``{"mu", "sigma", "min", "max"}`` of the word count."""
    inv = NormalDist().inv_cdf
    qs = [(i + 0.5) / n for i in range(n)]
    vals = [math.exp(spec["mu"] + spec["sigma"] * inv(q)) for q in qs]
    out = np.rint(vals).astype(np.int64)
    return np.clip(out, spec["min"], spec["max"])


class Words:
    """Zipf-distributed word tokens over ``vocab`` word types."""

    def __init__(self, vocab: int, exponent: float):
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -exponent
        self.cdf = np.cumsum(p / p.sum())
        self.vocab = vocab

    def texts(self, lengths: np.ndarray, rng: np.random.Generator
              ) -> list[str]:
        total = int(lengths.sum())
        ids = np.minimum(np.searchsorted(self.cdf, rng.random(total)),
                         self.vocab - 1)
        out, pos = [], 0
        for n in lengths.tolist():
            out.append(" ".join(f"w{i}" for i in ids[pos:pos + n].tolist()))
            pos += n
        return out


def shuffled_lengths(n: int, spec: dict, rng: np.random.Generator
                     ) -> np.ndarray:
    return rng.permutation(lognormal_lengths(n, spec))


def make_texts(n: int, length_spec: dict, word_spec: dict, seed: int,
               stream: str) -> list[str]:
    """``n`` texts: lengths from ``length_spec`` (same multiset for every
    seed), words from ``word_spec`` = ``{"vocab", "zipf"}``."""
    rng = rng_for(seed, stream)
    lengths = shuffled_lengths(n, length_spec, rng)
    return Words(word_spec["vocab"], word_spec["zipf"]).texts(lengths, rng)


def poisson_schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Send times in ``[0, seconds)`` of ``round(rate * seconds)``
    requests: the exponential gaps' quantiles in a seeded order, scaled so
    the last send falls half a mean gap before the window closes."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = rng_for(seed, "arrival").permutation(gaps)
    times = np.cumsum(gaps) - gaps[0]
    span = seconds * (1.0 - 0.5 / n)
    return times * (span / times[-1]) if n > 1 else times
