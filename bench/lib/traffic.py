"""The benchmark's one request generator, driven by a traffic file.

An open-loop mix (``"kind": "open_loop"``) gives a Poisson arrival rate
and lognormal prompt and output lengths, each with a median, a sigma and
clipping bounds. The window of ``W`` seconds holds ``round(rate * W)``
arrivals; their gaps are the exponential distribution's quantiles at
``(i + 0.5) / n``, scaled to fill the window, and each arrives at the
middle of its gap. Prompt and output lengths are the lognormal's quantiles
at the same points. Each set is dealt into strata of about ``stratum``
consecutive arrivals, one value of each band to a stratum. The warm-up
arrivals before the window are made alike from their own sets. So every
seed offers each stretch of the window alike work, and the seed draws
which value of a band goes to which stratum, the order within each, the
pairing of prompts with outputs, and the token ids (and, elsewhere, the
weights). With one unstratified order per seed, which requests spilled
past the close changed the window's output tokens by 13% from seed to
seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the schedule: due time on the run's clock (seconds,
    0 is the window's start), prompt token ids and output length."""

    due: float
    prompt: np.ndarray
    max_new: int


def _lognormal_set(n: int, median: float, sigma: float, lo: int, hi: int):
    nd = NormalDist()
    u = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in u])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def lengths(spec: dict, n: int) -> np.ndarray:
    """The fixed set of ``n`` lengths of one lognormal spec
    (``median``, ``sigma``, ``min``, ``max``), in rising order."""
    return _lognormal_set(n, spec["median"], spec["sigma"], spec["min"],
                          spec["max"])


def _deal(values: np.ndarray, sizes: np.ndarray, rng) -> np.ndarray:
    """``values`` dealt into consecutive strata of ``sizes``: each band of
    ``len(sizes)`` values in rising order gives one value to each stratum
    that has room, which value to which from ``rng``; each stratum then in
    an order from ``rng``."""
    m = len(sizes)
    strata = [[] for _ in range(m)]
    v = np.sort(values)
    for b in range(0, len(v), m):
        room = [j for j in range(m) if len(strata[j]) < sizes[j]]
        for x, j in zip(v[b:b + m], rng.permutation(room)):
            strata[j].append(x)
    return np.concatenate([rng.permutation(np.array(st)) for st in strata])


def _span(mix: dict, rng, start: float, length: float, n: int):
    """``n`` arrivals over ``[start, start + length)``: due times, prompt
    lengths and output lengths. Each fixed set is dealt into strata of
    about ``stratum`` arrivals that follow each other in time, so that
    every stretch of the span offers alike work."""
    m = max(1, -(-n // int(mix["stratum"])))
    sizes = rng.permutation(
        np.array([n // m + (j < n % m) for j in range(m)]))
    u = (np.arange(n) + 0.5) / n
    gaps = _deal(-np.log1p(-u), sizes, rng)
    gaps *= length / gaps.sum()
    due = start + np.cumsum(gaps) - gaps / 2
    return (due, _deal(lengths(mix["prompt"], n), sizes, rng),
            _deal(lengths(mix["output"], n), sizes, rng))


def schedule(mix: dict, seed: int, *, lead: float, window: float,
             vocab: int, rate: float | None = None) -> List[Planned]:
    """Requests due from ``-lead`` until the window's close at the mix's
    rate (or ``rate``), in due order."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    rng = np.random.default_rng([int(seed), 3])
    parts = [_span(mix, rng, 0.0, window, max(1, round(rate * window)))]
    if lead > 0:
        parts.insert(0, _span(mix, rng, -lead, lead,
                              max(1, round(rate * lead))))
    due, prompts, outputs = (np.concatenate(x) for x in zip(*parts))
    toks = np.random.default_rng([int(seed), 1])
    return [
        Planned(float(t), toks.integers(0, vocab, int(p)).astype(np.int32),
                int(o))
        for t, p, o in zip(due, prompts, outputs)
    ]
