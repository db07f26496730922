"""The one traffic generator.  A mix is a data file, ``traffic/<mix>.json``;
its ``kind`` picks one of two shapes:

* ``open_loop``: independent requests due at exponential gaps (Poisson
  arrivals at the cell's ``rate``, requests/s), each with a prompt and
  an output length;
* ``backlog``: an offline batch whose requests are all due at 0, more
  than the window can finish, so the queue never empties.

Fixed work per seed: open-loop traffic comes in two segments, the ramp
and the measured window, each with ``round(rate x length)`` arrivals
whose gaps are the quantiles ``q = (i + 0.5) / n`` of the exponential,
scaled to fill the segment exactly, and whose lengths are the quantiles
of their distributions.  The seed draws the order of gaps and of each
kind of length within a segment, independently and with no other
constraint (and the token ids), so short gaps and long requests bunch as
they would by chance; every seed offers the window the same number of
requests with the same set of sizes and gaps.

A length spec is ``{"dist": "lognormal", "median": m, "sigma": s,
"min": a, "max": b}`` (clipped to [a, b]) or ``{"dist": "uniform",
"min": a, "max": b}``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclass
class Item:
    due: float            # seconds after the traffic starts
    prompt: np.ndarray    # int32 token ids
    max_new: int


def quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``spec``."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        v = lo + q * (hi - lo + 1)
        return np.clip(np.floor(v), lo, hi).astype(np.int64)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.round(v), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def exp_gaps(mean: float, n: int) -> np.ndarray:
    """``n`` exponential gaps of mean ``mean`` at the quantiles."""
    q = (np.arange(n) + 0.5) / n
    return -mean * np.log1p(-q)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def arrivals(order, rate: float, start: float, length: float) -> np.ndarray:
    """Due times of one segment: ``round(rate x length)`` exponential gaps
    at the quantiles, in the seed's order, scaled to sum to ``length``;
    the first is due at ``start``."""
    n = max(1, int(round(rate * length)))
    g = order.permutation(exp_gaps(1.0, n))
    g *= length / g.sum()
    return start + np.concatenate([[0.0], np.cumsum(g)[:-1]])


def generate(mix: Dict[str, Any], rate: float, ramp: float, seconds: float,
             seed: int, vocab: int) -> List[Item]:
    """The items due in the ramp and the window that follows it, sorted
    by due time (seconds after the traffic starts)."""
    kind = mix["kind"]
    order, toks = _rng(seed, 1), _rng(seed, 2)
    segments = [(0.0, ramp), (ramp, seconds)]

    def tokens(n):
        return toks.integers(1, vocab, size=int(n), dtype=np.int32)

    def lengths(spec, n):
        return order.permutation(quantiles(spec, n))

    items: List[Item] = []
    if kind == "open_loop":
        for start, length in segments:
            due = arrivals(order, rate, start, length)
            n = len(due)
            items += [Item(float(d), tokens(p), int(o)) for d, p, o in
                      zip(due, lengths(mix["prompt"], n),
                          lengths(mix["output"], n))]
    elif kind == "backlog":
        n = int(mix["requests"])
        items = [Item(0.0, tokens(p), int(o)) for p, o in
                 zip(lengths(mix["prompt"], n), lengths(mix["output"], n))]
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    items = [it for it in items if it.due < ramp + seconds]
    items.sort(key=lambda it: it.due)
    return items


def prompt_range(mix: Dict[str, Any]) -> tuple:
    """Shortest and longest prompt the mix can produce."""
    return mix["prompt"]["min"], mix["prompt"]["max"]
