"""Requests from a traffic file and a seed.

A traffic file (``traffic/<mix>.json``) gives the loop and the length
distributions; this one generator reads every mix. The sizes and the gaps
between arrivals are a fixed set taken at evenly spaced quantiles of the
distributions, put in an order drawn from the mix's own
``schedule_seed``: every run replays the same schedule, as a recorded
trace would be replayed. ``--seed`` draws the token ids (and, elsewhere,
the weights). A queue at four fifths of its capacity turns a reordering
of the same arrivals into tails that differ by tens of percent, which is
why the seed does not order them.

Open loop ("loop": "open"): ``rate_per_s`` x ``seconds`` requests, due at
times whose gaps are exponential quantiles scaled to fill the window, the
first at 0.

Closed loop ("loop": "closed"): ``clients`` clients, each sending its next
request when its last one returns. Requests come in blocks of ``clients``
that each hold the same quantiles in a fresh order, so any whole number
of blocks holds the same work.

Length distributions: {"dist": "uniform", "min", "max"} (integers, both
ends included) or {"dist": "lognormal", "median", "sigma", "min", "max"}
(clipped to min..max).
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int
    due: float            # seconds after the window opens (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``dist``, ascending."""
    q = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + np.floor(q * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(v)) for v in q])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def _request(rng, index, due, plen, max_new, vocab) -> Request:
    return Request(index, float(due), rng.integers(0, vocab, int(plen), dtype=np.int32),
                   int(max_new))


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    order = np.random.default_rng(mix["schedule_seed"])
    gaps = -np.log1p(-_quantiles(n))                  # exponential quantiles
    gaps = order.permutation(gaps) * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plens = order.permutation(lengths(mix["prompt"], n))
    outs = order.permutation(lengths(mix["output"], n))
    ids = np.random.default_rng(seed)
    return [_request(ids, i, due[i], plens[i], outs[i], vocab) for i in range(n)]


def closed_loop(mix: Dict, seed: int, vocab: int) -> Iterator[Request]:
    """Endless stream of requests in the order clients send them."""
    k = int(mix["clients"])
    order = np.random.default_rng(mix["schedule_seed"])
    ids = np.random.default_rng(seed)
    plens, outs = lengths(mix["prompt"], k), lengths(mix["output"], k)
    i = 0
    while True:
        for p, o in zip(order.permutation(plens), order.permutation(outs)):
            yield _request(ids, i, 0.0, p, o, vocab)
            i += 1


def offered_tokens_per_s(mix: Dict) -> float:
    """Output tokens per second that an open loop offers."""
    return mix["rate_per_s"] * float(lengths(mix["output"], 1000).mean())
