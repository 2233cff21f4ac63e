"""The one traffic generator: a mix file's parameters and a seed in, a list
of requests out.

Lengths are stratified: ``n`` draws are the distribution's quantiles at
``(i + 1/2) / n``, clipped and rounded. Their order, and in an open loop the
order of the gaps between arrivals (Poisson quantiles likewise), is drawn
from the mix's ``schedule_seed``, not from the run's seed: every seed serves
the same lengths at the same times, so the work and the queueing of a run
do not swing with the seed. The run's seed draws the prompt tokens,
uniform over the vocabulary (excluding id 0), as it draws the weights.

Mix keys read here:

* ``prompt``, ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}``; an output is also cut to ``max_seq_len - prompt``;
* ``max_seq_len``; ``schedule_seed`` (the order of lengths and gaps);
* open loop: ``rate_rps`` (arrivals over ``[0, seconds)``, Poisson gaps);
* closed loop: ``requests`` (how many to draw; clients cycle through them).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    prompt: np.ndarray          # (L,) int32
    max_new: int
    due_s: float = 0.0          # open loop: when it is due after the start


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(p)) for p in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    x = np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    return rng.permutation(x)


def poisson_gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(-np.log1p(-_quantiles(n)) / rate)


def make(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Spec]:
    order = np.random.default_rng(int(mix["schedule_seed"]))
    rng = np.random.default_rng(int(seed))
    if "rate_rps" in mix:
        n = max(1, int(round(mix["rate_rps"] * seconds)))
    else:
        n = int(mix["requests"])
    plen = lengths(mix["prompt"], n, order)
    olen = lengths(mix["output"], n, order)
    olen = np.minimum(olen, mix["max_seq_len"] - plen)
    due = np.zeros(n)
    if "rate_rps" in mix:
        gaps = poisson_gaps(mix["rate_rps"], n, order)
        # the first request is due at 0 and the mean gap after the last
        # would close the window: all n fall inside [0, seconds)
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        due *= seconds * (n - 1) / n / max(due[-1], 1e-12) if n > 1 else 0.0
    out = []
    for i in range(n):
        toks = rng.integers(1, vocab, size=int(plen[i])).astype(np.int32)
        out.append(Spec(prompt=toks, max_new=int(olen[i]), due_s=float(due[i])))
    return out


def summary(specs: List[Spec]) -> str:
    p = np.array([len(s.prompt) for s in specs])
    o = np.array([s.max_new for s in specs])
    return (f"{len(specs)} requests; prompt median {int(np.median(p))} "
            f"[{p.min()}, {p.max()}], output median {int(np.median(o))} "
            f"[{o.min()}, {o.max()}]")

