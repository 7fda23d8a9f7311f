"""One generator for every serving mix, driven by the mix's data file
(``bench/traffic/<name>.json``).

A mix fixes the distributions of prompt and output lengths and the
arrivals.  Its requests are one fixed trace: sizes and gaps between
arrivals drawn at evenly spaced quantiles of those distributions, put in
an order drawn from the mix's own ``"sizes_seed"``.  The run's seed
draws what each request asks (its prompt tokens; the weights come from
it too).  So every seed replays the same schedule with other content:
the work of a run does not swing with the seed, a tail over a hundred
requests is comparable between commits (where the order came from the
run's seed, the 95th percentile of time to first token swung between
orders by 14 % with no slot wait and twentyfold where an order packed
long requests together), and the compiled shapes (the prompt buckets,
the widest context) are the same in every run.

Length distributions (``"prompt"`` / ``"output"``):

* ``{"kind": "lognormal", "median": m, "sigma": s}``
* ``{"kind": "choice", "values": [...]}``: the values in equal shares
* ``{"kind": "fixed", "value": v}``

each with optional ``"min"`` / ``"max"`` clips and, for prompts,
``"buckets"``: a length is rounded up to the next bucket, so that the
engine compiles one prefill per bucket.

Arrivals (``"arrivals"``): ``{"kind": "poisson", "rate_per_s": r}``,
an open loop at a fixed rate, ``floor(r * seconds)`` requests over the
window.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request to send: when, and what."""

    arrival: float
    prompt: np.ndarray        # int32 token ids
    gen_len: int

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``dist``, ascending."""
    kind = dist["kind"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(u) for u in _grid(n)])
        v = np.ceil(dist["median"] * np.exp(dist["sigma"] * z))
    elif kind == "choice":
        vals = sorted(dist["values"])
        v = np.array([vals[i * len(vals) // n] for i in range(n)], float)
    elif kind == "fixed":
        v = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    v = np.clip(v, dist.get("min", 1), dist.get("max", np.inf))
    if "buckets" in dist:
        b = np.asarray(sorted(dist["buckets"]), float)
        v = b[np.minimum(np.searchsorted(b, v), len(b) - 1)]
    return v.astype(np.int64)


def sizes(mix: dict, n: int) -> List[tuple]:
    """The fixed multiset of ``(prompt_len, gen_len)`` pairs of ``n``
    requests.  Prompts and outputs are paired by a permutation fixed by
    the mix (``"sizes_seed"``), and the request with the longest prompt
    also gets the longest output, so the widest context, which sets the
    engine's page-table width, is the same in every run."""
    p = lengths(mix["prompt"], n)
    g = lengths(mix["output"], n)
    g = g[np.random.default_rng(mix.get("sizes_seed", 0)).permutation(n)]
    top = int(np.argmax(p))
    j = int(np.argmax(g))
    g[top], g[j] = g[j], g[top]
    return [(int(a), int(b)) for a, b in zip(p, g)]


def gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps between arrivals at evenly spaced quantiles of the
    exponential distribution of a Poisson process at ``rate``."""
    return -np.log1p(-_grid(n)) / rate


def _order(mix: dict) -> np.random.Generator:
    """The generator of the trace's order: the mix's, not the run's."""
    return np.random.default_rng([mix.get("sizes_seed", 0), 1])


def _prompts(rng: np.random.Generator, lens, vocab: int) -> list:
    return [rng.integers(1, vocab, int(k)).astype(np.int32) for k in lens]


def open_loop(mix: dict, seed: int, seconds: float,
              vocab: int) -> List[Spec]:
    """The requests of one window of an open loop at the mix's fixed
    rate, every arrival inside ``[0, seconds)``."""
    arr = mix["arrivals"]
    if arr["kind"] != "poisson":
        raise ValueError(f"open_loop needs poisson arrivals, not "
                         f"{arr['kind']!r}")
    n = max(1, math.floor(arr["rate_per_s"] * seconds))
    order = _order(mix)
    g = gaps(arr["rate_per_s"], n)[order.permutation(n)]
    t = np.cumsum(g)
    if t[-1] >= seconds:      # keep the whole schedule inside the window
        t *= (seconds * n / (n + 1)) / t[-1]
    pairs = sizes(mix, n)
    pairs = [pairs[i] for i in order.permutation(n)]
    prompts = _prompts(np.random.default_rng(seed), [p for p, _ in pairs],
                       vocab)
    return [Spec(arrival=float(a), prompt=pr, gen_len=g_)
            for a, pr, (_, g_) in zip(t, prompts, pairs)]


def warmup_sizes(mix: dict, seconds: float,
                 decode_steps: int = 16) -> List[tuple]:
    """``(prompt_len, gen_len)`` pairs that compile every shape a run of
    this mix uses: one request per prompt length the sizes hold, and one
    that reaches the widest context, which sets the width of the decode
    step's page table.  That one decodes ``decode_steps`` tokens after a
    prompt of the rest of the widest context: a prompt length the window
    may never send (one prefill more to compile), in place of hundreds
    of decode steps after the longest bucket."""
    n = max(1, math.floor(mix["arrivals"]["rate_per_s"] * seconds))
    pairs = sizes(mix, n)
    widest = max(p + g for p, g in pairs)
    out = [(p, 2) for p in sorted({p for p, _ in pairs})]
    tail = min(widest - out[-1][0], decode_steps)
    if widest - tail == out[-1][0]:
        out[-1] = (widest - tail, tail)
    else:
        out.append((widest - tail, tail))
    return out
