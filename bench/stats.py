"""The arithmetic behind the end-to-end serving metrics, on request
timestamps of one serving clock.  Every statistic is taken over all
requests or all gaps of the window, never over medians of pieces."""
from __future__ import annotations

from typing import Iterable, List, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (linear interpolation between closest
    ranks, numpy's default) of every value given."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(requests: Iterable) -> List[float]:
    """Seconds from each request's scheduled arrival to its first token:
    the wait a stall imposes on later arrivals counts."""
    return [r.token_times[0] - r.arrival for r in requests if r.token_times]


def token_gaps(requests: Iterable) -> List[float]:
    """Every gap between consecutive output tokens of every request."""
    out: List[float] = []
    for r in requests:
        t = r.token_times
        out.extend(t[i + 1] - t[i] for i in range(len(t) - 1))
    return out


def queue_waits(requests: Iterable) -> List[float]:
    """Seconds from scheduled arrival to admission into a decode slot."""
    return [r.admitted_at - r.arrival for r in requests
            if r.admitted_at is not None]


def tokens_in(requests: Iterable, start: float, end: float) -> int:
    """Output tokens emitted within ``[start, end]`` of the serving
    clock."""
    return sum(1 for r in requests for t in r.token_times
               if start <= t <= end)


def rate(count: float, seconds: float) -> float:
    """A rate over the whole window: all the work over all the time."""
    if seconds <= 0:
        raise ValueError("a rate needs a window of positive length")
    return count / seconds


def decode_steps(requests: Iterable, start: float = float("-inf"),
                 end: float = float("inf")) -> List[List[tuple]]:
    """The decode steps whose tokens were emitted within ``[start,
    end]``, reconstructed from the timestamps: one decode step stamps all
    the tokens it emits with one time.  Each step is a list of
    ``(prompt_len, position)`` pairs, one per live request, where
    ``position`` is the context length the step attended over.  A
    request's first token comes from its prefill and is no decode."""
    by_time: dict = {}
    for r in requests:
        for i, t in enumerate(r.token_times[1:], start=1):
            if start <= t <= end:
                by_time.setdefault(t, []).append(
                    (r.prompt_len, r.prompt_len + i))
    return [by_time[t] for t in sorted(by_time)]
