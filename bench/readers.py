"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader takes the run's :class:`bench.run.Record` and returns a
number, or ``None`` where the run holds nothing to read it from (no
trace, no such kernel, no decode step in the traced part)."""
from __future__ import annotations

from typing import Optional

from bench import stats, work

DECODE_KERNEL = "decode_attention"   # the kernel that marks the decode program


def decode_program(run) -> Optional[tuple]:
    """(executions, device seconds) of the program that holds the decode
    attention kernel, within the traced part of the window."""
    if run.trace is None:
        return None
    return run.trace.program_holding(DECODE_KERNEL)


def decode_step_ms(run) -> Optional[float]:
    p = decode_program(run)
    return None if p is None else 1e3 * p[1] / p[0]


def traced_steps(run) -> list:
    """The decode steps whose tokens came out in the traced part."""
    a, b = run.window.trace_window
    return stats.decode_steps(run.window.requests, a, b)


def decode_mfu(run) -> Optional[float]:
    """The decode step's share of the chip's peak, in %: the least time
    its required work takes at the peaks (from the live batch, see
    :func:`bench.work.decode_step_work`) over its measured device time,
    both per step."""
    p = decode_program(run)
    steps = traced_steps(run) if p is not None else []
    if not steps:
        return None
    cfg = run.cell.config
    least = sum(work.least_seconds(
        *work.decode_step_work(cfg, [pos for _, pos in s],
                               cfg["served_dtype"]), run.peak)
        for s in steps) / len(steps)
    return 100.0 * least / (p[1] / p[0])


def idle_share(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def traced_calls(run) -> int:
    return run.trace.span_count("bench.call") if run.trace else 0
