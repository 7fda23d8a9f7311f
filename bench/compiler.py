"""Runner for programs compiled by the program's own pipeline
(``"runner": "compiler"`` in the configuration's file).

The mix's file names the input program, found by that name in
``bench/programs/<name>.py``, and its sizes.  One run: the inputs made
from the seed, ``pipeline.compile`` with the configuration's
target, one warm call, then calls back to back for the window, each
ending in ``block_until_ready``; the answers of a sample of the calls,
drawn from the seed, are compared with a plain reference afterwards.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np

from bench import common

KEEP = 4          # answers kept for the check, a reservoir drawn from the seed


@dataclasses.dataclass
class Program:
    """What ``make`` of ``bench/programs/<name>.py`` returns."""

    args: tuple                     # inputs made from the seed
    fn: Callable                    # traced by pipeline.compile
    reference: Callable             # args -> float64 answer
    control: Callable               # args -> answer at the control's
    flops: float                    #   precision
    bytes: float


def rel_err(got, ref) -> float:
    """max |got - ref| over max |ref|."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)),
                                                 1e-30))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Setup:
    program: Program
    module: object                  # the pipeline's CompiledModule
    dev_args: list
    pass_s: float


def set_up(cell: common.Cell, seed: int) -> Setup:
    import jax
    from repro.core import pipeline
    from repro.core.options import CompileOptions
    cfg, mix = cell.config, cell.traffic
    prog = common.program_maker(mix["program"])(cfg, mix, seed)
    opts = CompileOptions(target=cfg["target"],
                          interpret=cfg.get("interpret"))
    mod = pipeline.compile(prog.fn, *prog.args, options=opts,
                           name=mix["program"])
    pass_s = sum(r.seconds for r in getattr(mod.graph, "pass_stats", []))
    dev = [jax.device_put(a) for a in prog.args]
    jax.block_until_ready(mod(*dev))          # compiles the callable
    return Setup(program=prog, module=mod, dev_args=dev, pass_s=pass_s)


@dataclasses.dataclass
class Called:
    calls: int
    seconds: float
    kept: List[np.ndarray]          # a sample of the answers, on the host


def run_window(s: Setup, seed: int, seconds: float,
               trace_dir: Optional[str] = None,
               trace_calls: int = 1) -> Called:
    """Calls back to back until ``seconds`` have passed; the call in
    flight at the end completes and counts.  A reservoir of the answers,
    drawn from the seed, is kept for the check.  With ``trace_dir`` the
    first ``trace_calls`` calls run under the profiler, inside a
    ``bench.traced`` span."""
    import jax
    rng = np.random.default_rng([seed, 3])
    kept: list = []
    n = 0
    traced = None
    t0 = time.monotonic()
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        traced = jax.profiler.TraceAnnotation("bench.traced")
        traced.__enter__()
    try:
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                y = jax.block_until_ready(s.module(*s.dev_args))
            if len(kept) < KEEP:
                kept.append(y)
            else:
                j = int(rng.integers(n + 1))
                if j < KEEP:
                    kept[j] = y
            n += 1
            if traced is not None and n == trace_calls:
                traced.__exit__(None, None, None)
                jax.profiler.stop_trace()
                traced = None
            if time.monotonic() - t0 >= seconds and traced is None:
                break
    finally:
        if traced is not None:
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
    elapsed = time.monotonic() - t0
    return Called(calls=n, seconds=elapsed,
                  kept=[np.asarray(y) for y in kept])


def checks(cell: common.Cell, s: Setup, called: Called):
    """(failed calls, the numbers compared with their limits)."""
    want = s.program.reference(*s.program.args)
    limit = cell.traffic["check"]["rel_err"]
    errs = [rel_err(y, want) for y in called.kept]
    failed = sum(1 for e in errs if not e <= limit)
    worst = max(errs) if errs else float("inf")
    return failed, [
        {"name": "answers_compared", "value": len(errs), "limit": 1,
         "ok": len(errs) >= 1},
        {"name": "worst_rel_err", "value": worst, "limit": limit,
         "ok": worst <= limit}]
