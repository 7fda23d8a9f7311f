"""Run one cell of the benchmark once.

  python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json``.  Set-up (weights or inputs from the seed, every shape
the window uses compiled) counts as ``setup_s``; then the window is
measured, then what the window produced is checked against the plain
reference.  With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of part of the window, the program's counters and spans.

Without a TPU, or with fewer chips than the cell asks for, or where the
program cannot be imported, the run exits with code 2 and prints no
result.  Otherwise the last line of stdout is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and
with ``--trace 1`` ``breakdown``), and the numbers compared, each beside
its limit, are the last lines of stderr and the result's last key.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
import traceback
from typing import Optional

if __package__ in (None, ""):           # python3 bench/run.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import common  # noqa: E402

PROCESS_START = common.process_start_monotonic()


@dataclasses.dataclass
class Record:
    """What the per-layer metric readers read (``bench/metrics``)."""

    cell: common.Cell
    peak: dict
    setup: object                    # serve.Engine or compiler.Setup
    window: object                   # serve.Served or compiler.Called
    trace: Optional[object] = None   # trace.Reduced, with --trace 1


def _trace_dir(cell: common.Cell, seed: int) -> str:
    d = common.ROOT / ".bench_traces" / f"{cell.name}-{seed}"
    shutil.rmtree(d, ignore_errors=True)
    return str(d)


def run_serve(cell, seed, seconds, trace: bool):
    from bench import serve
    engine = serve.set_up(cell, seed, seconds)
    window_start = time.monotonic()
    tdir = _trace_dir(cell, seed) if trace else None
    served = serve.run_window(engine, cell.traffic, seed, seconds,
                              cell.config["vocab_size"], tdir)
    peak = common.memory_peak_bytes()
    failed, checks = serve.checks(cell, engine.weights, served, seed)
    e2e = serve.end_to_end(served)
    return (engine, served, window_start, peak, len(served.requests),
            failed, checks, e2e, tdir)


def run_compiler(cell, seed, seconds, trace: bool):
    from bench import compiler
    s = compiler.set_up(cell, seed)
    window_start = time.monotonic()
    tdir = _trace_dir(cell, seed) if trace else None
    called = compiler.run_window(
        s, seed, seconds, tdir, cell.traffic.get("trace", {}).get("calls", 1))
    peak = common.memory_peak_bytes()
    failed, checks = compiler.checks(cell, s, called)
    e2e = {"call_ms": 1e3 * called.seconds / called.calls}
    return (s, called, window_start, peak, called.calls, failed, checks,
            e2e, tdir)


RUNNERS = {"serve": run_serve, "compiler": run_compiler}


def measure(cell: common.Cell, seed: int, seconds: float, trace: bool,
            device: dict, process_start: float) -> dict:
    """One run of ``cell`` on the device in hand, without the look for a
    chip: set-up, the window, the check.  Returns the result object."""
    from bench import trace as trace_mod
    run = RUNNERS[cell.config["runner"]]
    (setup, window, window_start, peak, attempted, failed, checks, e2e,
     tdir) = run(cell, seed, seconds, trace)
    dev = dict(device, memory_peak_bytes=peak)
    correct = all(c["ok"] for c in checks) and failed == 0
    e2e["setup_s"] = window_start - process_start
    metrics = {}
    breakdown = None
    if not trace:
        # a metric is named for its quantity, up to the first dot; what
        # follows names the cells it is bounded over (call_ms.sparse)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"].split(".")[0]],
                                  "unit": m["unit"]}
    else:
        reduced = trace_mod.reduce_dir(tdir)
        shutil.rmtree(tdir, ignore_errors=True)     # keep disk writes small
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
        rec = Record(cell=cell, peak=common.peaks(device["kind"]),
                     setup=setup,
                     window=window, trace=reduced)
        for m in cell.per_layer:
            v = common.metric_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": dev, "breakdown": breakdown,
            "checks": checks}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        cell = common.cell(args.workload)
        device = common.require_tpu(cell.chips)
        common.import_program()
    except (KeyError, OSError, ImportError, common.NoAccelerator) as e:
        print(f"bench.run: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    print(f"[device] {json.dumps(device)}", flush=True)
    print(f"[cache] {common.enable_compile_cache()}", flush=True)
    try:
        res = measure(cell, args.seed, args.seconds, bool(args.trace),
                      device, PROCESS_START)
    except Exception:  # noqa: BLE001 — a run that cannot finish prints no result
        traceback.print_exc()
        return 1
    gc.collect()
    print(common.checks_text(res["checks"]), file=sys.stderr, flush=True)
    print(common.result_line(
        correct=res["correct"], attempted=res["attempted"],
        failed=res["failed"], metrics=res["metrics"], device=res["device"],
        breakdown=res["breakdown"], checks=res["checks"]), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
