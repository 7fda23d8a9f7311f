"""Readings behind a cell's correctness limits: the program's number over
many seeds and the control's over some of them, in one process.

  python3 -m bench.control --workload <name> --seeds 1,2,... \\
      --control-seeds 1,2,3 --seconds <s> [--out readings.json]

The benchmark's own runs never run this.  For each seed it makes the
weights or inputs, drives the cell's timed path for one window, and
reads the number the run's check compares (the widest logit gap of the
served tokens, or the worst relative error of the answers).  For each
control seed it also reads the same number for the control: the plain
reference in the program's place at the precision below the one the
configuration states (``bench/reference``).  Prints one JSON line per
seed and writes them all to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import common


def serve_readings(cell, seeds, control_seeds, seconds):
    from bench import serve
    engine = serve.set_up(cell, seeds[0], seconds)
    vocab = cell.config["vocab_size"]
    for seed in seeds:
        t = time.monotonic()
        if seed != seeds[0]:
            engine.weights = serve.make_weights(
                cell.config, engine.model.cfg.padded_vocab, seed)
        served = serve.run_window(engine, cell.traffic, seed, seconds,
                                  vocab, None)
        failed = serve.failures(served.requests, vocab)
        picked = serve.sample(served.requests, seed,
                              cell.traffic["check"]["sample"])
        gap, n = serve.widest_gap(engine.weights, cell.config, picked)
        row = dict({"seed": seed, "program": gap, "compared_tokens": n,
                    "failed": failed, "requests": len(served.requests)},
                   **serve.end_to_end(served))
        if seed in control_seeds:
            row["control"] = serve.control_gap(engine.weights, cell.config,
                                               picked)
        row["seconds"] = time.monotonic() - t
        yield row


def compiler_readings(cell, seeds, control_seeds, seconds):
    from bench import compiler
    for seed in seeds:
        t = time.monotonic()
        s = compiler.set_up(cell, seed)
        called = compiler.run_window(s, seed, seconds)
        want = s.program.reference(*s.program.args)
        row = {"seed": seed, "calls": called.calls,
               "program": max(compiler.rel_err(y, want)
                              for y in called.kept)}
        if seed in control_seeds:
            row["control"] = compiler.rel_err(
                s.program.control(*s.program.args), want)
        row["seconds"] = time.monotonic() - t
        del s
        yield row


READINGS = {"serve": serve_readings, "compiler": compiler_readings}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out")
    args = p.parse_args(argv)
    cell = common.cell(args.workload)
    try:
        device = common.require_tpu(cell.chips)
    except common.NoAccelerator as e:
        print(f"bench.control: {e}", file=sys.stderr)
        return 2
    common.import_program()
    common.enable_compile_cache()
    seeds = [int(x) for x in args.seeds.split(",")]
    control = {int(x) for x in args.control_seeds.split(",") if x}
    rows = []
    for row in READINGS[cell.config["runner"]](cell, seeds, control,
                                               args.seconds):
        row["device"] = device
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["program"] for r in rows]
    ctrl = [r["control"] for r in rows if "control" in r]
    summary = {"workload": cell.name, "program_max": max(prog),
               "control_min": min(ctrl) if ctrl else None,
               "seeds": len(prog), "control_seeds": len(ctrl)}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
