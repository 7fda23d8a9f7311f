"""Find the knee of an open-loop serving cell: the highest arrival rate
served without a growing backlog.  Run once when a cell is defined; the
rate the cell uses is then written into its traffic file as a number.

  python3 -m bench.tools.sweep --workload qwen2-1.5b.chat \\
      --rates 1.2,1.4,1.6 --orders 0,1,2 --seconds 51 --seed 1

One process: set-up once, then one window per order and rate with the
mix's sizes at that rate, put in the order that ``sizes_seed`` draws
(the mix's own is its ``sizes_seed``).  Prints one JSON line per window:
requests, output tokens per second over the window, the time the last
request finished after the window closed (the backlog left), TTFT and
queue-wait percentiles.  The knee is the highest rate at which no order
builds a backlog.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys

from bench import common, stats


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--orders", default="",
                   help="sizes_seed values; default the mix's own")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    cell = common.cell(args.workload)
    try:
        common.require_tpu(cell.chips)
    except common.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    common.import_program()
    common.enable_compile_cache()
    from bench import serve
    engine = serve.set_up(cell, args.seed, args.seconds)
    orders = [int(o) for o in args.orders.split(",") if o] or \
        [cell.traffic.get("sizes_seed", 0)]
    rates = [float(r) for r in args.rates.split(",")]
    for order, rate in ((o, r) for o in orders for r in rates):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_s"] = rate
        mix["sizes_seed"] = order
        # the widest context is the mix's, whatever the rate
        served = serve.run_window(engine, mix, args.seed, args.seconds,
                                  cell.config["vocab_size"], None)
        reqs = served.requests
        e2e = serve.end_to_end(served)
        print(json.dumps({
            "sizes_seed": order, "rate_per_s": rate, "requests": len(reqs),
            "output_tok_s": e2e["output_tok_s"],
            "drain_s": served.end - args.seconds,
            "ttft_p50_ms": 1e3 * stats.percentile(stats.ttfts(reqs), 50),
            "ttft_p95_ms": e2e["ttft_p95_ms"],
            "tbt_p50_ms": 1e3 * stats.percentile(stats.token_gaps(reqs), 50),
            "tbt_p95_ms": e2e["tbt_p95_ms"],
            "queue_wait_p95_ms": 1e3 * stats.percentile(
                stats.queue_waits(reqs), 95),
            "queue_wait_max_ms": 1e3 * max(stats.queue_waits(reqs)),
            "decode_steps": served.decode_steps,
            "compiles": served.compiles}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
