"""The whole call's share of the chip's bf16 peak, in %: the FLOPs of
the 28 gated MLP sublayers' products (``bench.work.swiglu_mlp_work``)
at the peak, over the traced window per traced call, idle time
included.  It bounds every kernel's share: a kernel taken off the path
leaves its own metric silent, not this one."""
from bench import readers


def read(run):
    n = readers.traced_calls(run)
    if not n or run.trace.window_s <= 0:
        return None
    least = run.setup.program.flops / run.peak["bf16_flops_per_s"]
    return 100.0 * least / (run.trace.window_s / n)
