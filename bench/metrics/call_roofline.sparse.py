"""The generated program's share of its roofline, in %: the least time
of the problem's own work at the peaks (``bench.work``: for SpMV the
CSR, x and y bytes; for the MLP its FLOPs against its bytes), over the
device's busy time per traced call, whatever implements it."""
from bench import readers, work


def read(run):
    n = readers.traced_calls(run)
    if not n or run.trace.busy_s <= 0:
        return None
    prog = run.setup.program
    least = work.least_seconds(prog.flops, prog.bytes, run.peak)
    return 100.0 * least / (run.trace.busy_s / n)
