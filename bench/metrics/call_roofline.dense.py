"""The generated program's share of its roofline, in %: the least time
of the 28 gated MLP sublayers' products at the peaks (compute-bound:
``bench.work.swiglu_mlp_work``), over the device's busy time per traced
call, whatever implements it.  The arithmetic is the sparse cell's
reader's, loaded from beside this file."""
import pathlib

from bench import common

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(run):
    return common.metric_reader("call_roofline.sparse", ROOT)(run)
