"""The whole decode step's share of the chip's peak, in %: the least
time of the step's required work (weights read once, each live
request's K/V read once, new K/V and logits written; FLOPs 2 per
parameter per token plus attention over the live context) at the
peaks, over the step's measured device time."""
from bench import readers


def read(run):
    return readers.decode_mfu(run)
