"""1 - device busy time over the traced window, in %: the host's share
of the dense cell's calls (dispatch, the wait on the answer).  Busy
time is the union of device operation intervals in the trace."""
from bench import readers


def read(run):
    return readers.idle_share(run)
