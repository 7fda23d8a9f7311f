"""Device time of the decode program's executions over their count, from
the profiler trace.  The decode program is the one holding the
``decode_attention`` kernel."""
from bench import readers


def read(run):
    return readers.decode_step_ms(run)
