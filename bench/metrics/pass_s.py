"""Host seconds the compiler's passes took, summed from
``graph.pass_stats`` of the compiled module."""


def read(run):
    return run.setup.pass_s
