"""Device time of the Pallas ``matmul`` kernel per traced call: the three
products of each of the 28 gated MLP sublayers, apart from the norms,
casts, SiLU and residual sums between them."""
from bench import readers

KERNEL = "matmul"


def read(run):
    n = readers.traced_calls(run)
    t = run.trace.kernel_seconds((KERNEL,)) if n else None
    return 1e3 * t / n if t else None
