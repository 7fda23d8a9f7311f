"""Device time of the ``spmv_ell`` kernel per traced call: the kernel
apart from the in-program CSR to ELL conversion."""
from bench import readers


def read(run):
    n = readers.traced_calls(run)
    t = run.trace.kernel_seconds(("spmv_ell",)) if n else None
    return 1e3 * t / n if t else None
