"""CSR SpMV, ``ops.spmv_csr``, on a banded matrix with a SuiteSparse
matrix's row count and row-length statistics (the mix's ``rows``,
``nnz_mean``, ``nnz_max``, ``band``), float32.  A static ``max_nnz_row``
lets the pipeline convert to ELL and run the ``spmv_ell`` kernel."""
import numpy as np

from bench import work
from bench.compiler import Program
from bench.reference import sparse


def make(cfg: dict, mix: dict, seed: int) -> Program:
    from repro.core import ops
    n = mix["rows"]
    indptr, indices, values = sparse.banded_csr(
        n, mix["nnz_mean"], mix["nnz_max"], mix["band"], seed,
        mix.get("sizes_seed", 0))
    x = np.random.default_rng([seed, 1]).standard_normal(
        n, dtype=np.float32)
    max_nnz_row = int(np.max(np.diff(indptr)))
    nnz = int(indptr[-1])

    def fn(ip, ind, val, xv):
        return ops.spmv_csr(ip, ind, val, xv, n_rows=n,
                            max_nnz_row=max_nnz_row)

    return Program(args=(indptr, indices, values, x), fn=fn,
                   reference=sparse.spmv_reference,
                   control=sparse.spmv_bf16,
                   flops=work.spmv_csr_flops(nnz),
                   bytes=work.spmv_csr_bytes(n, n, nnz, mix["dtype"]))
