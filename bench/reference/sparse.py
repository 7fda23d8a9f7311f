"""Sparse inputs and the plain SpMV reference.  Imports nothing of the
program.

:func:`banded_csr` makes a CSR matrix with a SuiteSparse matrix's row
count and row-length statistics (mean and maximum nonzeros per row),
columns in a band around the diagonal as in a finite-element matrix.
The reference is a float64 row sum on the host; the control is the same
product with the values and ``x`` rounded to bfloat16, summed in
float32, the precision below the float32 that the program computes in.
"""
from __future__ import annotations

import numpy as np


def banded_csr(rows: int, nnz_mean: float, nnz_max: int, band: int,
               seed: int, sizes_seed: int = 0):
    """(indptr, indices, values): Poisson row lengths of mean
    ``nnz_mean`` (at least 1), one row of exactly ``nnz_max``, columns
    within ``band`` of the diagonal (wrapping), float32 values.  The
    multiset of row lengths is fixed by ``sizes_seed``, so every seed
    gives a matrix of the same shape (one compiled program) and the
    same work; ``seed`` orders the rows and draws columns and values."""
    lens = np.random.default_rng(sizes_seed).poisson(
        nnz_mean - 1.0, rows).astype(np.int32) + 1
    lens[0] = nnz_max
    lens = np.minimum(lens, nnz_max)
    rng = np.random.default_rng(seed)
    lens = lens[rng.permutation(rows)]
    indptr = np.zeros(rows + 1, np.int32)
    np.cumsum(lens, out=indptr[1:])
    nnz = int(indptr[-1])
    row_of = np.repeat(np.arange(rows, dtype=np.int64), lens)
    off = rng.integers(-band, band + 1, nnz)
    indices = ((row_of + off) % rows).astype(np.int32)
    values = rng.standard_normal(nnz, dtype=np.float32)
    return indptr, indices, values


def _row_sums(indptr, prod):
    out = np.zeros(len(indptr) - 1, prod.dtype)
    nonempty = np.diff(indptr) > 0
    out[nonempty] = np.add.reduceat(prod, indptr[:-1][nonempty])
    return out


def spmv_reference(indptr, indices, values, x) -> np.ndarray:
    """y = A x in float64."""
    prod = values.astype(np.float64) * x.astype(np.float64)[indices]
    return _row_sums(indptr, prod)


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    r = ((u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000).astype(np.uint32)
    return r.view(np.float32)


def spmv_bf16(indptr, indices, values, x) -> np.ndarray:
    """The control: bfloat16 operands, float32 products and sums."""
    prod = _bf16(values) * _bf16(x)[indices]
    return _row_sums(indptr, prod.astype(np.float32))
