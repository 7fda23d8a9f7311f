"""CSR SpMV — the paper's flagship sparse kernel (§6.2), TPU-adapted.

Paper (GPU): row-parallel TeamPolicy with a ThreadVector inner loop over the
row's entries; vector length = ceil(avg nnz/row) clamped to warp width.

TPU has no warps — the adaptation (DESIGN.md §8.6): convert CSR to a padded
ELL layout whose **row width is the lane axis** and block rows into VMEM
tiles.  The paper's vector-length heuristic becomes ``row_width`` — the
column-tile width each grid step covers — clamped to a multiple of the
128-lane unit instead of warp 32.  The `x[cols]` gather stays in XLA (TPU
has native gather support; Pallas-side HBM gather does not map to the
hardware), so the kernel proper is the multiply+row-reduce over regular
tiles — exactly the part the MXU/VPU can run at full tilt.

Grid = (row_blocks, width_slabs); slabs revisit the output block and
accumulate (``arbitrary`` semantics), mirroring the paper's sequential
vector loop when a row is longer than the vector length.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import TPU_HIERARCHY
from repro.core.ir import ell_storage_width


class CsrMatrix(NamedTuple):
    """Runtime composite CSR value (what a sparse-encoded IR value holds
    between ``sparse.pack`` and the consuming kernel)."""
    indptr: jax.Array     # (n_rows + 1,)
    indices: jax.Array    # (nnz,) column ids
    values: jax.Array     # (nnz,)
    n_rows: int
    n_cols: int


class EllMatrix(NamedTuple):
    """Padded ELL form of a CSR matrix (built once, reusable)."""
    values: jax.Array     # (n_rows, width)
    indices: jax.Array    # (n_rows, width) column ids (0 where padded)
    valid: jax.Array      # (n_rows, width) bool
    n_rows: int
    n_cols: int
    nnz_mean: float


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def lane_block(row_width: int, width: int) -> int:
    """The ELL column-slab width a kernel block may take.  Mosaic needs a
    block's last dim to be a multiple of the 128-lane tile or the whole
    array dim, so the tiling's ``row_width`` (the paper's vector length,
    often 8 or 16) is rounded up to whole lanes, and a slab that would
    cover the full ELL width becomes exactly that width."""
    lanes = TPU_HIERARCHY.vector_width
    rw = _ceil(max(row_width, 1), lanes) * lanes
    return width if rw >= width else rw


def csr_to_ell(indptr, indices, values, n_rows: int, n_cols: int,
               pad_to: int = 8, max_nnz_row: int = None) -> EllMatrix:
    """One-time layout conversion (vectorized, no python loop over rows).

    ``max_nnz_row`` makes the call jit-traceable (static ELL width); the
    paper's Table 6.1 carries exactly this statistic per matrix.  Without
    it the width is computed eagerly from the data."""
    indptr = jnp.asarray(indptr)
    indices = jnp.asarray(indices)
    values = jnp.asarray(values)
    if n_rows == 0:
        # degenerate matrix: indptr is the single sentinel 0, so the row
        # windows below would index indptr[:-1] into an undefined width —
        # return a well-formed all-padding ELL instead
        width = ell_storage_width(max_nnz_row, pad_to)
        return EllMatrix(jnp.zeros((0, width), values.dtype),
                         jnp.zeros((0, width), jnp.int32),
                         jnp.zeros((0, width), bool), 0, n_cols, 0.0)
    row_len = indptr[1:] - indptr[:-1]
    if max_nnz_row is None:
        max_nnz_row = int(jnp.max(row_len))
    width = ell_storage_width(max_nnz_row, pad_to)
    offs = jnp.arange(width)[None, :]
    idx = indptr[:-1, None] + offs
    valid = offs < row_len[:, None]
    nnz = values.shape[0]
    if nnz == 0:                          # empty matrix: all-padding ELL
        vals_ell = jnp.zeros((n_rows, width), values.dtype)
        cols_ell = jnp.zeros((n_rows, width), jnp.int32)
        return EllMatrix(vals_ell, cols_ell, valid, n_rows, n_cols, 0.0)
    idx = jnp.clip(idx, 0, nnz - 1)
    vals_ell = jnp.where(valid, values[idx], 0).astype(values.dtype)
    cols_ell = jnp.where(valid, indices[idx], 0).astype(jnp.int32)
    nnz_mean = float(nnz) / max(n_rows, 1)
    return EllMatrix(vals_ell, cols_ell, valid, n_rows, n_cols, nnz_mean)


def _spmv_kernel(vals_ref, xg_ref, o_ref, *, slabs: int):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    partial = jnp.sum(vals_ref[...].astype(jnp.float32) * xg_ref[...],
                      axis=1, keepdims=True)
    o_ref[...] += partial.astype(o_ref.dtype)


def spmv_ell(ell: EllMatrix, x: jax.Array, *, row_block: int = 256,
             row_width: int = 128, interpret: bool = False) -> jax.Array:
    """y = A @ x from the padded ELL layout."""
    n_rows, width = ell.values.shape
    if n_rows == 0:
        return jnp.zeros((0,), x.dtype)   # no rows: never launch a 0-grid
    x_g = jnp.where(ell.valid, x[ell.indices], 0.0).astype(jnp.float32)
    rb = min(row_block, max(n_rows, 1))
    rw = lane_block(row_width, width)
    pr = _ceil(n_rows, rb) * rb
    pw = _ceil(width, rw) * rw
    vals = ell.values
    if (pr, pw) != (n_rows, width):
        vals = jnp.pad(vals, ((0, pr - n_rows), (0, pw - width)))
        x_g = jnp.pad(x_g, ((0, pr - n_rows), (0, pw - width)))
    grid = (pr // rb, pw // rw)
    out = pl.pallas_call(
        functools.partial(_spmv_kernel, slabs=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec((rb, rw), lambda i, s: (i, s)),
                  pl.BlockSpec((rb, rw), lambda i, s: (i, s))],
        out_specs=pl.BlockSpec((rb, 1), lambda i, s: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pr, 1), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=TPU_HIERARCHY.scratch_bytes),
        name="spmv_ell",
        interpret=interpret,
    )(vals, x_g)
    return out[:n_rows, 0]


def as_ell(a, max_nnz_row: int = None) -> EllMatrix:
    """Composite sparse value → ELL layout (identity if already ELL).
    ``a`` is a :class:`CsrMatrix` or :class:`EllMatrix` — what the
    ``sparse.pack`` / ``sparse.convert`` ops produce at runtime."""
    if isinstance(a, EllMatrix):
        return a
    return csr_to_ell(a.indptr, a.indices, a.values, a.n_rows, a.n_cols,
                      max_nnz_row=max_nnz_row)


def spmv_reference(a, x):
    """Library-semantics SpMV on either layout of the composite value —
    the single implementation behind the xla kernel-table entry and the
    emitter's reference fallback (keep them from diverging)."""
    from repro.kernels import ref
    if isinstance(a, EllMatrix):
        x_g = jnp.where(a.valid, x[a.indices], 0.0)
        return jnp.sum(a.values * x_g, axis=1).astype(x.dtype)
    return ref.spmv_csr(a.indptr, a.indices, a.values, x, n_rows=a.n_rows)


def spmm_reference(a, b):
    """Library-semantics SpMM on either layout of the composite value."""
    from repro.kernels import ref
    if isinstance(a, EllMatrix):
        b_g = jnp.where(a.valid[:, :, None], b[a.indices], 0.0)
        return jnp.sum(a.values[:, :, None] * b_g, axis=1).astype(b.dtype)
    return ref.spmm_csr(a.indptr, a.indices, a.values, b, n_rows=a.n_rows)


def spmv_csr(indptr, indices, values, x, *, n_rows: int,
             row_block: int = 256, row_width: int = 128,
             max_nnz_row: int = None, interpret: bool = False) -> jax.Array:
    """CSR entry point: layout-convert then run the ELL kernel.  For
    repeated products with the same sparsity, build the EllMatrix once and
    call ``spmv_ell`` (what the benchmark does).  Pass ``max_nnz_row`` when
    calling under jit (static ELL width)."""
    ell = csr_to_ell(indptr, indices, values, n_rows, int(x.shape[0]),
                     max_nnz_row=max_nnz_row)
    return spmv_ell(ell, x, row_block=row_block, row_width=row_width,
                    interpret=interpret)
