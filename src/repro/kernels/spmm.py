"""CSR SpMM (Y = A @ B, B dense) — the multi-vector companion of the
paper's flagship SpMV kernel (§6.2), TPU-adapted.

Same layout strategy as ``kernels/spmv.py``: the CSR matrix is converted
to padded ELL so the per-row entry loop is a *regular* axis.  Where SpMV
gathers a vector (one scalar per stored entry), SpMM gathers whole rows of
``B`` — the gathered operand is (rows, width, n) and the kernel contracts
the width axis on (row-block × n-block) output tiles, revisiting each tile
once per width slab (``arbitrary`` grid semantics, like the SpMV
accumulator).  The B-row gather stays in XLA (native TPU gather), so the
kernel proper is the dense multiply+reduce the MXU/VPU runs at full tilt.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import TPU_HIERARCHY
from repro.kernels.spmv import EllMatrix, _ceil, as_ell, lane_block


def _spmm_kernel(vals_ref, bg_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    partial = jnp.sum(
        vals_ref[...].astype(jnp.float32)[:, :, None] * bg_ref[...], axis=1)
    o_ref[...] += partial.astype(o_ref.dtype)


def spmm_ell(ell: EllMatrix, b: jax.Array, *, row_block: int = 128,
             row_width: int = 128, col_block: int = 128,
             interpret: bool = False) -> jax.Array:
    """Y = A @ B from the padded ELL layout; B: (n_cols, n)."""
    n_rows, width = ell.values.shape
    n = int(b.shape[1])
    if n_rows == 0 or n == 0:
        return jnp.zeros((n_rows, n), b.dtype)
    # gather B rows per stored entry: (n_rows, width, n), zero where padded
    b_g = jnp.where(ell.valid[:, :, None], b[ell.indices], 0.0) \
        .astype(jnp.float32)
    rb = min(row_block, max(n_rows, 1))
    rw = lane_block(row_width, width)
    cb = min(col_block, n)
    pr = _ceil(n_rows, rb) * rb
    pw = _ceil(width, rw) * rw
    pn = _ceil(n, cb) * cb
    vals = ell.values
    if (pr, pw) != (n_rows, width):
        vals = jnp.pad(vals, ((0, pr - n_rows), (0, pw - width)))
    if (pr, pw, pn) != b_g.shape:
        b_g = jnp.pad(b_g, ((0, pr - n_rows), (0, pw - width),
                            (0, pn - n)))
    grid = (pr // rb, pn // cb, pw // rw)
    out = pl.pallas_call(
        _spmm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rb, rw), lambda i, j, s: (i, s)),
                  pl.BlockSpec((rb, rw, cb), lambda i, j, s: (i, s, j))],
        out_specs=pl.BlockSpec((rb, cb), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pr, pn), b.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=TPU_HIERARCHY.scratch_bytes),
        name="spmm_ell",
        interpret=interpret,
    )(vals, b_g)
    return out[:n_rows, :n]


def spmm_sparse(a, b, *, row_block: int = 128, row_width: int = 128,
                max_nnz_row: int = None, interpret: bool = False):
    """Packed-operand entry point (CsrMatrix or EllMatrix)."""
    ell = as_ell(a, max_nnz_row=max_nnz_row)
    return spmm_ell(ell, b, row_block=row_block, row_width=row_width,
                    interpret=interpret)
