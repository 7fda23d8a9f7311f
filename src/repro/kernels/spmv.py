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

The layout conversion is a kernel of its own (``csr_to_ell``): a CSR row
is already contiguous, so each ELL row is one window of the CSR arrays,
cut from a block's stretch of them in VMEM rather than gathered from HBM
one padded slot at a time.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
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
               pad_to: int = 8, max_nnz_row: int = None,
               interpret: Optional[bool] = None) -> EllMatrix:
    """One-time layout conversion (vectorized, no python loop over rows).

    ``max_nnz_row`` makes the call jit-traceable (static ELL width); the
    paper's Table 6.1 carries exactly this statistic per matrix, and no
    row may be longer.  Without it the width is computed eagerly from the
    data.  ``interpret`` runs the window kernel in Pallas interpret mode;
    ``None`` means: when no TPU is present."""
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
    valid = jnp.arange(width)[None, :] < row_len[:, None]
    nnz = values.shape[0]
    if nnz == 0:                          # empty matrix: all-padding ELL
        vals_ell = jnp.zeros((n_rows, width), values.dtype)
        cols_ell = jnp.zeros((n_rows, width), jnp.int32)
        return EllMatrix(vals_ell, cols_ell, valid, n_rows, n_cols, 0.0)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    vals_ell, cols_ell = _ell_windows(indptr, indices, values, width,
                                      interpret=interpret)
    nnz_mean = float(nnz) / max(n_rows, 1)
    return EllMatrix(vals_ell, cols_ell, valid, n_rows, n_cols, nnz_mean)


def _windows_kernel(base_ref, starts_ref, lens_ref, vals_ref, cols_ref,
                    vals_out, cols_out, *, rows: int, width: int,
                    spans: int, last_q: int):
    """One block of ``rows`` ELL rows, one window each.  ``vals_ref`` /
    ``cols_ref`` hold the block's stretch of the CSR arrays as 128-lane
    rows from the 1024-aligned word ``base_ref[b] * 1024``; row r's
    window starts ``starts_ref[r]`` less that many words in, spans at
    most ``spans`` lane rows, and is rotated down to lane 0."""
    lanes = TPU_HIERARCHY.vector_width
    base = base_ref[pl.program_id(0)] * (8 * lanes)
    lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    col = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    slabs = _ceil(width, lanes)

    def window(ref, q, m):
        segs = [pltpu.roll(ref[pl.ds(q + j, 1), :], (lanes - m) % lanes, 1)
                for j in range(spans)]
        head = lane < lanes - m
        out = [jnp.where(head, segs[j], segs[j + 1]) if j + 1 < spans
               else segs[j] for j in range(slabs)]
        return (jnp.concatenate(out, axis=1) if slabs > 1 else out[0]
                )[:, :width]

    def row(r):
        # a row longer than the width (max_nnz_row below the matrix's
        # own) would start past the block's stretch: keep reads inside it
        o = starts_ref[0, r] - base
        q = jnp.minimum(o // lanes, last_q)
        m = o % lanes
        keep = col < lens_ref[0, r]
        vals_out[pl.ds(r, 1), :] = jnp.where(
            keep, window(vals_ref, q, m), 0).astype(vals_out.dtype)
        cols_out[pl.ds(r, 1), :] = jnp.where(
            keep, window(cols_ref, q, m), 0).astype(cols_out.dtype)

    def eight_rows(g, carry):
        for k in range(8):
            row(g * 8 + k)
        return carry

    lax.fori_loop(0, rows // 8, eight_rows, 0)


def _ell_windows(indptr, indices, values, width: int, *,
                 interpret: bool = False):
    """The ELL ``(values, indices)`` planes, masked to each row's length:
    CSR row r is already contiguous, so ELL row r is the window
    ``[indptr[r], indptr[r] + width)`` of each array.  The Pallas kernel
    ``csr_to_ell`` copies each row block's stretch of the CSR arrays into
    VMEM in one piece and cuts one window per row out of it, where a
    scalar gather would read every padded slot from HBM on its own.

    The stretch of a block of ``rows`` rows is at most ``rows * width``
    words past its 1024-aligned start (no row is longer than the width),
    so the arrays are padded at the tail by one stretch: no block reads
    past them."""
    lanes = TPU_HIERARCHY.vector_width
    n_rows = indptr.shape[0] - 1
    nnz = values.shape[0]
    narrow = values.dtype.itemsize < 4    # widened to a word, exactly
    vals = values.astype(jnp.float32 if jnp.issubdtype(
        values.dtype, jnp.floating) else jnp.int32) if narrow else values
    spans = _ceil(lanes - 1 + width, lanes)
    slab_w = _ceil(width, lanes) * lanes
    # double-buffered stretch + output block of both planes in 16 MiB
    rows = max(8, min(1024, 2**20 // (width + slab_w) // 8 * 8))
    n_blocks = _ceil(n_rows, rows)
    last_q = _ceil(8 * lanes - 1 + (rows - 1) * width, lanes)
    stretch = _ceil(last_q + spans, 8) * 8
    starts = jnp.pad(indptr[:-1].astype(jnp.int32),
                     (0, n_blocks * rows - n_rows), constant_values=nnz)
    lens = jnp.pad((indptr[1:] - indptr[:-1]).astype(jnp.int32),
                   (0, n_blocks * rows - n_rows))
    base = starts.reshape(n_blocks, rows)[:, 0] // (8 * lanes)
    lane_rows = _ceil(nnz, 8 * lanes) * 8 + stretch

    def lane_view(a):
        return jnp.pad(a, (0, lane_rows * lanes - nnz)).reshape(
            lane_rows, lanes)

    per_row = pl.BlockSpec((None, 1, rows), lambda b, base_ref: (b, 0, 0),
                           memory_space=pltpu.SMEM)
    # element-offset block: the stretch starts at base_ref[b] * 8 lane
    # rows, a multiple of the 8-sublane tile
    window = pl.BlockSpec((pl.Element(stretch), pl.Element(lanes)),
                          lambda b, base_ref: (base_ref[b] * 8, 0))
    out = pl.BlockSpec((rows, width), lambda b, base_ref: (b, 0))
    ell_vals, ell_cols = pl.pallas_call(
        functools.partial(_windows_kernel, rows=rows, width=width,
                          spans=spans, last_q=last_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_blocks,),
            in_specs=[per_row, per_row, window, window],
            out_specs=[out, out]),
        out_shape=[jax.ShapeDtypeStruct((n_rows, width), vals.dtype),
                   jax.ShapeDtypeStruct((n_rows, width), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=TPU_HIERARCHY.scratch_bytes),
        name="csr_to_ell",
        interpret=interpret,
    )(base, starts.reshape(n_blocks, 1, rows),
      lens.reshape(n_blocks, 1, rows), lane_view(vals),
      lane_view(indices.astype(jnp.int32)))
    return (ell_vals.astype(values.dtype) if narrow else ell_vals), ell_cols


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


def as_ell(a, max_nnz_row: int = None,
           interpret: Optional[bool] = None) -> EllMatrix:
    """Composite sparse value → ELL layout (identity if already ELL).
    ``a`` is a :class:`CsrMatrix` or :class:`EllMatrix` — what the
    ``sparse.pack`` / ``sparse.convert`` ops produce at runtime."""
    if isinstance(a, EllMatrix):
        return a
    return csr_to_ell(a.indptr, a.indices, a.values, a.n_rows, a.n_cols,
                      max_nnz_row=max_nnz_row, interpret=interpret)


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
                     max_nnz_row=max_nnz_row, interpret=interpret)
    return spmv_ell(ell, x, row_block=row_block, row_width=row_width,
                    interpret=interpret)
