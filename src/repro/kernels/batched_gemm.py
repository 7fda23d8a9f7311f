"""Batched GEMM — paper §6.4 (Fig 6.3).

The paper's point for batched kernels: for small/medium matrices "it is
critical to vectorize on the batch dimension".  On TPU the analogue is to
make **batch** a blocked grid axis and pack several matrices into one VMEM
block so the (8,128) vector unit and MXU stay occupied:

* small matrices (m·n ≤ MXU²/4): block = (batch_block, m, k) — several
  whole matrices per grid step, contracted with a batched dot_general;
* large matrices: fall back to per-matrix MXU tiling (batch_block = 1,
  grid also over M/N/K tiles).

The choice is the map_parallelism heuristic (``vectorize_batch``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import TPU_HIERARCHY


def _small_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        a_ref[...], b_ref[...],
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _tiled_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(3) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def batched_gemm(a: jax.Array, b: jax.Array, *, batch_block: int = 8,
                 vectorize_batch: bool = None, bm: int = 128, bn: int = 128,
                 bk: int = 512, interpret: bool = False) -> jax.Array:
    """C[B,M,N] = A[B,M,K] @ B[B,K,N].  Leading batch dims are flattened."""
    orig_batch = a.shape[:-2]
    m, k = a.shape[-2:]
    n = b.shape[-1]
    a = a.reshape((-1, m, k))
    b = jnp.broadcast_to(b, orig_batch + b.shape[-2:]).reshape((-1, k, n)) \
        if b.ndim != a.ndim or b.shape[0] != a.shape[0] else \
        b.reshape((-1, k, n))
    bsz = a.shape[0]
    if vectorize_batch is None:
        vectorize_batch = m * n <= 128 * 128 // 4
    if vectorize_batch:
        bb = min(batch_block, bsz)
        pb = _ceil(bsz, bb) * bb
        if pb != bsz:
            a = jnp.pad(a, ((0, pb - bsz), (0, 0), (0, 0)))
            b = jnp.pad(b, ((0, pb - bsz), (0, 0), (0, 0)))
        out = pl.pallas_call(
            _small_kernel,
            grid=(pb // bb,),
            in_specs=[pl.BlockSpec((bb, m, k), lambda i: (i, 0, 0)),
                      pl.BlockSpec((bb, k, n), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((bb, m, n), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((pb, m, n), a.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=TPU_HIERARCHY.scratch_bytes),
            name="batched_gemm",
            interpret=interpret,
        )(a, b)
        out = out[:bsz]
    else:
        bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
        pm, pn, pk = (_ceil(m, bm_) * bm_, _ceil(n, bn_) * bn_,
                      _ceil(k, bk_) * bk_)
        if (pm, pk) != (m, k):
            a = jnp.pad(a, ((0, 0), (0, pm - m), (0, pk - k)))
        if (pk, pn) != (k, n):
            b = jnp.pad(b, ((0, 0), (0, pk - k), (0, pn - n)))
        grid = (bsz, pm // bm_, pn // bn_, pk // bk_)
        out = pl.pallas_call(
            functools.partial(_tiled_kernel, k_steps=grid[3]),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm_, bk_), lambda bi, i, j, kk: (bi, i, kk)),
                pl.BlockSpec((1, bk_, bn_), lambda bi, i, j, kk: (bi, kk, j)),
            ],
            out_specs=pl.BlockSpec((1, bm_, bn_),
                                   lambda bi, i, j, kk: (bi, i, j)),
            out_shape=jax.ShapeDtypeStruct((bsz, pm, pn), a.dtype),
            scratch_shapes=[pltpu.VMEM((1, bm_, bn_), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary"),
                vmem_limit_bytes=TPU_HIERARCHY.scratch_bytes),
            name="batched_gemm",
            interpret=interpret,
        )(a, b)
        out = out[:, :m, :n]
    return out.reshape(orig_batch + (m, n))
