"""Tiled MXU matmul — the "pure Kokkos lowering" of kk.gemm (paper §6.4).

Pallas grid = (M/bm, N/bn, K/bk); the K axis is an ``arbitrary`` revisiting
dimension accumulating into an f32 VMEM scratch tile (HBM→VMEM→VREG: operand
tiles stream through VMEM, the accumulator lives in VMEM for the whole K
sweep).  Block shapes come from the map_parallelism pass's heuristics
(``choose_matmul_blocks``) — the TeamPolicy team-size/vector-length analogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import TPU_HIERARCHY


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def matmul(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
           bk: int = 512, interpret: bool = False,
           out_dtype=None) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N] with f32 accumulation.

    Shapes need not divide the block sizes — inputs are padded (zeros are
    additive-identity under accumulation) and the output is sliced back.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bm, bn, bk = min(bm, m) or 1, min(bn, n) or 1, min(bk, k) or 1
    pm, pn, pk = _ceil(m, bm) * bm, _ceil(n, bn) * bn, _ceil(k, bk) * bk
    if (pm, pk) != (m, k):
        a = jnp.pad(a, ((0, pm - m), (0, pk - k)))
    if (pk, pn) != (k, n):
        b = jnp.pad(b, ((0, pk - k), (0, pn - n)))
    grid = (pm // bm, pn // bn, pk // bk)
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=TPU_HIERARCHY.scratch_bytes),
        name="matmul",
        interpret=interpret,
    )(a, b)
    return out[:m, :n] if (pm, pn) != (m, n) else out
