"""Generic blocked map kernel — materializes mapped ``kokkos.*_parallel``
nests on the Pallas path.

The map_parallelism pass binds a logical league/team/vector nest onto the
backend's declared hierarchy (grid/block/lane here); this kernel executes
the nest body (``fn``, the op's reference semantics) on VMEM blocks.
Equivalent of LAPIS emitting a Kokkos parallel_for whose body is the
scalarized linalg op — here the body is vectorized over the block instead
of scalarized (TPU has no scalar loop level worth using).
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import TPU_HIERARCHY


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def block_map(fn: Callable, args: Sequence[jax.Array], out_shape: tuple,
              out_dtype, *, block: tuple, interpret: bool = False
              ) -> jax.Array:
    """Apply elementwise/row-local ``fn`` over blocks of the iteration
    space.  All args must share the iteration-space shape (guaranteed by
    the linalg-to-loops pass preconditions)."""
    if not out_shape:  # scalar result: no blocking
        return fn(*args)
    block = tuple(min(b, s) for b, s in zip(block, out_shape))
    padded = tuple(_ceil(s, b) * b for s, b in zip(out_shape, block))
    pad_cfg = tuple((0, p - s) for p, s in zip(padded, out_shape))
    padded_args = [jnp.pad(a, pad_cfg) if padded != tuple(out_shape) else a
                   for a in args]
    grid = tuple(p // b for p, b in zip(padded, block))
    nd = len(out_shape)

    def kernel(*refs):
        ins, out = refs[:-1], refs[-1]
        out[...] = fn(*[r[...] for r in ins]).astype(out.dtype)

    def idx_map(*gi):
        return gi

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, idx_map) for _ in padded_args],
        out_specs=pl.BlockSpec(block, idx_map),
        out_shape=jax.ShapeDtypeStruct(padded, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            vmem_limit_bytes=TPU_HIERARCHY.scratch_bytes),
        name="block_map",
        interpret=interpret,
    )(*padded_args)
    if padded != tuple(out_shape):
        out = out[tuple(slice(0, s) for s in out_shape)]
    return out


def block_map_region(region, args: Sequence[jax.Array], out_shape: tuple,
                     out_dtype, *, block: tuple, interpret: bool = False
                     ) -> jax.Array:
    """Execute a whole ``kokkos.fused`` region as ONE blocked kernel.

    The multi-op body interprets the region's sub-op records over each
    VMEM block: block arguments bind to the incoming block refs, every
    sub-op runs its reference semantics on values that stay resident in
    SCRATCH (VMEM) for the life of the block, and only the yielded value
    is written out.  A chain of N fused elementwise ops therefore costs
    one kernel launch and zero HBM round-trips for intermediates —
    versus N launches (with N-1 materialized intermediates) unfused.
    ``map_parallelism`` already charged the region's sub-op count against
    ``scratch_bytes`` when it chose ``block``.
    """
    from repro.core import refs
    return block_map(refs.region_ref(region), args, out_shape, out_dtype,
                     block=block, interpret=interpret)
