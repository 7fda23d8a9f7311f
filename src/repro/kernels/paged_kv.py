"""Block-paged KV-cache kernels (``kokkos.page_gather`` / ``page_append`` / ``page_copy``).

The serving engine keeps each sequence's KV history in fixed-size blocks
drawn from a shared pool; a per-slot page table names the blocks in
order.  ``paged_to_kokkos`` lowers the tensor-level ``paged.*`` ops to
the ``kokkos.*`` dialect and the emitter dispatches them here through the
backend registry, so the paged decode step is compiled IR end to end —
this module is the backend *implementation* of those ops, never the IR's
meaning (that lives in ``repro.core.refs``).

Layouts:

* pool    — ``(n_blocks, Hkv, block_size, hd)``; block 0 is the scrap
            block inactive slots write into (their table rows are all
            zero), so every slot's append is unconditional.
* table   — ``(n_slots, max_blocks)`` int32 block ids.
* lengths — ``(n_slots,)`` int32 valid positions per slot; stale data
            past a slot's length is masked by the consuming decode-
            attention kernel, so gather never needs to zero it.

Three implementations per op, mirroring the rest of the kernel surface:
``xla`` (vendor-library gather/scatter), ``loops`` (explicit serial
league loop over slots — the generated-Kokkos-loops reading of the nest
attrs), and for the gather a hand-written Pallas kernel whose grid walks
(slot, block) and uses the *scalar-prefetched page table* as the pool
index map — the vLLM-style paged-attention gather.  The pallas append
intentionally falls back to the library scatter via the fallback chain
(a one-position scatter is a library strength; a hand kernel would
round-trip the whole pool).

``kokkos.page_copy`` is the block-granular bulk copy behind the engine's
copy-on-write forks and the preemption/swap tier: operands are
``(dst, src, src_ids, dst_ids)`` arenas of rank 4 (one layer) or rank 5
(the engine's L-stacked pools), and block ``src_ids[c]`` of ``src`` is
copied over block ``dst_ids[c]`` of ``dst``.  The ``direction`` attr set
by ``paged_to_kokkos`` records which engine path emitted the op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import register_kernel


# ---------------------------------------------------------------------------
# xla — the vendor-library path
# ---------------------------------------------------------------------------

def page_gather_xla(pool, table, lengths, *, block_size):
    n_slots, blocks_per_slot = table.shape
    g = jnp.take(pool, table.reshape(-1), axis=0)
    g = g.reshape((n_slots, blocks_per_slot) + pool.shape[1:])
    g = jnp.moveaxis(g, 1, 2)
    return g.reshape(n_slots, pool.shape[1],
                     blocks_per_slot * pool.shape[2], pool.shape[3])


def page_append_xla(pool, table, lengths, kv, *, block_size):
    rows = jnp.arange(table.shape[0])
    blk = table[rows, lengths // block_size]
    off = lengths % block_size
    return pool.at[blk, :, off, :].set(kv.astype(pool.dtype))


def page_copy_xla(dst, src, src_ids, dst_ids, *, block_size):
    # block-granular arena copy (CoW fork / swap tier); arenas are rank 4
    # (one layer) or rank 5 (L-stacked engine pools) — block axis ndim-4
    axis = dst.ndim - 4
    taken = jnp.take(src, src_ids, axis=axis).astype(dst.dtype)
    idx = (slice(None),) * axis + (dst_ids,)
    return dst.at[idx].set(taken)


# ---------------------------------------------------------------------------
# loops — explicit league loop over slots (the nest attrs, interpreted)
# ---------------------------------------------------------------------------

def page_gather_loops(pool, table, lengths, *, block_size):
    n_slots, blocks_per_slot = table.shape
    rows = []
    for s in range(n_slots):                 # league loop over slots
        blocks = jnp.take(pool, table[s], axis=0)   # (MB, Hkv, bs, hd)
        rows.append(jnp.moveaxis(blocks, 0, 1).reshape(
            pool.shape[1], blocks_per_slot * pool.shape[2], pool.shape[3]))
    return jnp.stack(rows)


def page_append_loops(pool, table, lengths, kv, *, block_size):
    for s in range(table.shape[0]):          # league loop over slots
        blk = table[s, lengths[s] // block_size]
        off = lengths[s] % block_size
        pool = jax.lax.dynamic_update_slice(
            pool, kv[s][None, :, None, :].astype(pool.dtype),
            (blk, 0, off, 0))
    return pool


def page_copy_loops(dst, src, src_ids, dst_ids, *, block_size):
    axis = dst.ndim - 4
    for c in range(src_ids.shape[0]):        # league loop over copies
        block = jax.lax.dynamic_index_in_dim(
            src, src_ids[c], axis=axis, keepdims=True).astype(dst.dtype)
        start = (jnp.int32(0),) * axis + (dst_ids[c],) + (jnp.int32(0),) * 3
        dst = jax.lax.dynamic_update_slice(dst, block, start)
    return dst


# ---------------------------------------------------------------------------
# pallas — page-table-indexed gather (scalar-prefetched block ids)
# ---------------------------------------------------------------------------

def _gather_kernel(table_ref, pool_ref, o_ref):
    # the index maps did the paging: this program's pool block IS the
    # (slot, block)-th page — copy it into the slot's contiguous view
    o_ref[...] = pool_ref[...]


def page_gather_pallas(pool, table, lengths, *, block_size,
                       interpret=False):
    n_blocks, heads, bs, hd = pool.shape
    n_slots, blocks_per_slot = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_slots, blocks_per_slot),
        in_specs=[
            # the page table rides as a scalar-prefetch operand so the
            # *input index map* can read it: program (s, b) pulls pool
            # block table[s, b] — the paged indirection happens in the
            # block fetch, not in kernel arithmetic
            pl.BlockSpec((1, heads, bs, hd),
                         lambda s, b, table_ref: (table_ref[s, b], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, heads, bs, hd),
                               lambda s, b, table_ref: (s, 0, b, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_slots, heads, blocks_per_slot * bs, hd), pool.dtype),
        name="page_gather",
        interpret=interpret,
    )(table.astype(jnp.int32), pool)


register_kernel("kokkos.page_gather", "xla", page_gather_xla)
register_kernel("kokkos.page_append", "xla", page_append_xla)
register_kernel("kokkos.page_copy", "xla", page_copy_xla)
register_kernel("kokkos.page_gather", "loops", page_gather_loops)
register_kernel("kokkos.page_append", "loops", page_append_loops)
register_kernel("kokkos.page_copy", "loops", page_copy_loops)
register_kernel("kokkos.page_gather", "pallas", page_gather_pallas)
# no pallas page_append or page_copy on purpose: the fallback chain
# routes both to the xla scatter/gather (see module docstring)
