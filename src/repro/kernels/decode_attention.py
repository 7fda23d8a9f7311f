"""Single-token decode attention kernel (the serve_step hot loop).

Decode is pure cache streaming: one query token per sequence reads the
whole (B, Hkv, S, hd) KV cache.  Grid = (B·Hkv, S/bs): each program
handles one (batch row, kv head) pair; the GQA head group (rep = Hq/Hkv)
rides the sublane axis so the q·K product is a (rep, bs) MXU matmul per
block.  Running (m, l, acc) online-softmax state lives in VMEM scratch
across the KV sweep; ``lengths`` masks the valid prefix per row.  The
per-row lengths ride as a scalar-prefetch operand in SMEM (as the page
table does in ``paged_kv.py``): a ``(1, 1)`` VMEM block of a
``(B·Hkv, 1)`` array is not a tile Mosaic accepts.

This is the kernel the decode_32k / long_500k cells would run on TPU —
the XLA library path (ref.decode_attention) remains the CPU/dry-run
lowering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, bs: int, kv_steps: int, scale: float,
                   window: Optional[int]):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]

    # skip KV blocks wholly past this row's valid prefix: with ragged
    # per-row lengths (continuous batching / paged slots) short rows
    # would otherwise burn the full sweep on all-masked blocks — and an
    # all-invalid row (length 0) now correctly leaves l at 0
    @pl.when(ki * bs < length)
    def _update():
        q = q_ref[0].astype(jnp.float32)             # (rep, D)
        k = k_ref[0].astype(jnp.float32)             # (bs, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < length
        if window is not None:
            valid &= pos >= length - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] /
                    jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, window: Optional[int] = None,
                     scale: Optional[float] = None, bs: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); caches: (B, Hkv, S, hd); lengths: (B,) → (B, Hq, D)."""
    B, Hq, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bs = min(bs, S)
    ps = _ceil(S, bs) * bs
    if ps != S:
        pad = ((0, 0), (0, 0), (0, ps - S), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    qr = q.reshape(B, Hkv, rep, D).reshape(B * Hkv, rep, D)
    kr = k_cache.reshape(B * Hkv, ps, D)
    vr = v_cache.reshape(B * Hkv, ps, D)
    len_r = jnp.repeat(lengths.astype(jnp.int32), Hkv)
    grid = (B * Hkv, ps // bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rep, D), lambda h, j, len_ref: (h, 0, 0)),
            pl.BlockSpec((1, bs, D), lambda h, j, len_ref: (h, j, 0)),
            pl.BlockSpec((1, bs, D), lambda h, j, len_ref: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep, D), lambda h, j, len_ref: (h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rep, 1), jnp.float32),
                        pltpu.VMEM((rep, 1), jnp.float32),
                        pltpu.VMEM((rep, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, kv_steps=grid[1],
                          scale=scale, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, rep, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attention",
        interpret=interpret,
    )(len_r, qr, kr, vr)
    return out.reshape(B, Hq, D)
