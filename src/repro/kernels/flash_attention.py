"""Flash attention (online-softmax) Pallas kernel.

Grid = (batch·q_heads, Sq/bq, Skv/bkv); the KV axis is an ``arbitrary``
revisiting dimension carrying the running max/sum/accumulator in VMEM
scratch.  Causal and sliding-window masks skip fully-masked KV blocks via
``pl.when`` (no memory traffic for the skipped triangle — this is the
compute-side analogue of the paper's "don't let threads idle" vector-length
clamp).  GQA is handled by the index map: q head h reads kv head
h // group_size, so KV blocks are never materialized per-q-head.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: Optional[int],
                 logit_softcap: Optional[float],
                 bq: int, bkv: int, kv_steps: int, seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bkv

    # is any (q, k) pair in this block pair unmasked?  (data-independent —
    # the causal triangle / window band is known from block coordinates)
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + bkv - 1 > q_start - window)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)             # (bq, d)
        k = k_ref[0].astype(jnp.float32)             # (bkv, d)
        v = v_ref[0].astype(jnp.float32)             # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if logit_softcap:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                          # (bq, 1)
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


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    logit_softcap: Optional[float] = None, bq: int = 256,
                    bkv: int = 512, interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) — GQA via index-map
    sharing; rectangular Sq ≠ Skv supported (cross-attention)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    ps = _ceil(Sq, bq) * bq
    pk = _ceil(Skv, bkv) * bkv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, ps - Sq), (0, 0))) if ps != Sq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk - Skv), (0, 0))) if pk != Skv \
        else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk - Skv), (0, 0))) if pk != Skv \
        else v
    qp = qp.reshape(B * Hq, ps, D)
    kp = kp.reshape(B * Hkv, pk, D)
    vp = vp.reshape(B * Hkv, pk, D)
    grid = (B * Hq, ps // bq, pk // bkv)

    def q_map(h, i, j):
        return (h, i, 0)

    def kv_map(h, i, j, *, _g=group):
        return (h // _g, j, 0)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          window=window, logit_softcap=logit_softcap,
                          bq=bq, bkv=bkv, kv_steps=grid[2],
                          seq_len=Skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bkv, D), kv_map),
            pl.BlockSpec((1, bkv, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, ps, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(qp, kp, vp)
    return out.reshape(B, Hq, ps, D)[:, :, :Sq, :]
