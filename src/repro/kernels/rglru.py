"""RG-LRU scan kernel (recurrentgemma / Griffin).

Elementwise gated linear recurrence: channels vectorize onto the 128-lane
axis (grid over channel blocks — fully parallel), time is the sequential
``arbitrary`` axis with the (1, d_block) hidden state held in VMEM scratch.

    a_t = exp(-c · softplus(Λ) · σ(r_t))
    h_t = a_t ⊙ h_{t-1} + sqrt(1 − a_t²) ⊙ (σ(i_t) ⊙ x_t)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


RGLRU_C = 8.0


def _rglru_kernel(x_ref, r_ref, i_ref, la_ref, o_ref, h_ref, *, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    log_a = -RGLRU_C * jax.nn.softplus(la_ref[0].astype(jnp.float32))

    def step(t, _):
        xt = x_ref[0, t].astype(jnp.float32)[None, :]
        rt = jax.nn.sigmoid(r_ref[0, t].astype(jnp.float32))[None, :]
        it = jax.nn.sigmoid(i_ref[0, t].astype(jnp.float32))[None, :]
        la_r = log_a[None, :] * rt
        a_t = jnp.exp(la_r)
        scale = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * la_r), 1e-12))
        h = a_t * h_ref[...] + scale * (it * xt)
        h_ref[...] = h
        o_ref[0, t] = h[0].astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def rglru_scan(x: jax.Array, r_gate: jax.Array, i_gate: jax.Array,
               log_a_param: jax.Array, *, chunk: int = 128,
               d_block: int = 512, interpret: bool = False) -> jax.Array:
    """x, r_gate, i_gate: (B, T, D); log_a_param: (D,) → h: (B, T, D)."""
    B, T, D = x.shape
    chunk = min(chunk, T)
    d_block = min(d_block, D)
    pt = _ceil(T, chunk) * chunk
    pd = _ceil(D, d_block) * d_block

    def prep(a):
        if (pt, pd) != (T, D):
            a = jnp.pad(a, ((0, 0), (0, pt - T), (0, pd - D)))
        return a

    xp, rp, ip = prep(x), prep(r_gate), prep(i_gate)
    lap = jnp.pad(log_a_param, (0, pd - D))[None, :] \
        if pd != D else log_a_param[None, :]
    grid = (B, pd // d_block, pt // chunk)
    out = pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, chunk, d_block), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, chunk, d_block), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, d_block), lambda b, d, t: (0, d)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda b, d, t: (b, t, d)),
        out_shape=jax.ShapeDtypeStruct((B, pt, pd), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, d_block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="rglru_scan",
        interpret=interpret,
    )(xp, rp, ip, lap)
    return out[:, :T, :D]
