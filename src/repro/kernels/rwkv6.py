"""RWKV6 (Finch) WKV scan kernel — data-dependent decay linear attention.

TPU adaptation of the recurrence (DESIGN.md §5): time stays **sequential**
(an ``arbitrary`` grid axis revisiting the state scratch), the channel dims
(K, V) are the vectorized lane/sublane axes — the paper's rule that the
innermost level vectorizes.  The per-head state S ∈ (K, V) lives in VMEM
scratch across the whole time sweep; r/k/v/w stream through VMEM in time
chunks.

    y_t = r_t · (S + diag(u) k_t v_tᵀ)
    S  ← diag(w_t) S + k_t v_tᵀ
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)                     # (1, K)

    def step(t, _):
        rt = r_ref[0, t].astype(jnp.float32)[None, :]    # (1, K)
        kt = k_ref[0, t].astype(jnp.float32)[None, :]
        vt = v_ref[0, t].astype(jnp.float32)[None, :]    # (1, V)
        wt = w_ref[0, t].astype(jnp.float32)[None, :]
        s = s_ref[...]                                   # (K, V)
        kv = kt.T * vt                                   # (K, V)
        y = jnp.dot(rt, s + u.T * kv,
                    preferred_element_type=jnp.float32)  # (1, V)
        o_ref[0, t] = y[0].astype(o_ref.dtype)
        s_ref[...] = wt.T * s + kv
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, *, chunk: int = 128,
               interpret: bool = False) -> jax.Array:
    """r, k, w: (B, T, H, K); v: (B, T, H, V); u: (H, K) → y: (B, T, H, V).

    (The zero-initial-state training form; decode-time stateful stepping
    uses the pure-jnp cell in models/rwkv.py where T == 1.)
    """
    B, T, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, T)
    pt = _ceil(T, chunk) * chunk

    def prep(x):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, T, x.shape[-1])
        if pt != T:
            x = jnp.pad(x, ((0, 0), (0, pt - T), (0, 0)))
        return x

    rp, kp, vp, wp = prep(r), prep(k), prep(v), prep(w)
    # pad w with ones in the tail so padded steps keep the state unchanged
    if pt != T:
        wp = wp.at[:, T:, :].set(1.0)
    u_full = jnp.broadcast_to(u[None, :, :], (B, H, K)).reshape(B * H, 1, K)
    grid = (B * H, pt // chunk)
    out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, chunk, K), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, chunk, V), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, chunk, K), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, 1, K), lambda h, t: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, V), lambda h, t: (h, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, pt, V), v.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="rwkv6_scan",
        interpret=interpret,
    )(rp, kp, vp, wp, u_full)
    out = out[:, :T, :].reshape(B, H, T, V).transpose(0, 2, 1, 3)
    return out
