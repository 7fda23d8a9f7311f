"""Dense inputs and the plain reference of a stack of gated MLP
sublayers, as a Qwen2-style decoder has one in each layer.  Imports
nothing of the program.

Each layer ``l`` takes the residual stream ``x`` to
``x + (silu(h @ gate_l) * (h @ up_l)) @ down_l`` with
``h = rmsnorm(x) * norm_l``.  The inputs are made on the device from the
seed in one jitted call, in the served dtype.  The reference computes
in float64 on the host from the operands as served, over the answer
rows the check samples (rows are independent here), one layer's weights
at a time; the control rounds every operand of the three products of
each layer (the normed and the gated activations included) to float8
e4m3 with a scale per row or column, the precision below the bfloat16
operands that the program takes.
"""
from __future__ import annotations

import numpy as np

F8_MAX = 448.0
PER_LAYER = 4                  # norm, gate, up, down


def swiglu_inputs(t: int, d: int, f: int, layers: int, dtype: str,
                  seed: int) -> tuple:
    """x (t, d) standard normal, then per layer the norm's scale (d,)
    N(1, 0.1) and gate, up (d, f) and down (f, d) normal with variance
    1 / fan-in; all in ``dtype``, on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        k = jax.random.split(key, 1 + PER_LAYER * layers)
        n = lambda i, shape: jax.random.normal(  # noqa: E731
            k[i], shape, jnp.float32) * shape[0] ** -0.5
        out = [jax.random.normal(k[0], (t, d), jnp.float32)]
        for i in range(1, len(k), PER_LAYER):
            out += [1.0 + 0.1 * jax.random.normal(k[i], (d,), jnp.float32),
                    n(i + 1, (d, f)), n(i + 2, (d, f)), n(i + 3, (f, d))]
        return tuple(a.astype(dtype) for a in out)

    return make(jax.random.key(seed))


def layers_of(weights) -> list:
    """The flat ``(norm, gate, up, down, norm, ...)`` as one tuple a
    layer."""
    return [weights[i:i + PER_LAYER]
            for i in range(0, len(weights), PER_LAYER)]


def _silu(g):
    return g / (1.0 + np.exp(-g))


def _rmsnorm(x, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps)


def swiglu_reference(x, *weights, rows, eps) -> np.ndarray:
    """The stack over ``x[rows]``, in float64."""
    f = lambda a: np.asarray(a).astype(np.float64)  # noqa: E731
    x = np.asarray(x)[rows].astype(np.float64)
    for norm, gate, up, down in layers_of(weights):
        h = _rmsnorm(x, eps) * f(norm)
        x = x + (_silu(h @ f(gate)) * (h @ f(up))) @ f(down)
    return x


def _f8(a: np.ndarray, axis: int) -> np.ndarray:
    import ml_dtypes
    a = np.asarray(a).astype(np.float32)
    s = np.max(np.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = np.where(s == 0, 1.0, s).astype(np.float32)
    return (a / s).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * s


def swiglu_fp8(x, *weights, rows, eps) -> np.ndarray:
    """The control over ``x[rows]``: float8 e4m3 operands of every
    product, float32 sums, the norm and the residual in float32."""
    f = lambda a: np.asarray(a).astype(np.float32)  # noqa: E731
    x = np.asarray(x)[rows].astype(np.float32)
    for norm, gate, up, down in layers_of(weights):
        h = _f8(_rmsnorm(x, eps) * f(norm), 1)
        g = _silu(h @ _f8(gate, 0)) * (h @ _f8(up, 0))
        x = x + _f8(g, 1) @ _f8(down, 0)
    return x
