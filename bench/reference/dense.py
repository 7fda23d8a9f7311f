"""Dense inputs and the plain reference of a gated MLP block.  Imports
nothing of the program.

``(silu(x @ gate) * (x @ up)) @ down``.  The inputs are made on the
device from the seed in one jitted call, in the served dtype.  The
reference computes in float64 on the host from the operands as served;
the control rounds every operand of the three products (the gated
activations included) to float8 e4m3 with a scale per row or column,
the precision below the bfloat16 operands that the program takes.  Both
run in blocks of rows, so that the host holds one block's activations.
"""
from __future__ import annotations

import numpy as np

F8_MAX = 448.0
ROWS = 2048


def swiglu_inputs(t: int, d: int, f: int, dtype: str, seed: int) -> tuple:
    """x (t, d) standard normal; gate, up (d, f) and down (f, d) normal
    with variance 1 / fan-in; all in ``dtype``, on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        k = jax.random.split(key, 4)
        n = lambda i, shape: jax.random.normal(  # noqa: E731
            k[i], shape, jnp.float32) * shape[0] ** -0.5
        x = jax.random.normal(k[0], (t, d), jnp.float32)
        return tuple(a.astype(dtype) for a in (x, n(1, (d, f)), n(2, (d, f)),
                                               n(3, (f, d))))

    return make(jax.random.key(seed))


def _silu(g):
    return g / (1.0 + np.exp(-g))


def swiglu_reference(x, gate, up, down) -> np.ndarray:
    f = lambda a: np.asarray(a).astype(np.float64)  # noqa: E731
    x, gate, up, down = np.asarray(x), f(gate), f(up), f(down)
    out = []
    for i in range(0, len(x), ROWS):
        xb = x[i:i + ROWS].astype(np.float64)
        out.append((_silu(xb @ gate) * (xb @ up)) @ down)
    return np.concatenate(out)


def _f8(a: np.ndarray, axis: int) -> np.ndarray:
    import ml_dtypes
    a = np.asarray(a).astype(np.float32)
    s = np.max(np.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = np.where(s == 0, 1.0, s).astype(np.float32)
    return (a / s).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * s


def swiglu_fp8(x, gate, up, down) -> np.ndarray:
    """The control: float8 e4m3 operands, float32 sums."""
    x = np.asarray(x)
    gate, up, down = _f8(gate, 0), _f8(up, 0), _f8(down, 0)
    out = []
    for i in range(0, len(x), ROWS):
        xb = _f8(x[i:i + ROWS], 1)
        h = _silu(xb @ gate) * (xb @ up)
        out.append(_f8(h, 1) @ down)
    return np.concatenate(out)
