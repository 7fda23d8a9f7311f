"""The gated MLP sublayers of a Qwen2-style decoder, one a layer for the
configuration's ``num_hidden_layers``: each takes the residual stream
``x`` to ``x + (silu(h @ gate) * (h @ up)) @ down`` with
``h = rmsnorm(x) * norm`` (the layer's post-attention norm), at the
configuration's ``hidden_size``, ``intermediate_size`` and
``rms_norm_eps`` over the mix's ``tokens`` rows, every layer with its
own weights, operands and the stream in the configuration's
``torch_dtype``."""
import functools

import numpy as np

from bench import work
from bench.compiler import Program
from bench.reference import dense

CHECKED_ROWS = 256      # answer rows the check compares, drawn from the seed


def make(cfg: dict, mix: dict, seed: int) -> Program:
    from repro.core import ops
    t, d, f = mix["tokens"], cfg["hidden_size"], cfg["intermediate_size"]
    layers, eps = cfg["num_hidden_layers"], cfg["rms_norm_eps"]
    dtype = cfg["torch_dtype"]

    def f32(a):
        return ops.cast(a, "float32")

    def fn(x, *weights):
        # the norm, the gate and the residual sum in float32: the
        # pipeline's fused elementwise blocks do not lower for bfloat16
        # on Mosaic (PERF.md, Open questions)
        for norm, gate, up, down in dense.layers_of(weights):
            xf = f32(x)
            ms = ops.mean(ops.mul(xf, xf), axis=-1, keepdims=True)
            r = ops.rsqrt(ops.add(ms, ops.constant(eps)))
            h = ops.mul(norm, ops.cast(ops.mul(xf, r), dtype))
            g = f32(ops.matmul(h, gate))
            u = f32(ops.matmul(h, up))
            m = ops.matmul(ops.cast(ops.mul(ops.silu(g), u), dtype), down)
            x = ops.cast(ops.add(xf, f32(m)), dtype)
        return x

    rows = np.sort(np.random.default_rng([seed, 5]).choice(
        t, min(CHECKED_ROWS, t), replace=False))
    flops, bytes_ = work.swiglu_mlp_work(t, d, f, layers, dtype)
    return Program(
        args=dense.swiglu_inputs(t, d, f, layers, dtype, seed), fn=fn,
        reference=functools.partial(dense.swiglu_reference, rows=rows,
                                    eps=eps),
        control=functools.partial(dense.swiglu_fp8, rows=rows, eps=eps),
        flops=flops, bytes=bytes_, rows=rows)
