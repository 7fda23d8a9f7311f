"""The gated MLP block of a Qwen2-style decoder layer,
``(silu(x @ gate) * (x @ up)) @ down``, at the configuration's
``hidden_size`` and ``intermediate_size`` over the mix's ``tokens`` rows,
operands in the configuration's ``torch_dtype``."""
from bench import work
from bench.compiler import Program
from bench.reference import dense


def make(cfg: dict, mix: dict, seed: int) -> Program:
    from repro.core import ops
    t, d, f = mix["tokens"], cfg["hidden_size"], cfg["intermediate_size"]
    dtype = cfg["torch_dtype"]

    def fn(x, gate, up, down):
        # the gate in float32: the pipeline's fused SiLU block does not
        # lower for bfloat16 on Mosaic (PERF.md, Open questions)
        g = ops.cast(ops.matmul(x, gate), "float32")
        u = ops.cast(ops.matmul(x, up), "float32")
        return ops.matmul(ops.cast(ops.mul(ops.silu(g), u), dtype), down)

    flops, bytes_ = work.swiglu_mlp_work(t, d, f, dtype)
    return Program(args=dense.swiglu_inputs(t, d, f, dtype, seed), fn=fn,
                   reference=dense.swiglu_reference,
                   control=dense.swiglu_fp8, flops=flops, bytes=bytes_)
