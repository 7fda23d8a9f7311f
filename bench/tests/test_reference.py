"""The plain qwen2 reference against the program's own forward pass on
the same weights, in float32, at a size the CPU holds: they must agree
to rounding.  A wrong RoPE pairing, bias, norm or head mapping on either
side lands far off."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, serve
from bench.reference import qwen2 as ref

DATA = common.ROOT / "bench" / "tests" / "data"


@pytest.fixture(scope="module")
def tiny():
    common.import_program()
    cfg = dict(common.load_json(DATA / "tiny-qwen2.json"),
               served_dtype="float32")
    model = serve.program_model(ref, cfg)
    w = serve.make_weights(ref, cfg, model.cfg.padded_vocab, 5)
    serve.check_layout(w, model)
    return cfg, model, w


def test_reference_matches_the_program_in_float32(tiny):
    cfg, model, w = tiny
    from repro.core.options import CompileOptions, use_options
    toks = np.random.default_rng(0).integers(1, cfg["vocab_size"], 1024)
    with use_options(CompileOptions(target="xla")), \
            jax.default_matmul_precision("highest"):
        logits, _ = model.forward(w, {"tokens": jnp.asarray(toks)[None]})
    got = np.asarray(logits[0, :, :cfg["vocab_size"]], np.float64)
    x = ref.hidden(w, jnp.asarray(toks, jnp.int32), cfg)
    want = np.asarray(ref._logits(w, x, cfg), np.float64)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-4


def test_served_gaps_are_zero_on_the_reference_own_tokens(tiny):
    cfg, _, w = tiny
    items = ref.cfg_items(cfg)
    toks = np.random.default_rng(1).integers(1, cfg["vocab_size"],
                                             ref.PAD).astype(np.int32)
    x = ref.hidden(w, jnp.asarray(toks), cfg)
    best = np.asarray(jnp.argmax(ref._logits(w, x, cfg), -1), np.int32)
    gaps = np.asarray(ref.served_gaps(w, toks, best, items))
    assert gaps.max() == 0.0
    other = (best + 1) % cfg["vocab_size"]
    assert np.asarray(ref.served_gaps(w, toks, other, items)).min() > 0.0


def test_swiglu_reference_and_its_control():
    from bench.reference import dense
    args = dense.swiglu_inputs(64, 32, 96, 2, "float32", 3)
    x = args[0]
    for norm, gate, up, down in dense.layers_of(args[1:]):
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * norm
        x = x + (jax.nn.silu(h @ gate) * (h @ up)) @ down
    want = np.asarray(x, np.float64)
    rows = np.array([0, 5, 63])
    got = dense.swiglu_reference(*args, rows=rows, eps=1e-6)
    # the rows asked for, each as the whole stack over every row gives it
    assert got.shape == (3, 32)
    assert np.max(np.abs(got - want[rows])) / np.max(np.abs(want)) < 1e-5
    ctrl = dense.swiglu_fp8(*args, rows=rows, eps=1e-6)
    err = np.max(np.abs(ctrl - got)) / np.max(np.abs(got))
    assert 1e-3 < err < 0.2          # float8 rounding, not a wrong product
