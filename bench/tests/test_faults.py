"""Whole runs of the harness past its look for a chip, at a size a test
can hold, on the CPU: the sound program comes out correct; the program
with a fault planted where it produces its answer, and the control (the
reference in the program's place at the precision below the
configuration's), come out not correct."""
import functools
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, run, serve

DATA = common.ROOT / "bench" / "tests" / "data"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SERVE_E2E = [{"name": n, "unit": u} for n, u in (
    ("output_tok_s", "tokens/s"), ("ttft_p95_ms", "ms"),
    ("tbt_p95_ms", "ms"), ("setup_s", "s"))]
CALL_E2E = [{"name": "call_ms", "unit": "ms"}, {"name": "setup_s",
                                                "unit": "s"}]


def _cell(config, mix, e2e):
    return common.Cell(name="tiny", chips=1, config_name="tiny",
                       config=common.load_json(DATA / config),
                       traffic_name="tiny",
                       traffic=common.load_json(DATA / mix),
                       end_to_end=e2e, per_layer=[])


@pytest.fixture(scope="module")
def program():
    common.import_program()


def _measure(cell, seed, seconds):
    return run.measure(cell, seed, seconds, False, CPU, time.monotonic())


def test_sound_serving_run_is_correct(program):
    res = _measure(_cell("tiny-qwen2.json", "tiny-chat.json", SERVE_E2E),
                   2**31 + 11, 0.5)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 20 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in SERVE_E2E}


def test_a_token_altered_where_produced_is_not_correct(program,
                                                       monkeypatch):
    from repro.models import serve as model_serve
    real = model_serve.paged_decode_step

    def off_by_one(*a, **k):
        logits, pools = real(*a, **k)
        # every decode step emits the token after the one it should
        return jnp.roll(logits, 1, axis=-1), pools

    monkeypatch.setattr(model_serve, "paged_decode_step", off_by_one)
    res = _measure(_cell("tiny-qwen2.json", "tiny-chat.json", SERVE_E2E),
                   2**31 + 11, 0.5)
    assert not res["correct"]
    gap = next(c for c in res["checks"] if c["name"] == "widest_logit_gap")
    assert gap["value"] > gap["limit"]


def test_serving_control_reads_above_the_limit(program):
    cell = _cell("tiny-qwen2.json", "tiny-chat.json", SERVE_E2E)
    engine = serve.set_up(cell, 1, 0.5)
    served = serve.run_window(engine, cell.traffic, 1, 0.5,
                              cell.config["vocab_size"], None)
    picked = serve.sample(served.requests, 1, cell.traffic["check"]["sample"])
    limit = cell.traffic["check"]["widest_logit_gap"]
    ref = common.reference(cell.config)
    assert serve.widest_gap(ref, engine.weights, cell.config,
                            picked)[0] <= limit
    assert serve.control_gap(ref, engine.weights, cell.config, picked) > limit


def test_sound_compiler_run_is_correct(program):
    res = _measure(_cell("tiny-compiler.json", "tiny-spmv.json", CALL_E2E),
                   2**31 + 3, 0.2)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and "call_ms" in res["metrics"]


def _replace_program(monkeypatch, answer):
    """pipeline.compile hands back ``answer(*args)`` in the program's
    place."""
    from repro.core import pipeline

    class Module:
        graph = None

        def __init__(self, args):
            self.out = jnp.asarray(answer(*args))

        def __call__(self, *_):
            return self.out

    monkeypatch.setattr(pipeline, "compile",
                        lambda fn, *args, **_: Module(args))


def test_an_answer_altered_where_produced_is_not_correct(program,
                                                         monkeypatch):
    from bench.reference import sparse

    def altered(*args):
        y = sparse.spmv_reference(*args).astype(np.float32)
        y[len(y) // 2] += 1.0
        return y

    _replace_program(monkeypatch, altered)
    res = _measure(_cell("tiny-compiler.json", "tiny-spmv.json", CALL_E2E),
                   2**31 + 3, 0.2)
    assert not res["correct"] and res["failed"] >= 1


def test_compiler_control_is_not_correct(program, monkeypatch):
    from bench.reference import sparse
    _replace_program(monkeypatch, sparse.spmv_bf16)
    res = _measure(_cell("tiny-compiler.json", "tiny-spmv.json", CALL_E2E),
                   2**31 + 3, 0.2)
    assert not res["correct"]


def test_sound_dense_run_is_correct(program):
    res = _measure(_cell("tiny-qwen2-mlp.json", "tiny-prefill.json",
                         CALL_E2E), 2**31 + 5, 0.2)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and "call_ms" in res["metrics"]


def test_dense_control_is_not_correct(program, monkeypatch):
    from bench.reference import dense
    _replace_program(monkeypatch, functools.partial(
        dense.swiglu_fp8, rows=slice(None), eps=1e-6))
    res = _measure(_cell("tiny-qwen2-mlp.json", "tiny-prefill.json",
                         CALL_E2E), 2**31 + 5, 0.2)
    assert not res["correct"]


def test_dense_answer_altered_where_produced_is_not_correct(program,
                                                            monkeypatch):
    from bench.reference import dense

    def altered(*args):
        # every row as the reference gives it, then one element of each
        # row altered: the check compares a sample of the rows
        y = dense.swiglu_reference(*args, rows=slice(None),
                                   eps=1e-6).astype(np.float32)
        y[:, len(y[0]) // 2] += 0.1 * np.abs(y).max()
        return y

    _replace_program(monkeypatch, altered)
    res = _measure(_cell("tiny-qwen2-mlp.json", "tiny-prefill.json",
                         CALL_E2E), 2**31 + 5, 0.2)
    assert not res["correct"] and res["failed"] >= 1
