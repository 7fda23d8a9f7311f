"""The trace reduction on traces recorded on one TPU v5e
(``python3 -m bench.tools.record_trace``): a two-layer qwen2-1.5b served
by ``serve_paged`` and three calls of a small SpMV compiled on
``pallas``, each inside a ``bench.traced`` span."""
import pytest

from bench import trace

DATA = __import__("bench.common", fromlist=["ROOT"]).ROOT / "bench" / \
    "tests" / "data"


@pytest.fixture(scope="module")
def served():
    return trace.reduce(trace.load(str(DATA / "serve_paged.xplane.pb")))


@pytest.fixture(scope="module")
def spmv():
    return trace.reduce(trace.load(str(DATA / "spmv.xplane.pb")))


def test_union_merges_overlaps():
    assert trace.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == \
        [(0, 3), (5, 10)]


def test_busy_within_window(served, spmv):
    for r in (served, spmv):
        assert 0 < r.busy_s < r.window_s
        idle = sum(s for _, s in r.gaps)
        assert idle + r.busy_s == pytest.approx(r.window_s, rel=1e-6)


def test_kernels_are_found_by_their_names(served, spmv):
    for k in ("rmsnorm", "page_gather", "decode_attention",
              "flash_attention"):
        assert served.kernel_seconds([k]) > 0, k
    assert spmv.kernel_seconds(["spmv_ell"]) > 0
    assert served.kernel_seconds(["no_such_kernel"]) is None


def test_the_decode_program_is_the_one_holding_decode_attention(served):
    count, seconds = served.program_holding("decode_attention")
    assert count >= 1 and seconds > 0
    prefill = served.program_holding("flash_attention")
    decode = [n for n, p in served.programs.items()
              if "decode_attention" in p.kernels]
    assert len(decode) == 1
    assert "flash_attention" not in served.programs[decode[0]].kernels
    assert prefill is not None
    assert served.program_holding("spmv_ell") is None


def test_control_flow_is_not_counted_as_an_operation(served):
    assert not any(n.startswith("while") for n in served.op_seconds)


def test_calls_and_breakdown(spmv, served):
    assert spmv.span_count("bench.call") == 3
    b = served.breakdown()
    assert len(b["device_ops"]) <= trace.TOP
    assert len(b["idle_gaps"]) <= trace.TOP
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(isinstance(n, str) and s > 0 for n, s in b["idle_gaps"])


def test_a_trace_without_its_window_is_refused():
    t = trace.load(str(DATA / "spmv.xplane.pb"))
    with pytest.raises(ValueError):
        trace.reduce(t, window_span="no.such.span")


def test_readers_on_a_chip_trace(served):
    """The per-layer readers of a serving cell over the recorded trace of
    a two-layer cut of qwen2-1.5b and a synthetic window of two
    requests decoded together twice."""
    import types

    from bench import common, run

    cfg = dict(common.load_json(common.ROOT / "bench" / "configs" /
                                "qwen2-1.5b.json"), num_hidden_layers=2)
    reqs = [types.SimpleNamespace(prompt_len=p, token_times=[0.0, 1.0, 2.0],
                                  tokens=[1, 2, 3]) for p in (64, 48)]
    rec = run.Record(
        cell=common.Cell(name="t", chips=1, config_name="t", config=cfg,
                         traffic_name="t", traffic={}, end_to_end=[],
                         per_layer=[]),
        peak=common.peaks("TPU v5 lite"), setup=None,
        window=types.SimpleNamespace(requests=reqs, trace_window=(0.5, 2.5),
                                     compiles=0),
        trace=served)
    count, seconds = served.program_holding("decode_attention")
    read = common.metric_reader
    assert read("decode_step_ms.chat")(rec) == pytest.approx(
        1e3 * seconds / count)
    assert 0 < read("decode_mfu.chat")(rec) < 100
    assert read("compiles_in_window.chat")(rec) == 0.0
    assert read("idle_share.sparse")(rec) == pytest.approx(
        100 * (1 - served.busy_s / served.window_s))
    rec.trace = None
    assert read("decode_step_ms.chat")(rec) is None


def test_dense_readers(spmv):
    """The dense cell's readers: the ``matmul`` kernel's device time per
    traced call, the call's roofline share by the arithmetic of the
    sparse cell's, its share of the peak over the traced window, and
    the idle share; nothing where the trace holds no such kernel or
    there is no trace."""
    import types

    from bench import common, work

    read = common.metric_reader
    flops, bytes_ = work.swiglu_mlp_work(16384, 1536, 8960, 28, "bfloat16")
    run = types.SimpleNamespace(
        peak=common.peaks("TPU v5 lite"), trace=spmv,
        setup=types.SimpleNamespace(program=types.SimpleNamespace(
            flops=flops, bytes=bytes_)))
    assert read("matmul_ms.dense")(run) is None     # an SpMV has no matmul
    assert read("call_roofline.dense")(run) == \
        read("call_roofline.sparse")(run)
    run.trace = trace.Reduced(
        window_s=0.0168, busy_s=0.0150,
        op_seconds={"matmul": 0.0074, "block_map": 0.0026}, programs={},
        gaps=[], spans={"bench.call": 1})
    assert read("matmul_ms.dense")(run) == pytest.approx(7.4)
    assert read("call_roofline.dense")(run) == pytest.approx(
        100 * flops / 197e12 / 0.0150)
    assert read("mfu.dense")(run) == pytest.approx(
        100 * flops / 197e12 / 0.0168)
    assert read("idle_share.dense")(run) == pytest.approx(
        100 * (1 - 0.0150 / 0.0168))
    run.trace = None
    for name in ("matmul_ms.dense", "call_roofline.dense", "mfu.dense",
                 "idle_share.dense"):
        assert read(name)(run) is None
