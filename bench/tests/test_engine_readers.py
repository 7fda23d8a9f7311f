"""The engine's per-layer readers, ``host_gap_ms.chat`` and
``prefill_ms.chat``, on a trace recorded on one TPU v5e with the
engine's spans and program names (``python3 -m bench.tools.record_trace``:
a two-layer cut of qwen2-1.5b, four requests served by ``serve_paged``
inside ``bench.traced``), and on the earlier trace without them."""
import types

import pytest

from bench import common, trace

DATA = common.ROOT / "bench" / "tests" / "data"
PREFILL_PROGRAMS = ("jit_prefill", "jit_kv_scatter")


def _reduce(name):
    return trace.reduce(trace.load(str(DATA / name)))


@pytest.fixture(scope="module")
def spans():
    return _reduce("serve_paged_spans.xplane.pb")


def _read(metric, reduced):
    return common.metric_reader(metric)(types.SimpleNamespace(trace=reduced))


def test_host_gap_is_idle_per_decode_step(spans):
    steps = spans.span_count("engine.dispatch")
    assert steps > 0
    assert steps == spans.span_count("engine.readback")
    gap = _read("host_gap_ms.chat", spans)
    assert gap > 0
    assert gap == pytest.approx(
        1e3 * (spans.window_s - spans.busy_s) / steps)


def test_prefill_is_its_programs_per_admission(spans):
    n = spans.span_count("engine.prefill")
    assert n == 4
    seconds = sum(p.seconds for name, p in spans.programs.items()
                  if name.split("(")[0] in PREFILL_PROGRAMS)
    ms = _read("prefill_ms.chat", spans)
    assert ms > 0
    assert ms == pytest.approx(1e3 * seconds / n)


def test_programs_carry_their_function_names(spans):
    names = {name.split("(")[0] for name in spans.programs}
    assert {"jit_paged_decode", "jit_prefill", "jit_kv_scatter"} <= names
    assert "jit__lambda" not in names
    decode = [n for n, p in spans.programs.items()
              if "decode_attention" in p.kernels]
    assert [n.split("(")[0] for n in decode] == ["jit_paged_decode"]


def test_idle_with_no_span_is_under_a_tenth(spans):
    idle = sum(s for _, s in spans.gaps)
    assert idle > 0
    assert dict(spans.gaps).get(trace.NO_SPAN, 0.0) < 0.1 * idle
    engine = sum(s for label, s in spans.gaps if label.startswith("engine."))
    assert engine > 0


def test_readers_read_nothing_without_the_engine_spans():
    """The trace of the engine before it had the spans and names
    (``serve_paged.xplane.pb``): both readers give no number."""
    old = _reduce("serve_paged.xplane.pb")
    assert old.span_count("engine.dispatch") == 0
    assert _read("host_gap_ms.chat", old) is None
    assert _read("prefill_ms.chat", old) is None
    assert _read("host_gap_ms.chat", None) is None
    assert _read("prefill_ms.chat", None) is None
