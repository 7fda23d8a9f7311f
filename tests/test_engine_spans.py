"""The serving engine's host spans and program names in a profiler trace.

``serve_paged`` wraps each phase of a loop iteration in a
``jax.profiler.TraceAnnotation`` (the ``SPAN_*`` constants of
``repro.launch.serve``) and jits named functions, so a trace taken
around a serving window says what the host did between device
programs, and which program ran.  These tests serve the reduced qwen2
under ``jax.profiler.start_trace`` on the CPU and read the trace back
with ``jax.profiler.ProfileData``:

* one ``engine.dispatch`` and one ``engine.readback`` per decode step,
  one ``engine.prefill`` per request carrying its ``rid``;
* every phase span inside an ``engine.iteration``;
* the wait for an arrival as ``engine.idle``;
* the programs as ``PjitFunction(paged_decode)`` and friends;
* the preemption, chunk and copy-on-write spans with their ``rid``;
* and the same tokens as a run without the profiler.
"""
import collections
import dataclasses
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.launch import serve as launch_serve
from repro.launch import steps as steps_mod
from repro.launch.serve import make_requests, serve_paged
from repro.models.model import build_model
from repro.runtime.scheduler import Request

PHASES = (launch_serve.SPAN_ADMIT, launch_serve.SPAN_PREFILL,
          launch_serve.SPAN_CHUNK, launch_serve.SPAN_SWAP_OUT,
          launch_serve.SPAN_SWAP_IN, launch_serve.SPAN_FORK,
          launch_serve.SPAN_PREPARE, launch_serve.SPAN_DISPATCH,
          launch_serve.SPAN_SCAN, launch_serve.SPAN_READBACK,
          launch_serve.SPAN_EMIT, launch_serve.SPAN_IDLE)


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int
    args: dict


def _model(compute_dtype=None):
    cfg = get_config("qwen2-1.5b", reduced=True)
    if compute_dtype:
        cfg = dataclasses.replace(cfg, compute_dtype=compute_dtype)
    model = build_model(cfg)
    return model, steps_mod.cast_compute(model.init(0), cfg.compute_dtype)


def _traced(trace_dir, serve):
    """``serve()`` under the profiler; (its result, the host events)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        out = serve()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    spans = [Span(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                  {k: v for k, v in e.stats})
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    return out, spans


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _tokens(out):
    return {r.rid: list(r.tokens) for r in out["requests"]}


def _inside_iterations(spans):
    """The engine's phase spans that no ``engine.iteration`` holds."""
    its = sorted((s.start, s.end) for s in
                 _named(spans, launch_serve.SPAN_ITERATION))
    return [s for s in spans if s.name in PHASES
            and not any(a <= s.start and s.end <= b for a, b in its)]


# -- continuous batching with staggered arrivals ------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Four requests arriving 0.1-0.25 s after the loop starts, so its
    first iterations wait; served once without the profiler (which also
    compiles every program) and once under it."""
    model, params = _model()

    def serve():
        reqs = make_requests(4, prompt_len=8, gen_len=5,
                             vocab=model.cfg.vocab_size, seed=3)
        for r, t in zip(reqs, (0.1, 0.15, 0.2, 0.25)):
            r.arrival = t
        return serve_paged(model, params, reqs, n_slots=2, block_size=4,
                           num_blocks=16)

    plain = serve()
    out, spans = _traced(tmp_path_factory.mktemp("trace"), serve)
    return plain, out, spans


def test_one_dispatch_and_readback_per_decode_step(served):
    _, out, spans = served
    assert out["steps"] > 0
    for name in (launch_serve.SPAN_DISPATCH, launch_serve.SPAN_READBACK,
                 launch_serve.SPAN_PREPARE, launch_serve.SPAN_EMIT):
        assert len(_named(spans, name)) == out["steps"], name
    batches = [s.args["batch"]
               for s in _named(spans, launch_serve.SPAN_DISPATCH)]
    assert all(1 <= b <= 2 for b in batches)


def test_one_prefill_per_request_with_its_rid(served):
    _, out, spans = served
    pre = _named(spans, launch_serve.SPAN_PREFILL)
    assert sorted(s.args["rid"] for s in pre) == \
        sorted(r.rid for r in out["requests"])
    lens = {r.rid: r.prompt_len for r in out["requests"]}
    assert all(s.args["prompt_len"] == lens[s.args["rid"]] for s in pre)


def test_every_phase_lies_inside_an_iteration(served):
    _, _, spans = served
    its = _named(spans, launch_serve.SPAN_ITERATION)
    assert its
    assert [s.args["step"] for s in sorted(its, key=lambda s: s.start)] \
        == sorted(s.args["step"] for s in its)
    assert not _inside_iterations(spans)


def test_an_arrival_gap_is_idle(served):
    _, _, spans = served
    idle = _named(spans, launch_serve.SPAN_IDLE)
    assert idle
    assert max(s.end - s.start for s in idle) > 10e6    # over 10 ms asleep


def test_programs_carry_stable_names(served):
    _, _, spans = served
    names = {s.name for s in spans}
    for fn in ("paged_decode", "prefill", "kv_scatter"):
        assert f"PjitFunction({fn})" in names, fn
    assert "PjitFunction(<lambda>)" not in names


def test_tokens_match_a_run_without_the_profiler(served):
    plain, out, _ = served
    assert _tokens(out) == _tokens(plain)
    assert all(len(r.tokens) == r.gen_len for r in out["requests"])


# -- preemption, chunked prefill, copy-on-write -------------------------------

def _lazy(model):
    """Four requests of 3-block contexts into a 4-block pool: growth
    preempts to the swap arena and resumes."""
    reqs = make_requests(4, prompt_len=4, gen_len=8,
                         vocab=model.cfg.vocab_size, seed=7)
    return dict(reqs=reqs, n_slots=2, block_size=4, num_blocks=5,
                lazy_alloc=True)


def _chunked(model):
    reqs = make_requests(3, prompt_len=11, gen_len=5,
                         vocab=model.cfg.vocab_size, seed=9)
    return dict(reqs=reqs, n_slots=2, block_size=4, num_blocks=16,
                prefill_chunk=4)


def _shared(model):
    """Three requests with one prompt: its tail block is shared, and the
    first divergent append forks it."""
    prompt = np.random.default_rng(5).integers(
        1, model.cfg.vocab_size, 6).astype(np.int32)
    reqs = [Request(rid=i, prompt=prompt.copy(), gen_len=4, arrival=0.0)
            for i in range(3)]
    return dict(reqs=reqs, n_slots=3, block_size=4, num_blocks=16,
                max_prefill_per_step=3, prefix_share=True)


@pytest.mark.parametrize("case,spans_wanted,program", [
    (_lazy, (launch_serve.SPAN_SWAP_OUT, launch_serve.SPAN_SWAP_IN), None),
    (_chunked, (launch_serve.SPAN_CHUNK,), "prefill_chunk"),
    (_shared, (launch_serve.SPAN_FORK,), None),
], ids=["swap", "chunk", "fork"])
def test_copy_and_chunk_spans(tmp_path, case, spans_wanted, program):
    model, params = _model("float32" if case is _chunked else None)

    def serve():
        kw = case(model)
        return serve_paged(model, params, kw.pop("reqs"), **kw)

    plain = serve()
    out, spans = _traced(tmp_path, serve)
    rids = {r.rid for r in out["requests"]}
    for name in spans_wanted:
        got = _named(spans, name)
        assert got, name
        assert all(s.args["rid"] in rids for s in got), name
    if program:
        assert f"PjitFunction({program})" in {s.name for s in spans}
    for s in _named(spans, launch_serve.SPAN_CHUNK):
        assert 0 < s.args["size"] <= 4
    assert not _inside_iterations(spans)
    assert collections.Counter(s.name for s in spans)[
        launch_serve.SPAN_DISPATCH] == out["steps"]
    assert _tokens(out) == _tokens(plain)
