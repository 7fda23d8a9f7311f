"""Serving-engine regressions: the block-paged KV cache must be a pure
layout change.

* **Token parity on every registered backend** — the continuous-batching
  engine over the paged cache must emit exactly the tokens the
  contiguous-cache ``generate`` path emits, per request, on every
  backend the registry knows (the paged gather/append lower through the
  pipeline, so each target compiles a different program) and for both
  the dense and moe model families.  The workload is ragged (per-request
  prompt AND generation lengths) with more requests than slots, so
  mid-stream slot refill is exercised on every combination.
* **Logits parity to 1e-5** — one decode step, paged vs contiguous, on
  the same prefilled context: the gather feeds the attention kernel the
  same K/V values the contiguous cache holds.
* **Quantized composition** — ``quantized=True`` (int8 KV + per-block
  scale pools riding the same page table) must match the quantized
  contiguous cache token-for-token.
* **Page-pool exhaustion** — a request that could never fit the pool is
  an error (:class:`PagePoolExhausted`), while one that merely has to
  wait for freed blocks is FCFS back-pressure, not an error.

Scheduler/allocator behaviour is tested host-side without compiling a
model (the scheduler module is jax-free by design).
"""
import contextlib
import dataclasses
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ops as cops
from repro.core.backend import available_backends
from repro.core.options import CompileOptions, use_options
from repro.launch import serve as launch_serve
from repro.launch import steps as steps_mod
from repro.launch.serve import generate, make_requests, serve_paged
from repro.models import serve as serve_mod
from repro.models.model import build_model
from repro.runtime.scheduler import (BlockAllocator, ContinuousScheduler,
                                     PagePoolExhausted, PrefixIndex,
                                     Request)

ARCHS = ("qwen2-1.5b", "grok-1-314b")      # dense + moe families


@pytest.fixture(scope="module")
def models():
    out = {}
    for arch in ARCHS:
        cfg = get_config(arch, reduced=True)
        model = build_model(cfg)
        out[arch] = (model,
                     steps_mod.cast_compute(model.init(0), cfg.compute_dtype))
    return out


def _reference_tokens(model, params, reqs, *, quantized=False):
    """Greedy per-request reference through the contiguous-cache path,
    run under the ambient compile options (so engine and reference use
    the same backend's kernels)."""
    return {r.rid: generate(model, params, np.asarray(r.prompt)[None],
                            gen_len=r.gen_len,
                            max_len=r.prompt_len + r.gen_len,
                            quantized=quantized)[0].tolist()
            for r in reqs}


# -- paged vs contiguous parity ----------------------------------------------

@pytest.mark.parametrize("target", available_backends())
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_matches_contiguous_every_backend(models, arch, target):
    """Ragged batch, 5 requests into 2 slots: short generations finish
    while long ones are mid-stream, so freed slots are refilled and the
    page table rewired while neighbours keep decoding.  Token streams
    must still match the contiguous path request-for-request."""
    model, params = models[arch]
    opts = CompileOptions(target=target)
    reqs = make_requests(5, prompt_len=4, gen_len=4,
                         vocab=model.cfg.vocab_size, seed=3, ragged=True)
    out = serve_paged(model, params, reqs, n_slots=2, block_size=4,
                      num_blocks=7, options=opts)
    assert len(out["requests"]) == 5
    assert out["tokens"] == sum(r.gen_len for r in out["requests"])
    with use_options(opts):
        refs = _reference_tokens(model, params, out["requests"])
    for r in out["requests"]:
        assert len(r.tokens) == r.gen_len
        assert r.tokens == refs[r.rid], (arch, target, r.rid)


def test_keep_logits_rows_are_the_sampled_ones(models):
    """``keep_logits=2`` keeps, per request, the rows its first two tokens
    were sampled from: the prefill's last-token logits, then the first
    decode step's — what chip_smoke.py compares with its references."""
    model, params = models["qwen2-1.5b"]
    V = model.cfg.vocab_size
    reqs = make_requests(4, prompt_len=6, gen_len=3, vocab=V, seed=5,
                         ragged=True)
    out = serve_paged(model, params, reqs, n_slots=2, block_size=4,
                      num_blocks=9, keep_logits=2)
    for r in out["requests"]:
        assert len(r.logits) == min(2, r.gen_len)
        for row, tok in zip(r.logits, r.tokens):
            assert row.shape == (V,) and row.dtype == np.float32
            assert int(np.argmax(row)) == tok
        prefill = jax.jit(lambda p, b, n=r.prompt_len: model.prefill(
            p, b, max_len=n))
        logits, _ = prefill(params, {"tokens": jnp.asarray(r.prompt[None])})
        np.testing.assert_allclose(
            r.logits[0], np.asarray(logits[0, :V], np.float32), atol=1e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_logits_close(models, arch):
    """One decode step over the same prefilled context: paged gather +
    append must reproduce the contiguous cache's logits to 1e-5."""
    model, params = models[arch]
    P, bs, max_len = 4, 4, 8
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, model.cfg.vocab_size, (1, P)).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompt)}

    logits, cache = model.prefill(params, batch, max_len=max_len)
    tok = jnp.argmax(logits[:, :model.cfg.vocab_size],
                     axis=-1).astype(jnp.int32)
    ref_logits, _ = model.decode_step(params, tok, cache, jnp.int32(P))

    pools = model.init_paged_cache(4, bs)       # blocks 1..3 allocatable
    _, pcache = model.prefill(params, batch, max_len=P)
    pools = serve_mod.scatter_prefill_paged(
        pools, pcache["kv"], jnp.asarray([1], jnp.int32), bs)
    table = jnp.asarray([[1, 2]], jnp.int32)    # block 2 takes the append
    lengths = jnp.asarray([P], jnp.int32)
    paged_logits, _ = model.paged_decode_step(params, tok, pools, table,
                                              lengths, block_size=bs)
    np.testing.assert_allclose(np.asarray(paged_logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_quantized_paged_matches_quantized_contiguous(models, arch):
    """--quantized-kv composes with the paged layout: int8 pools plus
    per-block scale pools on the same page table must hold token parity
    with the quantized contiguous cache."""
    model, params = models[arch]
    reqs = make_requests(4, prompt_len=4, gen_len=4,
                         vocab=model.cfg.vocab_size, seed=5)
    out = serve_paged(model, params, reqs, n_slots=2, block_size=4,
                      num_blocks=7, quantized=True)
    refs = _reference_tokens(model, params, out["requests"], quantized=True)
    for r in out["requests"]:
        assert r.tokens == refs[r.rid], (arch, r.rid)


# -- page-pool exhaustion and back-pressure ----------------------------------

def test_page_pool_exhaustion_is_an_error(models):
    """A request whose block demand can never be met — even by an empty
    pool — must raise, not spin in the pending queue forever."""
    model, params = models["qwen2-1.5b"]
    reqs = make_requests(1, prompt_len=8, gen_len=8,
                         vocab=model.cfg.vocab_size, seed=0)
    # needs ceil(16/4)=4 blocks; a pool of 3 holds only 2 allocatable
    with pytest.raises(PagePoolExhausted):
        serve_paged(model, params, reqs, n_slots=1, block_size=4,
                    num_blocks=3)


def test_scheduler_rejects_request_wider_than_page_table():
    sched = ContinuousScheduler(1, BlockAllocator(8), block_size=4,
                                max_blocks_per_slot=2)
    req = Request(rid=0, prompt=np.zeros(8, np.int32), gen_len=8,
                  arrival=0.0)                  # 4 blocks > table width 2
    with pytest.raises(PagePoolExhausted):
        sched.submit(req)


def test_admission_backpressure_waits_for_freed_blocks():
    """A satisfiable-but-not-yet request is back-pressure: it stays at
    the queue head (no queue-jumping) and admits once a finished request
    returns its blocks to the pool."""
    alloc = BlockAllocator(4)                   # 3 allocatable blocks
    sched = ContinuousScheduler(2, alloc, block_size=4,
                                max_blocks_per_slot=2,
                                max_prefill_per_step=2)
    a, b = (Request(rid=i, prompt=np.zeros(4, np.int32), gen_len=4,
                    arrival=0.0) for i in range(2))   # 2 blocks each
    sched.submit(a)
    sched.submit(b)
    assert [r.rid for _, r in sched.admit(0.0)] == [0]
    assert sched.admit(0.1) == []               # 1 free block < b's 2
    sched.finish(a.slot, 0.2)
    assert a.blocks == [] and a.finished_at == 0.2
    assert [r.rid for _, r in sched.admit(0.3)] == [1]
    assert alloc.n_free == 1


def test_block_allocator_free_list():
    with pytest.raises(ValueError):
        BlockAllocator(1)                       # block 0 alone is no pool
    alloc = BlockAllocator(4)
    assert alloc.n_free == 3
    got = alloc.alloc(3)
    assert sorted(got) == [1, 2, 3]             # block 0 never handed out
    with pytest.raises(PagePoolExhausted):
        alloc.alloc(1)
    alloc.release(got[:2])
    assert alloc.n_free == 2


# -- lazy allocation, preemption/swap, chunked prefill, prefix sharing -------

@pytest.fixture(scope="module")
def model_f32():
    """qwen2 with float32 *compute*: chunked prefill recomputes the
    prompt projections in different batch shapes, so exact token parity
    with the monolithic path is only meaningful above bf16 rounding
    noise (which flips near-tie argmaxes in a random-weight model)."""
    cfg = dataclasses.replace(get_config("qwen2-1.5b", reduced=True),
                              compute_dtype="float32")
    model = build_model(cfg)
    return model, steps_mod.cast_compute(model.init(0), "float32")


@pytest.mark.parametrize("target", available_backends())
def test_lazy_preempt_swap_resume_matches_every_backend(models, target):
    """Pool-pressure path: 4 requests of 3-block max context into a
    4-block pool under lazy allocation.  Growth must preempt the
    lowest-priority request to the swap arena (compiled swap_out),
    resume it FCFS (compiled swap_in), and the emitted streams must
    still match the contiguous path token-for-token."""
    model, params = models["qwen2-1.5b"]
    opts = CompileOptions(target=target)
    reqs = make_requests(4, prompt_len=4, gen_len=8,
                         vocab=model.cfg.vocab_size, seed=7)
    out = serve_paged(model, params, reqs, n_slots=2, block_size=4,
                      num_blocks=5, lazy_alloc=True, options=opts)
    tel = out["telemetry"]
    assert tel["preemptions"] >= 1
    assert tel["swap"]["peak_blocks_in_use"] >= 1
    assert tel["allocator"]["peak_blocks_in_use"] <= 4
    with use_options(opts):
        refs = _reference_tokens(model, params, out["requests"])
    for r in out["requests"]:
        assert len(r.tokens) == r.gen_len
        assert r.tokens == refs[r.rid], (target, r.rid)


def test_lazy_swap_composes_with_quantized_kv(models):
    """Preempt/swap/resume must carry the int8 pools AND their scale
    pools: a request that loses its scales decodes garbage."""
    model, params = models["qwen2-1.5b"]
    reqs = make_requests(4, prompt_len=4, gen_len=8,
                         vocab=model.cfg.vocab_size, seed=7)
    out = serve_paged(model, params, reqs, n_slots=2, block_size=4,
                      num_blocks=5, lazy_alloc=True, quantized=True)
    assert out["telemetry"]["preemptions"] >= 1
    refs = _reference_tokens(model, params, out["requests"], quantized=True)
    for r in out["requests"]:
        assert r.tokens == refs[r.rid], r.rid


def test_lazy_admits_what_reserve_up_front_rejects(models):
    """The headline capacity win: a pool too small to *reserve* two full
    contexts still *serves* two in flight under lazy allocation."""
    model, params = models["qwen2-1.5b"]
    reqs = make_requests(2, prompt_len=4, gen_len=8,
                         vocab=model.cfg.vocab_size, seed=11)
    out = serve_paged(model, params, reqs, n_slots=2, block_size=4,
                      num_blocks=5, lazy_alloc=True)
    assert out["telemetry"]["peak_active"] == 2    # both in flight at once
    reqs2 = make_requests(2, prompt_len=4, gen_len=8,
                          vocab=model.cfg.vocab_size, seed=11)
    base = serve_paged(model, params, reqs2, n_slots=2, block_size=4,
                       num_blocks=5)
    assert base["telemetry"]["peak_active"] == 1   # reserve: one at a time
    assert ({r.rid: r.tokens for r in out["requests"]}
            == {r.rid: r.tokens for r in base["requests"]})


@pytest.mark.parametrize("target", available_backends())
def test_chunked_prefill_matches_monolithic_every_backend(model_f32,
                                                          target):
    """--prefill-chunk is a scheduling change, not a numeric one: the
    chunked engine must emit exactly the monolithic engine's tokens."""
    model, params = model_f32
    opts = CompileOptions(target=target)

    def mk():
        return make_requests(3, prompt_len=11, gen_len=5,
                             vocab=model.cfg.vocab_size, seed=9)

    mono = serve_paged(model, params, mk(), n_slots=2, block_size=4,
                       num_blocks=16, options=opts)
    chunked = serve_paged(model, params, mk(), n_slots=2, block_size=4,
                          num_blocks=16, prefill_chunk=4, options=opts)
    assert ({r.rid: r.tokens for r in mono["requests"]}
            == {r.rid: r.tokens for r in chunked["requests"]}), target


def test_chunked_prefill_logits_close(model_f32):
    """Final-chunk logits vs the monolithic prefill's last-token logits
    on the same prompt: 1e-5, through the paged chunk-scatter path."""
    model, params = model_f32
    bs = 4
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, model.cfg.vocab_size, 11).astype(np.int32)
    row = jnp.asarray([1, 2, 3, 0], jnp.int32)
    with use_options(CompileOptions(target="xla")):
        logits_m, _ = model.prefill(
            params, {"tokens": jnp.asarray(prompt[None], jnp.int32)},
            max_len=11)
        pools = model.init_paged_cache(8, bs)
        start = 0
        for size in (4, 4, 3):
            logits_c, pools = model.paged_prefill_chunk(
                params, jnp.asarray(prompt[start:start + size], jnp.int32),
                jnp.asarray(start, jnp.int32), pools, row, block_size=bs)
            start += size
    np.testing.assert_allclose(np.asarray(logits_c, np.float32),
                               np.asarray(logits_m[0], np.float32),
                               rtol=1e-5, atol=1e-5)


def test_prefill_chunk_must_align_to_block_size(models):
    model, params = models["qwen2-1.5b"]
    reqs = make_requests(1, prompt_len=8, gen_len=2,
                         vocab=model.cfg.vocab_size, seed=0)
    with pytest.raises(ValueError, match="multiple of"):
        serve_paged(model, params, reqs, n_slots=1, block_size=4,
                    num_blocks=8, prefill_chunk=6)


@pytest.mark.parametrize("target", available_backends())
def test_prefix_share_fork_parity_every_backend(models, target):
    """Three co-admitted requests with an identical prompt share its
    blocks (full + exact partial tail); the first divergent appends fork
    the shared tail copy-on-write.  Streams must match the unshared
    engine exactly, with fewer peak blocks."""
    model, params = models["qwen2-1.5b"]
    opts = CompileOptions(target=target)
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, model.cfg.vocab_size, 6).astype(np.int32)

    def mk():
        return [Request(rid=i, prompt=prompt.copy(), gen_len=4,
                        arrival=0.0) for i in range(3)]

    plain = serve_paged(model, params, mk(), n_slots=3, block_size=4,
                        num_blocks=16, max_prefill_per_step=3,
                        options=opts)
    shared = serve_paged(model, params, mk(), n_slots=3, block_size=4,
                         num_blocks=16, max_prefill_per_step=3,
                         prefix_share=True, options=opts)
    assert ({r.rid: r.tokens for r in plain["requests"]}
            == {r.rid: r.tokens for r in shared["requests"]}), target
    tel = shared["telemetry"]
    assert tel["forks"] >= 1                    # CoW fired
    assert tel["shared_block_hits"] >= 2
    assert (tel["allocator"]["peak_blocks_in_use"]
            < plain["telemetry"]["allocator"]["peak_blocks_in_use"])


def test_swap_and_fork_ops_compile_through_kokkos_ir():
    """The engine's swap/fork copies are compiled IR, not host Python:
    eager paged ops run through the pipeline, so the pass dump must show
    kokkos.page_copy with all three directions."""
    pool = jnp.zeros((4, 2, 4, 8), jnp.float32)
    swap = jnp.zeros((3, 2, 4, 8), jnp.float32)
    ids = jnp.asarray([1, 2], jnp.int32)
    buf = io.StringIO()
    opts = CompileOptions(target="xla", print_ir_after_all=True)
    with use_options(opts), contextlib.redirect_stdout(buf):
        swap = cops.page_swap_out(swap, pool, ids, ids, block_size=4)
        pool = cops.page_swap_in(pool, swap, ids, ids, block_size=4)
        pool = cops.page_copy(pool, pool, jnp.asarray([1], jnp.int32),
                              jnp.asarray([3], jnp.int32), block_size=4)
    dump = buf.getvalue()
    assert "kokkos.page_copy" in dump
    for direction in ("swap_out", "swap_in", "copy"):
        assert f"direction='{direction}'" in dump


# -- scheduler-level refcounting, forking, preemption ------------------------

def test_block_allocator_refcounts():
    alloc = BlockAllocator(5)
    a, b = alloc.alloc(2)
    alloc.share([a])
    assert alloc.refcount(a) == 2
    assert alloc.release([a]) == []             # still referenced
    assert alloc.release([a]) == [a]            # last reference frees
    with pytest.raises(ValueError):
        alloc.share([a])                        # can't share a free block
    with pytest.raises(ValueError):
        alloc.release([a])                      # double free
    tel = alloc.telemetry()
    assert tel["peak_blocks_in_use"] == 2
    assert tel["total_allocs"] == 2
    assert alloc.release([b]) == [b]


def test_prefix_index_chain_matching():
    idx = PrefixIndex(4)
    p1 = np.asarray([1, 2, 3, 4, 5, 6], np.int32)
    idx.insert(p1, [7, 8])
    assert idx.match(p1) == [7, 8]              # full + exact partial tail
    p2 = np.asarray([1, 2, 3, 4, 9], np.int32)
    assert idx.match(p2) == [7]                 # different tail: full only
    p3 = np.asarray([1, 9, 3, 4, 5, 6], np.int32)
    assert idx.match(p3) == []                  # chain gate: no skipping
    idx.drop_block(8)
    assert idx.match(p1) == [7]                 # partial entry forgotten


def test_prepare_append_grows_forks_and_drops():
    alloc = BlockAllocator(8)
    idx = PrefixIndex(4)
    sched = ContinuousScheduler(2, alloc, 4, 4, max_prefill_per_step=2,
                                lazy=True, prefix_index=idx)
    prompt = np.asarray([1, 2, 3, 4, 5, 6], np.int32)
    a = Request(rid=0, prompt=prompt, gen_len=6, arrival=0.0)
    b = Request(rid=1, prompt=prompt.copy(), gen_len=6, arrival=0.1)
    sched.submit(a)
    sched.submit(b)
    sched.admit(0.0)
    assert b.blocks == a.blocks                 # fully shared prompt
    assert alloc.refcount(a.blocks[1]) == 2
    fork = sched.prepare_append(a, 6)           # shared partial tail: CoW
    assert fork is not None
    src, dst = fork
    assert src == b.blocks[1] and a.blocks[1] == dst
    assert alloc.refcount(src) == 1
    assert sched.telemetry()["forks"] == 1
    # b's tail is now private but still indexed: append drops the entry
    assert sched.prepare_append(b, 6) is None
    assert not idx.indexed(b.blocks[1])
    # growth across a block boundary allocates lazily
    n0 = len(a.blocks)
    assert sched.prepare_append(a, 8) is None
    assert len(a.blocks) == n0 + 1


def test_preempt_requeues_head_and_resumes_fcfs():
    alloc = BlockAllocator(6)
    sched = ContinuousScheduler(2, alloc, 4, 4, max_prefill_per_step=2,
                                lazy=True)
    a, b, c = (Request(rid=i, prompt=np.zeros(4, np.int32), gen_len=8,
                       arrival=i / 10) for i in range(3))
    for r in (a, b, c):
        sched.submit(r)
    sched.admit(0.0)
    assert sched.pick_victim() is b             # latest arrival in flight
    vblocks = list(b.blocks)
    sched.preempt(b.slot, [5])  # engine swapped KV into swap block 5
    assert b.swap_blocks == [5] and b.blocks == [] and b.slot is None
    assert sched.pending[0] is b                # ahead of c: FCFS resume
    assert alloc.refcount(vblocks[0]) == 0      # pool blocks released
    admitted = sched.admit(0.3)
    assert admitted and admitted[0][1] is b
    assert len(b.blocks) == 1                   # len(swap_blocks) fresh
    assert sched.telemetry()["preemptions"] == 1


def test_pool_exhaustion_message_is_diagnosable():
    alloc = BlockAllocator(4)
    alloc.alloc(3)
    with pytest.raises(PagePoolExhausted) as ei:
        alloc.alloc(2)
    msg = str(ei.value)
    assert "need 2" in msg and "free" in msg and "pool of 4" in msg
    sched = ContinuousScheduler(1, BlockAllocator(4), 4, 8, lazy=True)
    req = Request(rid=0, prompt=np.zeros(4, np.int32), gen_len=8,
                  arrival=0.0)
    sched.submit(req)
    sched.admit(0.0)
    sched.allocator.alloc(2)                    # external pool pressure
    with pytest.raises(PagePoolExhausted) as ei:
        sched.prepare_append(req, 4)
    assert "slot usage" in str(ei.value)        # per-slot block report


# -- the compiled-program cache (LRU + eviction telemetry) -------------------

def test_engine_jit_cache_is_lru_bounded(models):
    model, _ = models["qwen2-1.5b"]
    model.__dict__.pop("_paged_jit_cache", None)
    ev0 = launch_serve.ENGINE_CACHE_STATS["evictions"]
    opts = CompileOptions(target="xla")
    cap = launch_serve.ENGINE_CACHE_CAP
    for bs in range(2, 2 + cap + 2):            # 2 past the cap
        launch_serve._engine_fns(model, bs, False, opts)
    cache = model.__dict__["_paged_jit_cache"]
    assert len(cache) == cap
    assert launch_serve.ENGINE_CACHE_STATS["evictions"] == ev0 + 2
    # a hit is an LRU touch: the touched entry survives the next evict
    hot_bs = next(iter(cache))[0]               # current LRU entry
    launch_serve._engine_fns(model, hot_bs, False, opts)
    launch_serve._engine_fns(model, 999, False, opts)
    assert any(k[0] == hot_bs for k in cache)
    # the per-prompt-length prefill programs are bounded the same way
    fns = launch_serve._engine_fns(model, 4, False, opts)
    for n in range(launch_serve.PREFILL_CACHE_CAP + 3):
        fns["prefill"][100 + n] = object()
    assert len(fns["prefill"]) == launch_serve.PREFILL_CACHE_CAP
    model.__dict__.pop("_paged_jit_cache", None)


def test_serve_telemetry_schema(models):
    """The bench record's telemetry block: scheduler counters, allocator
    peaks, swap-tier usage and jit-cache stats must all be present."""
    model, params = models["qwen2-1.5b"]
    reqs = make_requests(2, prompt_len=4, gen_len=4,
                         vocab=model.cfg.vocab_size, seed=1)
    out = serve_paged(model, params, reqs, n_slots=2, block_size=4,
                      num_blocks=8, lazy_alloc=True)
    tel = out["telemetry"]
    for key in ("preemptions", "forks", "shared_block_hits",
                "peak_active", "lazy", "prefix_sharing"):
        assert key in tel
    for key in ("n_blocks", "peak_blocks_in_use", "peak_utilization",
                "total_allocs"):
        assert key in tel["allocator"]
        assert key in tel["swap"]
    for key in ("hits", "misses", "evictions"):
        assert key in tel["engine_cache"]


def test_swap_roundtrip_and_fork_hold_decode_logits(model_f32):
    """The preemption round-trip (paged.swap_out -> clobber -> swap_in)
    and a copy-on-write fork (paged.copy to a fresh block + repointed
    table row) are pure block moves: the decode step after both must
    reproduce the contiguous cache's logits to 1e-5."""
    model, params = model_f32
    P, bs, max_len = 8, 4, 12
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, model.cfg.vocab_size, (1, P)).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompt)}

    logits, cache = model.prefill(params, batch, max_len=max_len)
    tok = jnp.argmax(logits[:, :model.cfg.vocab_size],
                     axis=-1).astype(jnp.int32)
    ref_logits, _ = model.decode_step(params, tok, cache, jnp.int32(P))

    pools = model.init_paged_cache(6, bs)
    _, pcache = model.prefill(params, batch, max_len=P)
    pools = serve_mod.scatter_prefill_paged(
        pools, pcache["kv"], jnp.asarray([1, 2], jnp.int32), bs)

    ids = jnp.asarray([1, 2], jnp.int32)
    scrap = jnp.asarray([0, 0], jnp.int32)
    arena = model.init_paged_cache(3, bs)
    # preempt: blocks out to the swap arena, clobber the originals with
    # scrap zeros (as if the allocator reused them), resume them back
    arena = {k: cops.page_swap_out(arena[k], pools[k], ids, ids,
                                   block_size=bs) for k in pools}
    pools = {k: cops.page_copy(pools[k], pools[k], scrap, ids,
                               block_size=bs) for k in pools}
    pools = {k: cops.page_swap_in(pools[k], arena[k], ids, ids,
                                  block_size=bs) for k in pools}
    # CoW fork of block 2 into fresh block 4; the repointed table row
    # must be transparent to the decode step
    pools = {k: cops.page_copy(pools[k], pools[k],
                               jnp.asarray([2], jnp.int32),
                               jnp.asarray([4], jnp.int32),
                               block_size=bs) for k in pools}
    table = jnp.asarray([[1, 4, 3]], jnp.int32)   # block 3: the append
    lengths = jnp.asarray([P], jnp.int32)
    paged_logits, _ = model.paged_decode_step(params, tok, pools, table,
                                              lengths, block_size=bs)
    np.testing.assert_allclose(np.asarray(paged_logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=1e-5, atol=1e-5)
