"""Serving entry point: continuous batching over a block-paged KV cache.

The engine (:func:`serve_paged`) replaces the seed's fixed-wave loop:

* a request queue with **continuous (in-flight) batching** — finished
  decode slots are refilled every step, ragged prompt lengths allowed;
* a **block-paged KV cache**: per-slot page tables over a shared pool of
  fixed-size blocks, freed on request completion.  The page gather /
  append steps are ``kokkos.*`` IR compiled through the pipeline
  (``paged_to_kokkos`` pass), never host Python;
* **prefill/decode disaggregation** — prefill is compiled separately
  (per prompt length) and admission is bounded by
  ``--max-prefill-per-step`` so bursts cannot stall the decode loop;
* an **async dispatch loop**: each decode step is dispatched, host-side
  arrival scanning/scheduling runs while the device computes, and
  ``jax.block_until_ready`` fences only the token readback;
* **lazy block allocation** (``--lazy-alloc``): admission reserves only
  the prompt's blocks, generation grows the page table one block at a
  time as it crosses block boundaries, and pool exhaustion preempts the
  lowest-priority in-flight request to a host-side **swap tier**
  (compiled ``paged.swap_out`` / ``paged.swap_in`` block copies) instead
  of failing admission;
* **chunked prefill** (``--prefill-chunk N``): long prompts are prefilled
  ``N`` tokens at a time, interleaved with decode steps, so one long
  prompt cannot stall every in-flight decode;
* **copy-on-write prefix sharing** (``--prefix-share``): requests with a
  common prompt prefix map the same physical blocks (refcounted); the
  first divergent append forks the shared block via a compiled
  ``paged.copy``.
* **host spans on the profiler's clock**: each phase of a loop iteration
  runs inside a ``jax.profiler.TraceAnnotation`` named by an ``SPAN_*``
  constant below, and the jitted programs carry stable names
  (``paged_decode``, ``kv_scatter``, ``prefill``, ``prefill_chunk``), so
  a trace taken around a serving window (``jax.profiler.trace``) shows
  what the host did in every device gap.

All block movement — gather, append, swap, fork — lowers through the
``paged_to_kokkos`` pass to ``kokkos.page_*`` IR (visible under
``--print-ir-after-all`` and in lapis-translate's C++), never host
Python.

The seed's lock-step wave loop survives as ``--policy static`` (and the
contiguous-cache path as ``generate``/``serve_loop``) so the two can be
benchmarked side by side (benchmarks/serve_bench.py → BENCH_serve.json).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --requests 8 --slots 4 --prompt-len 16 --gen-len 16 --paged
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from collections import OrderedDict
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import get_config
from repro.core import ops as cops
from repro.core.options import CompileOptions, use_options
from repro.launch import env
from repro.launch import steps as steps_mod
from repro.models import serve as serve_mod
from repro.models.model import build_model
from repro.runtime.scheduler import (BlockAllocator, ContinuousScheduler,
                                     PagePoolExhausted, PrefixIndex,
                                     Request, poisson_arrivals)


def generate(model, params, prompts: np.ndarray, *, gen_len: int,
             max_len: int, quantized: bool = False, greedy: bool = True,
             rng: Optional[np.random.Generator] = None,
             key: Optional[jax.Array] = None) -> np.ndarray:
    """Prefill + decode ``gen_len`` tokens for a batch of equal-length
    prompts.  Returns (B, gen_len) generated ids.

    Non-greedy decode consumes ``key`` (a JAX PRNG key), splitting a
    fresh subkey per step — never a position-derived ``PRNGKey(length)``,
    which would hand every request at the same position the identical
    sample stream regardless of the serving seed.
    """
    B, S = prompts.shape
    batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
    cfg = model.cfg
    if cfg.frontend == "audio":
        rng = rng or np.random.default_rng(0)
        batch["audio_frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.encoder_seq, cfg.d_model)),
            jnp.float32)
    prefill = jax.jit(
        lambda p, b: model.prefill(p, b, max_len=max_len,
                                   quantized=quantized))
    decode = jax.jit(model.decode_step)
    logits, cache = prefill(params, batch)
    out = []
    length = S
    if key is None:
        key = jax.random.PRNGKey(0)
    for _ in range(gen_len):
        if greedy:
            tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1) \
                .astype(jnp.int32)
        else:
            key, step_key = jax.random.split(key)
            tok = jax.random.categorical(
                step_key,
                logits[:, :cfg.vocab_size]).astype(jnp.int32)
        out.append(np.asarray(tok))
        logits, cache = decode(params, tok, cache, jnp.int32(length))
        length += 1
    return np.stack(out, axis=1)


def serve_loop(model, params, *, n_requests: int, batch: int,
               prompt_len: int, gen_len: int, quantized: bool = False,
               greedy: bool = True, seed: int = 0) -> dict:
    """Continuous batching over a synthetic request queue.  The serving
    ``seed`` roots one PRNG key; each wave decodes with its own split
    subkey, so two waves never reuse a sample stream."""
    cfg = model.cfg
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    queue: List[np.ndarray] = [
        rng.integers(1, cfg.vocab_size, prompt_len)
        for _ in range(n_requests)]
    done = 0
    t0 = time.monotonic()
    tokens_out = 0
    while queue:
        wave = queue[:batch]
        queue = queue[batch:]
        prompts = np.stack(
            wave + [wave[-1]] * (batch - len(wave)))  # pad the last wave
        key, wave_key = jax.random.split(key)
        gen = generate(model, params, prompts, gen_len=gen_len,
                       max_len=prompt_len + gen_len, quantized=quantized,
                       greedy=greedy, rng=rng, key=wave_key)
        done += len(wave)
        tokens_out += gen_len * len(wave)
    dt = time.monotonic() - t0
    return {"requests": done, "tokens": tokens_out, "seconds": dt,
            "tok_per_s": tokens_out / max(dt, 1e-9)}


# ---------------------------------------------------------------------------
# the serving engine: continuous batching over the block-paged KV cache
# ---------------------------------------------------------------------------

def make_requests(n: int, *, prompt_len: int, gen_len: int, vocab: int,
                  seed: int = 0, ragged: bool = False,
                  arrival_rate: Optional[float] = None) -> List[Request]:
    """Synthetic request set.  ``ragged`` draws per-request prompt and
    generation lengths from [1, prompt_len] / [1, gen_len]; a Poisson
    ``arrival_rate`` (requests/s) staggers arrivals, else all arrive at
    t=0."""
    rng = np.random.default_rng(seed)
    arrivals = (poisson_arrivals(n, arrival_rate, rng)
                if arrival_rate else [0.0] * n)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(1, prompt_len + 1)) if ragged else prompt_len
        glen = int(rng.integers(1, gen_len + 1)) if ragged else gen_len
        prompt = rng.integers(1, vocab, plen).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, gen_len=glen,
                            arrival=arrivals[i]))
    return reqs


# Host spans of the engine loop, one per phase of an iteration (never one
# per slot or per token).  They are written into the profiler's trace
# while one is being taken and cost under a microsecond each otherwise.
SPAN_ITERATION = "engine.iteration"   # one pass of the loop; arg: step
SPAN_ADMIT = "engine.admit"           # arrivals and admission
SPAN_PREFILL = "engine.prefill"       # prefill, scatter, first token; rid
SPAN_CHUNK = "engine.chunk"           # one prefill chunk; rid, size
SPAN_SWAP_OUT = "engine.swap_out"     # preemption copy; rid
SPAN_SWAP_IN = "engine.swap_in"       # resume copy; rid
SPAN_FORK = "engine.fork"             # copy-on-write block copy; rid
SPAN_PREPARE = "engine.prepare"       # page tables and decode inputs
SPAN_DISPATCH = "engine.dispatch"     # decode step and sample; batch
SPAN_SCAN = "engine.scan"             # arrivals while the step runs
SPAN_READBACK = "engine.readback"     # wait for and copy the tokens
SPAN_EMIT = "engine.emit"             # append tokens, retire requests
SPAN_IDLE = "engine.idle"             # sleep until the next arrival

ENGINE_CACHE_CAP = 8      # (geometry, quantized, backend) cache entries
PREFILL_CACHE_CAP = 32    # per-length prefill / chunk programs per entry
ENGINE_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


class _LruDict(OrderedDict):
    """Bounded insertion-ordered program cache.  :func:`_cached`
    re-inserts on every hit so order is true LRU; overflow evicts the
    stalest entry and counts it in ``ENGINE_CACHE_STATS``."""

    def __init__(self, cap: int):
        super().__init__()
        self.cap = cap

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self.move_to_end(key)
        while len(self) > self.cap:
            self.popitem(last=False)
            ENGINE_CACHE_STATS["evictions"] += 1


def _cached(cache: "_LruDict", key, make: Callable):
    """Fetch-or-build with an LRU touch (re-insert moves to MRU end)."""
    fn = cache.get(key)
    if fn is None:
        fn = make()
    cache[key] = fn
    return fn


def _engine_fns(model, block_size: int, quantized: bool,
                options: CompileOptions) -> dict:
    """Per-(model, geometry, backend) compiled-program cache.

    Repeated :func:`serve_paged` calls (benchmark repeats, tests) reuse
    the jitted decode / prefill-scatter programs — and the per-prompt-
    length prefill / prefill-chunk programs of the disaggregated prefill
    path — instead of re-jitting a cold engine every call.  The backend
    options are part of the key: the paged ops inside ``decode`` lower
    through the pipeline at jax-trace time, so a program traced under
    one target must never be replayed under another.

    Both cache levels are LRU-bounded (``ENGINE_CACHE_CAP`` outer
    entries, ``PREFILL_CACHE_CAP`` per-length programs each): bucketed
    ragged prompts plus chunked prefill multiply compiled geometries,
    and an unbounded cache would grow for the life of the process.
    Hits, misses and evictions are counted in ``ENGINE_CACHE_STATS``
    and exported in the serve telemetry.
    """
    cache = model.__dict__.setdefault("_paged_jit_cache",
                                      _LruDict(ENGINE_CACHE_CAP))
    key = (block_size, quantized, dataclasses.astuple(options))
    fns = cache.get(key)
    if fns is None:
        ENGINE_CACHE_STATS["misses"] += 1

        # named functions, so that a trace shows jit_paged_decode(...)
        def paged_decode(p, t, c, tb, ln):
            return model.paged_decode_step(p, t, c, tb, ln,
                                           block_size=block_size)

        def kv_scatter(c, kv, ids):
            return serve_mod.scatter_prefill_paged(c, kv, ids, block_size)

        fns = {
            "decode": jax.jit(paged_decode, donate_argnums=(2,)),
            "scatter": jax.jit(kv_scatter, donate_argnums=(0,)),
            "prefill": _LruDict(PREFILL_CACHE_CAP),  # per prompt length
            "chunk": _LruDict(PREFILL_CACHE_CAP),    # per chunk length
        }
    else:
        ENGINE_CACHE_STATS["hits"] += 1
    cache[key] = fns                 # insert or LRU-touch
    return fns


def _prefill_program(model, n: int, quantized: bool):
    """The prefill of an ``n``-token prompt, named ``prefill``."""
    def prefill(p, b):
        return model.prefill(p, b, max_len=n, quantized=quantized)
    return jax.jit(prefill)


def _chunk_program(model, block_size: int):
    """One chunk of a chunked prefill, named ``prefill_chunk``."""
    def prefill_chunk(p, t, s, c, tr):
        return model.paged_prefill_chunk(p, t, s, c, tr,
                                         block_size=block_size)
    return jax.jit(prefill_chunk, donate_argnums=(3,))


def serve_paged(model, params, requests: Sequence[Request], *,
                n_slots: int, block_size: int, num_blocks: int,
                max_prefill_per_step: int = 1, quantized: bool = False,
                greedy: bool = True, seed: int = 0,
                policy: str = "continuous",
                lazy_alloc: bool = False, prefill_chunk: int = 0,
                prefix_share: bool = False, num_swap_blocks: int = 0,
                keep_logits: int = 0,
                options: Optional[CompileOptions] = None) -> dict:
    """Serve ``requests`` with continuous batching over the paged cache.

    ``policy="continuous"`` refills freed slots every decode step (Orca-
    style in-flight batching).  ``policy="static"`` reproduces the seed's
    fixed waves over the *same* compiled kernels: a wave is admitted only
    when every slot is free — and only once enough requests have arrived
    to fill it (or none remain) — then runs to full completion, so the
    measured delta between the two policies is purely scheduling.

    ``lazy_alloc`` admits on prompt-block availability only and grows the
    page table block-by-block during generation; under pool pressure the
    lowest-priority in-flight request is preempted to a host-side swap
    arena (``num_swap_blocks`` blocks, default = ``num_blocks``) with a
    compiled ``paged.swap_out`` copy and re-admitted FCFS with
    ``paged.swap_in``.  ``prefill_chunk`` (a multiple of ``block_size``)
    prefills long prompts that many tokens per engine iteration,
    interleaved with decode steps.  ``prefix_share`` content-hashes
    prompt blocks and maps shared prefixes into multiple page tables
    (refcounted, copy-on-write on the first divergent append).
    ``keep_logits`` copies to the host, into ``Request.logits``, the
    logits row each request's first ``keep_logits`` tokens were sampled
    from (its prefill, then its first decode steps) — what a check
    against a reference model compares.

    Returns a dict with the finished Request objects (tokens + per-token
    emission timestamps relative to the serving clock), decode step
    count, wall time and a ``telemetry`` block (scheduler + allocator +
    jit-cache counters).  Mutates the ``requests`` objects in place.
    """
    cfg = model.cfg
    if policy not in ("continuous", "static"):
        raise ValueError(policy)
    if prefill_chunk and prefill_chunk % block_size:
        raise ValueError(
            f"prefill_chunk ({prefill_chunk}) must be a multiple of "
            f"block_size ({block_size}): non-final chunks must fill "
            f"whole KV blocks")
    requests = sorted(requests, key=lambda r: r.arrival)
    max_ctx = max(r.prompt_len + r.gen_len for r in requests)
    max_blocks = -(-max_ctx // block_size)
    sched = ContinuousScheduler(
        n_slots, BlockAllocator(num_blocks), block_size, max_blocks,
        max_prefill_per_step=(n_slots if policy == "static"
                              else max_prefill_per_step),
        lazy=lazy_alloc,
        prefix_index=PrefixIndex(block_size) if prefix_share else None)
    options = options or CompileOptions()

    with use_options(options):
        pools = model.init_paged_cache(num_blocks, block_size,
                                       quantized=quantized)
        swap_pools = swap_alloc = None
        if lazy_alloc:
            # the preemption tier: a host-side arena of the same block
            # geometry (block 0 reserved, like the pool)
            n_swap = num_swap_blocks or num_blocks
            swap_pools = model.init_paged_cache(n_swap + 1, block_size,
                                                quantized=quantized)
            swap_alloc = BlockAllocator(n_swap + 1)
        table = np.zeros((n_slots, max_blocks), np.int32)
        lengths = np.zeros((n_slots,), np.int32)
        next_tok = np.zeros((n_slots,), np.int32)
        prefilling: dict = {}    # slot -> Request mid-chunked-prefill
        chunk_rr = 0             # round-robin cursor over prefilling

        fns = _engine_fns(model, block_size, quantized, options)
        decode, scatter = fns["decode"], fns["scatter"]
        # prefill/decode disaggregation: prefill is its own compiled
        # program, cached per prompt length (ragged prompts allowed)
        prefill_fns: _LruDict = fns["prefill"]
        chunk_fns: _LruDict = fns["chunk"]

        def run_prefill(req: Request):
            fn = _cached(prefill_fns, req.prompt_len,
                         lambda: _prefill_program(model, req.prompt_len,
                                                  quantized))
            batch = {"tokens": jnp.asarray(req.prompt[None], jnp.int32)}
            return fn(params, batch)

        key = jax.random.PRNGKey(seed)

        def keep(req: Request, row):
            if len(req.tokens) < keep_logits:
                req.logits.append(
                    np.asarray(row, np.float32)[:cfg.vocab_size])

        def sample(logits):
            nonlocal key
            if greedy:
                return jnp.argmax(logits[..., :cfg.vocab_size],
                                  axis=-1).astype(jnp.int32)
            key, sk = jax.random.split(key)
            return jax.random.categorical(
                sk, logits[..., :cfg.vocab_size]).astype(jnp.int32)

        t0 = time.monotonic()

        def clock() -> float:
            return time.monotonic() - t0

        idx = 0            # next not-yet-arrived request
        steps = 0

        def scan_arrivals():
            nonlocal idx
            now = clock()
            while idx < len(requests) and requests[idx].arrival <= now:
                sched.submit(requests[idx])
                idx += 1

        def retire(slot: int, req: Request, now: float):
            sched.finish(slot, now)
            table[slot, :] = 0       # back to the scrap block
            lengths[slot] = 0
            next_tok[slot] = 0

        def swap_out(victim: Request):
            """Evict ``victim`` to the swap arena.  The compiled
            ``paged.swap_out`` copy runs BEFORE the scheduler releases
            the pool blocks — a freed block can be reallocated and
            overwritten by the very next admission."""
            nonlocal swap_pools
            with TraceAnnotation(SPAN_SWAP_OUT, rid=victim.rid):
                try:
                    sids = swap_alloc.alloc(len(victim.blocks))
                except PagePoolExhausted as e:
                    raise PagePoolExhausted(
                        f"swap arena exhausted while preempting request "
                        f"{victim.rid}: {e}; {sched.describe_usage()}"
                    ) from None
                src = np.asarray(victim.blocks, np.int32)
                dst = np.asarray(sids, np.int32)
                for k in swap_pools:
                    swap_pools[k] = cops.page_swap_out(
                        swap_pools[k], pools[k], src, dst,
                        block_size=block_size)
                prefilling.pop(victim.slot, None)
                sched.preempt(victim.slot, sids)

        def swap_in(req: Request):
            """Re-admission of a preempted request: restore its saved
            blocks into the freshly allocated ``req.blocks``."""
            nonlocal pools
            with TraceAnnotation(SPAN_SWAP_IN, rid=req.rid):
                src = np.asarray(req.swap_blocks, np.int32)
                dst = np.asarray(req.blocks, np.int32)
                for k in pools:
                    pools[k] = cops.page_swap_in(
                        pools[k], swap_pools[k], src, dst,
                        block_size=block_size)
                swap_alloc.release(req.swap_blocks)
                req.swap_blocks = []

        def ensure_append_capacity():
            """Before a decode step, make sure every decoding slot owns
            the block its KV append will write: lazily grow across
            block boundaries, fork refcount-shared (CoW) blocks, and —
            under pool pressure — preempt the lowest-priority request
            to the swap tier and retry."""
            nonlocal pools
            for slot in range(n_slots):
                req = sched.active[slot]
                if req is None or slot in prefilling:
                    continue
                while True:
                    try:
                        fork = sched.prepare_append(
                            req, req.stored_positions())
                    except PagePoolExhausted:
                        if swap_alloc is None:
                            raise
                        victim = sched.pick_victim()
                        if victim is None:
                            raise
                        swap_out(victim)
                        if victim is req:
                            break    # the requester itself was evicted
                        continue
                    if fork is not None:
                        src_bid, dst_bid = fork
                        s = np.asarray([src_bid], np.int32)
                        d = np.asarray([dst_bid], np.int32)
                        with TraceAnnotation(SPAN_FORK, rid=req.rid):
                            for k in pools:
                                pools[k] = cops.page_copy(
                                    pools[k], pools[k], s, d,
                                    block_size=block_size)
                    break

        def sync_slots():
            """Rebuild the device-visible page table / lengths / next
            token from scheduler state (the single source of truth):
            lazy growth, CoW forks, preemption and resume all edit
            ``req.blocks`` host-side, and the decode step reads the
            arrays fresh every iteration."""
            for slot in range(n_slots):
                req = sched.active[slot]
                table[slot, :] = 0
                if req is None or slot in prefilling or not req.tokens:
                    lengths[slot] = 0
                    next_tok[slot] = 0
                    continue
                table[slot, :len(req.blocks)] = req.blocks
                lengths[slot] = req.stored_positions()
                next_tok[slot] = req.tokens[-1]

        def advance_chunk():
            """Run one prefill chunk for one mid-prefill slot (round-
            robin).  Mid-prefill slots keep a scrap page-table row in
            the decode step — the chunk program writes through its own
            ``table_row`` — so a shared prompt block can never be
            clobbered by the slot's idle decode appends."""
            nonlocal pools, chunk_rr
            slots = sorted(prefilling)
            slot = slots[chunk_rr % len(slots)]
            chunk_rr += 1
            req = prefilling[slot]
            start = req.prefill_pos
            size = min(prefill_chunk, req.prompt_len - start)
            with TraceAnnotation(SPAN_CHUNK, rid=req.rid, size=size):
                fn = _cached(chunk_fns, size,
                             lambda: _chunk_program(model, block_size))
                row = np.zeros((max_blocks,), np.int32)
                row[:len(req.blocks)] = req.blocks
                logits, pools = fn(
                    params,
                    jnp.asarray(req.prompt[start:start + size], jnp.int32),
                    jnp.asarray(start, jnp.int32), pools, jnp.asarray(row))
                req.prefill_pos += size
                if req.prefill_pos < req.prompt_len:
                    return
                del prefilling[slot]  # prompt fully cached: start decode
                keep(req, logits)
                tok = int(np.asarray(sample(logits)))
                req.tokens.append(tok)
                req.token_times.append(clock())
                if req.done:          # gen_len == 1: prefill was enough
                    retire(slot, req, clock())

        def iteration():
            """One pass of the engine loop: admissions and their
            prefills, one prefill chunk, then one decode step over every
            decodable slot; each phase in its own span."""
            nonlocal pools, steps
            with TraceAnnotation(SPAN_ADMIT):
                scan_arrivals()
                if policy == "static" and (
                        sched.n_active > 0
                        or (len(sched.pending) < n_slots
                            and idx < len(requests))):
                    admitted = []    # wave barrier: wait to fill / drain
                else:
                    admitted = sched.admit(clock())
            for slot, req in admitted:
                if req.swap_blocks:  # resumed from the swap tier
                    swap_in(req)
                    if not req.tokens:
                        prefilling[slot] = req   # preempted mid-prefill
                    continue
                if prefill_chunk and req.prompt_len > prefill_chunk:
                    prefilling[slot] = req       # chunked: interleaved
                    continue
                with TraceAnnotation(SPAN_PREFILL, rid=req.rid,
                                     prompt_len=req.prompt_len):
                    logits, cache = run_prefill(req)
                    pools = scatter(pools, cache["kv"],
                                    jnp.asarray(req.blocks, jnp.int32))
                    keep(req, logits[0])
                    tok = int(np.asarray(sample(logits[0])))
                    req.tokens.append(tok)
                    req.token_times.append(clock())
                    req.prefill_pos = req.prompt_len
                    if req.done:     # gen_len == 1: prefill was enough
                        retire(slot, req, clock())
            if prefilling:
                # chunked prefill: one chunk per engine iteration,
                # interleaved with the decode step below so one long
                # prompt cannot stall every in-flight decode
                advance_chunk()
            decodable = sum(
                1 for s in range(n_slots)
                if sched.active[s] is not None and s not in prefilling)
            if decodable == 0:
                if sched.n_active == 0 and not prefilling \
                        and idx < len(requests):
                    # idle until the next arrival (open-loop load; the
                    # static policy also waits here for its wave)
                    with TraceAnnotation(SPAN_IDLE):
                        time.sleep(max(requests[idx].arrival - clock(),
                                       0.0))
                return
            with TraceAnnotation(SPAN_PREPARE):
                ensure_append_capacity()
                sync_slots()
                tok_in = jnp.asarray(next_tok)
                table_in = jnp.asarray(table)
                lengths_in = jnp.asarray(lengths)
            # async dispatch: the decode step is in flight on the device
            # while the host scans arrivals and plans admissions below
            with TraceAnnotation(SPAN_DISPATCH, batch=decodable):
                logits, pools = decode(params, tok_in, pools, table_in,
                                       lengths_in)
                tok_dev = sample(logits)
            steps += 1
            with TraceAnnotation(SPAN_SCAN):
                scan_arrivals()      # overlapped host-side scheduling
            with TraceAnnotation(SPAN_READBACK):
                tok_host = np.asarray(jax.block_until_ready(tok_dev))
            with TraceAnnotation(SPAN_EMIT):
                t_emit = clock()
                rows = None
                for slot in range(n_slots):
                    req = sched.active[slot]
                    if req is None or slot in prefilling:
                        continue     # inactive slots appended to scrap
                    if len(req.tokens) < keep_logits:
                        if rows is None:
                            rows = np.asarray(logits, np.float32)
                        keep(req, rows[slot])
                    req.tokens.append(int(tok_host[slot]))
                    req.token_times.append(t_emit)
                    if req.done:
                        retire(slot, req, t_emit)

        while sched.has_work() or idx < len(requests):
            with TraceAnnotation(SPAN_ITERATION, step=steps):
                iteration()

    total_tokens = sum(len(r.tokens) for r in requests)
    telemetry = sched.telemetry()
    telemetry["allocator"] = sched.allocator.telemetry()
    if swap_alloc is not None:
        telemetry["swap"] = swap_alloc.telemetry()
    telemetry["engine_cache"] = dict(ENGINE_CACHE_STATS)
    return {"requests": list(requests), "steps": steps,
            "tokens": total_tokens, "seconds": clock(),
            "tok_per_s": total_tokens / max(clock(), 1e-9),
            "telemetry": telemetry}


_CLI_EPILOG = """\
paged serving (--paged) and --quantized-kv:
  The paged engine backs decode with fixed-size KV blocks from a shared
  pool (--num-blocks x --block-size positions per layer), indexed by a
  per-slot page table; gather/append lower through the kokkos.* pipeline
  (see `python -m repro.core.pipeline --demo paged --print-ir`).

  --quantized-kv composes with the paged layout: the int8 K/V pools get
  sibling fp32 scale pools of the SAME block geometry (one scale per
  stored position, head-dim 1) — i.e. the scales live per block and ride
  the same page table, so freeing a request's blocks frees its scales.
  Token streams match the quantized contiguous cache exactly (regression-
  tested in tests/test_serve_paged.py); EXPERIMENTS.md §Perf numbers for
  --quantized-kv therefore carry over to --paged serving unchanged.

policies:
  --policy continuous   refill finished slots every decode step
                        (in-flight batching; the default)
  --policy static       the seed's fixed waves: admit a full wave, run
                        until every request in it finishes (baseline)

allocation and prefill (--paged):
  --lazy-alloc          admit a request once its PROMPT blocks fit
                        (instead of reserving prompt+gen up front) and
                        grow the page table one block at a time during
                        generation.  Pool pressure preempts the lowest-
                        priority in-flight request to a host-side swap
                        arena (--num-swap-blocks, default --num-blocks)
                        via compiled paged.swap_out / paged.swap_in
                        block copies; it re-enters the queue FCFS.
  --prefill-chunk N     split prompts longer than N into N-token prefill
                        chunks (N must be a multiple of --block-size),
                        interleaved one chunk per decode step, so a long
                        prompt cannot stall in-flight decodes.
  --prefix-share        content-hash prompt blocks and map shared
                        prefixes into multiple page tables (refcounted);
                        the first divergent append forks the block with
                        a compiled copy-on-write paged.copy.

  All of it stays compiled IR: swap and fork lower through the
  paged_to_kokkos pass to kokkos.page_copy (direction=copy|swap_out|
  swap_in) — `python -m repro.core.pipeline --demo paged_swap
  --print-ir` shows the nests, lapis-translate emits the C++.
"""


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        epilog=_CLI_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--arch", default="qwen2-1.5b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--batch", "--slots", dest="batch", type=int, default=4,
                   help="decode slots (batch rows) served in lock-step")
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen-len", type=int, default=16)
    p.add_argument("--quantized-kv", action="store_true",
                   help="int8 KV cache (+ per-block scale pools when "
                        "--paged; see epilog)")
    p.add_argument("--sample", action="store_true",
                   help="sample instead of greedy argmax decode")
    p.add_argument("--seed", type=int, default=0,
                   help="root PRNG seed for weights, prompts and sampling")
    p.add_argument("--paged", action="store_true",
                   help="serve with the continuous-batching engine over "
                        "the block-paged KV cache (see epilog)")
    p.add_argument("--policy", default="continuous",
                   choices=("continuous", "static"),
                   help="slot refill policy for --paged (see epilog)")
    p.add_argument("--block-size", type=int, default=16,
                   help="KV block size (positions per page) for --paged")
    p.add_argument("--num-blocks", type=int, default=0,
                   help="shared pool size for --paged (0 = sized to fit "
                        "all slots + one spare request)")
    p.add_argument("--max-prefill-per-step", type=int, default=1,
                   help="admissions between decode steps (bounds the "
                        "decode stall a burst of prefills can cause)")
    p.add_argument("--lazy-alloc", action="store_true",
                   help="admit on prompt-block availability and grow "
                        "page tables during generation; preempt to a "
                        "swap arena under pool pressure (see epilog)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked prefill size in tokens (multiple of "
                        "--block-size; 0 = monolithic prefill)")
    p.add_argument("--prefix-share", action="store_true",
                   help="copy-on-write sharing of common prompt-prefix "
                        "blocks across requests (see epilog)")
    p.add_argument("--num-swap-blocks", type=int, default=0,
                   help="swap arena size for --lazy-alloc preemption "
                        "(0 = same as --num-blocks)")
    p.add_argument("--ragged", action="store_true",
                   help="draw ragged prompt/gen lengths per request")
    p.add_argument("--arrival-rate", type=float, default=None,
                   help="Poisson arrival rate (requests/s); default: all "
                        "requests arrive at t=0")
    args = p.parse_args(argv)
    print(env.device_line())
    env.enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    params = steps_mod.cast_compute(model.init(args.seed), cfg.compute_dtype)
    if args.paged:
        reqs = make_requests(args.requests, prompt_len=args.prompt_len,
                             gen_len=args.gen_len, vocab=cfg.vocab_size,
                             seed=args.seed, ragged=args.ragged,
                             arrival_rate=args.arrival_rate)
        blocks_per_req = -(-(args.prompt_len + args.gen_len)
                           // args.block_size)
        num_blocks = args.num_blocks or \
            1 + blocks_per_req * (args.batch + 1)
        out = serve_paged(model, params, reqs, n_slots=args.batch,
                          block_size=args.block_size,
                          num_blocks=num_blocks,
                          max_prefill_per_step=args.max_prefill_per_step,
                          quantized=args.quantized_kv,
                          greedy=not args.sample, seed=args.seed,
                          policy=args.policy,
                          lazy_alloc=args.lazy_alloc,
                          prefill_chunk=args.prefill_chunk,
                          prefix_share=args.prefix_share,
                          num_swap_blocks=args.num_swap_blocks)
        print(f"[serve:{args.policy}] {len(out['requests'])} requests, "
              f"{out['tokens']} tokens in {out['steps']} decode steps, "
              f"{out['tok_per_s']:.1f} tok/s")
        return 0
    out = serve_loop(model, params, n_requests=args.requests,
                     batch=args.batch, prompt_len=args.prompt_len,
                     gen_len=args.gen_len, quantized=args.quantized_kv,
                     greedy=not args.sample, seed=args.seed)
    print(f"[serve] {out['requests']} requests, {out['tokens']} tokens, "
          f"{out['tok_per_s']:.1f} tok/s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
