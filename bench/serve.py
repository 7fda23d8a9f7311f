"""Runner for configurations served by the program's paged engine
(``"runner": "serve"`` in the configuration's file).

One run: weights made on the device from the seed, the cell's shapes
warmed up, one measured window of the mix's traffic through
``repro.launch.serve.serve_paged`` with the default ``CompileOptions()``,
then the check of the served tokens against ``bench/reference/qwen2.py``.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
import zlib
from typing import Optional

import numpy as np

from bench import common, stats, traffic
from bench.reference import qwen2 as ref

# what the program's config must say for the configuration's file to be
# what it serves: program field -> file key
PROGRAM_FIELDS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
                  "n_heads": "num_attention_heads",
                  "n_kv_heads": "num_key_value_heads",
                  "d_ff": "intermediate_size", "vocab_size": "vocab_size",
                  "head_dim": "head_dim", "rope_theta": "rope_theta",
                  "tie_embeddings": "tie_word_embeddings"}


def program_model(cfg: dict):
    """The program's model for the configuration's file; refuses a
    program config whose sizes differ from the file's."""
    from repro.configs import get_config
    from repro.models.model import build_model
    # depth is the one cut a configuration may make of the program's
    pc = dataclasses.replace(
        get_config(cfg["program_config"],
                   reduced=cfg.get("program_reduced", False)),
        n_layers=cfg["num_hidden_layers"],
        compute_dtype=cfg["served_dtype"])
    bad = {f: (getattr(pc, f), cfg[k]) for f, k in PROGRAM_FIELDS.items()
           if k in cfg and getattr(pc, f) != cfg[k]}
    if bad or not pc.qkv_bias or pc.norm != "rmsnorm" or pc.act != "silu":
        raise ValueError(f"program config {cfg['program_config']!r} is "
                         f"not the configuration's file: {bad}")
    return build_model(pc)


def make_weights(cfg: dict, padded_vocab: int, seed: int):
    """The served weights, made on the device from the seed in one
    jitted call, in the served dtype.  Matrices N(0, 1/fan_in), the
    embedding N(0, 0.02) with the padded rows zero, biases N(0, 0.1),
    norm scales N(1, 0.1)."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(cfg["served_dtype"])
    tree = ref.shapes(cfg, padded_vocab)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    names = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in paths]
    shapes = [s for _, s in paths]

    def leaf(key, name, shape):
        z = jax.random.normal(key, shape, jnp.float32)
        if name == "embed/table":
            z = z * 0.02
            z = jnp.where(jnp.arange(shape[0])[:, None] < cfg["vocab_size"],
                          z, 0.0)
        elif name.endswith("scale"):
            z = 1.0 + 0.1 * z
        elif len(shape) == 2 and name.startswith("layers/"):   # biases
            z = 0.1 * z
        else:
            z = z * (1.0 / shape[-2]) ** 0.5
        return z.astype(dt)

    @jax.jit
    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(jax.random.fold_in(key, zlib.crc32(n.encode())), n, s)
            for n, s in zip(names, shapes)])

    return make(jax.random.key(seed))


def check_layout(weights, model) -> None:
    """The benchmark's weight tree has the program's layout exactly."""
    import jax
    want = jax.tree_util.tree_map(lambda a: tuple(a.shape),
                                  model.abstract())
    got = jax.tree_util.tree_map(lambda a: tuple(a.shape), weights)
    if want != got:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got} vs {want}")


@dataclasses.dataclass
class Served:
    """What a window served, on one clock (seconds from the window's
    start)."""

    requests: list              # the program's Request objects
    decode_steps: int
    end: float                  # last request finished
    seconds: float              # the measured window
    tokens_in_window: int
    compiles: int
    trace_window: Optional[tuple] = None   # (start, end) on this clock


def _requests(specs, first_rid: int):
    from repro.runtime.scheduler import Request
    return [Request(rid=first_rid + i, prompt=s.prompt, gen_len=s.gen_len,
                    arrival=s.arrival)
            for i, s in enumerate(specs)]


def _shift(reqs, dt: float) -> None:
    for r in reqs:
        r.arrival += dt
        r.token_times = [t + dt for t in r.token_times]
        if r.admitted_at is not None:
            r.admitted_at += dt
        if r.finished_at is not None:
            r.finished_at += dt


class Engine:
    """The program's engine with this cell's settings."""

    def __init__(self, model, weights, mix: dict, widest: int):
        from repro.core.options import CompileOptions
        e = mix["engine"]
        self.model, self.weights, self.e = model, weights, e
        # every slot can hold the widest context at once: the pool never
        # runs out, so nothing is preempted and nothing is swapped
        self.num_blocks = 1 + e["slots"] * -(-widest // e["block_size"])
        self.options = CompileOptions()

    def serve(self, reqs, seed: int) -> dict:
        from repro.launch import serve as serve_mod
        e = self.e
        return serve_mod.serve_paged(
            self.model, self.weights, reqs, n_slots=e["slots"],
            block_size=e["block_size"], num_blocks=self.num_blocks,
            max_prefill_per_step=e.get("max_prefill_per_step", 1),
            lazy_alloc=e.get("lazy_alloc", False), num_swap_blocks=1,
            seed=seed, options=self.options)


def warm_up(engine: Engine, mix: dict, seconds: float, vocab: int) -> None:
    """Compile every shape the window will use: one request per prompt
    bucket of the mix, the longest reaching the widest context."""
    rng = np.random.default_rng(0)
    specs = [traffic.Spec(arrival=0.0,
                          prompt=rng.integers(1, vocab, p).astype(np.int32),
                          gen_len=g)
             for p, g in traffic.warmup_sizes(mix, seconds)]
    engine.serve(_requests(specs, 0), seed=0)


class _TraceTimer(threading.Thread):
    """Starts the profiler ``start`` seconds into the window and stops it
    ``length`` seconds later, inside a ``bench.traced`` span."""

    def __init__(self, t0: float, start: float, length: float, out: str):
        super().__init__(daemon=True)
        self.t0, self.start_at, self.length, self.out = t0, start, length, out
        self.window: Optional[tuple] = None

    def run(self):
        import jax
        time.sleep(max(self.t0 + self.start_at - time.monotonic(), 0.0))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out, profiler_options=opts)
        a = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.traced"):
            time.sleep(self.length)
        b = time.monotonic()
        jax.profiler.stop_trace()
        self.window = (a - self.t0, b - self.t0)


def run_window(engine: Engine, mix: dict, seed: int, seconds: float,
               vocab: int, trace_dir: Optional[str]) -> Served:
    """The measured window: one engine call whose open-loop arrivals
    fill ``[0, seconds)``; every request is waited for."""
    import jax
    tr = mix.get("trace", {})
    with common.CompileCounter() as cc:
        t0 = time.monotonic()
        timer = None
        if trace_dir:
            timer = _TraceTimer(t0, tr.get("start_s", 0.0),
                                tr.get("seconds", seconds), trace_dir)
            timer.start()
        with jax.profiler.TraceAnnotation("bench.window"):
            reqs = _requests(traffic.open_loop(mix, seed, seconds, vocab), 0)
            with jax.profiler.TraceAnnotation("bench.serve_paged"):
                out = engine.serve(reqs, seed)
            done = time.monotonic()
            # onto the window's clock: the engine's starts when its loop
            # does, and it reports how long that ran
            _shift(reqs, done - out["seconds"] - t0)
        end = time.monotonic() - t0
        if timer is not None:
            timer.join()
    return Served(requests=reqs, decode_steps=out["steps"], end=end,
                  seconds=seconds,
                  tokens_in_window=stats.tokens_in(reqs, 0.0, seconds),
                  compiles=cc.count,
                  trace_window=timer.window if timer else None)


def end_to_end(served: Served) -> dict:
    """The cell's end-to-end serving metrics, over every request and every
    token gap of the window.  ``ttft_p95_ms`` is read too, for the
    sweep and the readings, but no cell holds a bound on it (PERF.md)."""
    reqs = served.requests
    return {
        "output_tok_s": stats.rate(served.tokens_in_window, served.seconds),
        "ttft_p95_ms": 1e3 * stats.percentile(stats.ttfts(reqs), 95),
        "tbt_p95_ms": 1e3 * stats.percentile(stats.token_gaps(reqs), 95),
    }


def failures(reqs, vocab: int) -> int:
    """Requests that did not get exactly their output, in range."""
    return sum(1 for r in reqs
               if len(r.tokens) != r.gen_len
               or any(not 0 <= t < vocab for t in r.tokens))


def sample(reqs, seed: int, n: int) -> list:
    """The requests the check compares: the longest finished one and
    ``n - 1`` more drawn from the seed."""
    done = [r for r in reqs if len(r.tokens) == r.gen_len]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.gen_len, -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _padded(seq: np.ndarray) -> np.ndarray:
    n = ref.PAD * max(1, math.ceil(len(seq) / ref.PAD))
    out = np.zeros(n, np.int32)
    out[:len(seq)] = seq
    return out


def sequences(reqs) -> list:
    """Per request: (the prompt and the served tokens but the last,
    padded; the served token at each position; the compared slice)."""
    out = []
    for r in reqs:
        toks = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), toks[:-1]])
        served = np.zeros(len(seq), np.int32)
        lo = r.prompt_len - 1
        served[lo:lo + len(toks)] = toks
        out.append((_padded(seq), _padded(served),
                    slice(lo, lo + len(toks))))
    return out


def widest_gap(weights, cfg: dict, reqs) -> tuple:
    """(widest gap by which a served token's reference logit lies below
    the reference's best, tokens compared)."""
    items = ref.cfg_items(cfg)
    widest, n = 0.0, 0
    for seq, served, sl in sequences(reqs):
        g = np.asarray(ref.served_gaps(weights, seq, served, items))[sl]
        widest, n = max(widest, float(g.max())), n + len(g)
    return widest, n


def control_gap(weights, cfg: dict, reqs, quant: str = "fp8") -> float:
    """The same reading for the control: at each compared position, the
    token that the reference at ``quant`` precision puts first."""
    items = ref.cfg_items(cfg)
    widest = 0.0
    for seq, _, sl in sequences(reqs):
        g = np.asarray(ref.control_gaps(weights, seq, items, quant))[sl]
        widest = max(widest, float(g.max()))
    return widest


def set_up(cell: common.Cell, seed: int, seconds: float) -> Engine:
    """The engine with the seed's weights and every shape warmed up."""
    cfg, mix = cell.config, cell.traffic
    model = program_model(cfg)
    weights = make_weights(cfg, model.cfg.padded_vocab, seed)
    check_layout(weights, model)
    widest = max(p + g for p, g in traffic.warmup_sizes(mix, seconds))
    engine = Engine(model, weights, mix, widest)
    warm_up(engine, mix, seconds, cfg["vocab_size"])
    return engine


def checks(cell: common.Cell, weights, served: Served, seed: int):
    """(failed requests, the numbers compared with their limits).  Runs
    after the window, once the engine's pool has died with its call."""
    mix, cfg = cell.traffic, cell.config
    failed = failures(served.requests, cfg["vocab_size"])
    picked = sample(served.requests, seed, mix["check"]["sample"])
    gap, n = widest_gap(weights, cfg, picked)
    limit = mix["check"]["widest_logit_gap"]
    return failed, [
        {"name": "failed_requests", "value": failed, "limit": 0,
         "ok": failed == 0},
        {"name": "compared_tokens", "value": n,
         "limit": mix["check"]["min_compared_tokens"],
         "ok": n >= mix["check"]["min_compared_tokens"]},
        {"name": "widest_logit_gap", "value": gap, "limit": limit,
         "ok": gap <= limit}]
