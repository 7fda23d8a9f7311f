"""Smoke check on one TPU chip: the system's two main paths, end to end.

  python chip_smoke.py [--seed N]

1. Serving: the continuous paged engine (``serve_paged``, the code behind
   ``python -m repro.launch.serve --paged``) serves full-width qwen2-1.5b
   with bf16 weights drawn from ``--seed`` under the default
   ``CompileOptions()``, so on a TPU the ``auto`` target puts the Pallas
   flash-attention, decode-attention, rmsnorm and page-gather kernels on
   the path.
2. Correctness: each request's prefill logits and first-decode logits
   against two references over the same weights — the ``xla`` target in
   bf16 (isolates the Pallas kernels and the paged cache) and a float32
   ``jax.numpy`` reference (the ``xla`` target, f32 weights and compute,
   f32 matmul precision).
3. Compiler path: ``pipeline.compile`` on ``target="pallas"`` against
   ``target="xla"`` for a gemm-bearing MLP and for SpMV over a matrix with
   the row count and row-length statistics of StocF-1465 (Table 6.1).

Every phase also checks, in the compiled HLO, that each Pallas kernel it
expects is a ``tpu_custom_call``: a kernel that resolved interpret mode,
or an op that fell back to the library or reference path, leaves none.

Without a TPU, or where the program cannot be imported, the script exits
non-zero and prints no result.  Otherwise the last line of stdout is one
JSON object, ``{"ok": true, "device": {...}}``; it exits 1 after printing
``"ok": false`` when any check fails.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

# Logits bounds: max |engine - reference| over a request's vocab row,
# divided by max |reference| over that row.  Fixed from a reduced-width
# CPU rehearsal of the same comparison (see PERF.md); a wrong mask, head
# mapping or page lands at O(1).
BOUND_VS_XLA_BF16 = 0.06
BOUND_VS_F32 = 0.08
PIPELINE_RTOL = 1e-4          # pallas vs xla target, f32

SERVE = dict(arch="qwen2-1.5b", requests=16, prompt_len=512, gen_len=64,
             slots=8, block_size=16)
MLP_SHAPE = (2048, 4096, 4096)        # x (M, D), w1 (D, H), w2 (H, D)
STOCF_1465 = dict(rows=1_465_137, nnz_mean=14.34, nnz_max=189)

DECODE_KERNELS = ("decode_attention", "page_gather", "rmsnorm")
PREFILL_KERNELS = ("flash_attention", "rmsnorm")


class CompileClock:
    """Seconds XLA spent compiling (or fetching from the persistent
    cache) inside the ``with`` block, and the persistent-cache hits."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, monitoring):
        self._mon = monitoring
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == self.BACKEND_COMPILE:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self):
        self._mon.register_event_duration_secs_listener(self._on_duration)
        self._mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)


def rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)),
                                                 1e-30))


def serve_phase(cfg, *, seed: int, requests: int, prompt_len: int,
                gen_len: int, slots: int, block_size: int,
                options=None) -> dict:
    """Serve ragged requests through ``serve_paged``; compare each one's
    first two logits rows with both references.  ``options=None`` is the
    engine's own default."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.options import CompileOptions, use_options
    from repro.launch import steps as steps_mod
    from repro.launch.hlo import kernel_calls
    from repro.launch.serve import make_requests, serve_paged
    from repro.models.model import build_model

    def peak_bytes():
        return (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")

    model = build_model(cfg)
    params = steps_mod.cast_compute(model.init(seed), cfg.compute_dtype)
    peak_after_init = peak_bytes()
    reqs = make_requests(requests, prompt_len=prompt_len, gen_len=gen_len,
                         vocab=cfg.vocab_size, seed=seed, ragged=True)
    blocks_per_req = -(-(prompt_len + gen_len) // block_size)
    num_blocks = 1 + blocks_per_req * (slots + 1)
    with CompileClock(jax.monitoring) as clock:
        t0 = time.monotonic()
        out = serve_paged(model, params, reqs, n_slots=slots,
                          block_size=block_size, num_blocks=num_blocks,
                          seed=seed, keep_logits=2, options=options)
        wall = time.monotonic() - t0
    res = {"requests": len(out["requests"]), "tokens": out["tokens"],
           "decode_steps": out["steps"], "wall_s": wall,
           "compile_s": clock.seconds, "cache_hits": clock.cache_hits,
           "peak_bytes_after_init": peak_after_init,
           "peak_bytes_after_serve": peak_bytes()}

    # the decode and prefill programs, compiled as the engine compiles
    # them (same functions, same options), for their custom calls
    engine_options = options or CompileOptions()
    pools = jax.eval_shape(lambda: model.init_paged_cache(num_blocks,
                                                          block_size))
    max_blocks = -(-max(r.prompt_len + r.gen_len for r in reqs)
                   // block_size)
    i32 = jnp.int32
    with use_options(engine_options):
        decode = jax.jit(model.paged_decode_step,
                         static_argnames="block_size").lower(
            params, jax.ShapeDtypeStruct((slots,), i32), pools,
            jax.ShapeDtypeStruct((slots, max_blocks), i32),
            jax.ShapeDtypeStruct((slots,), i32),
            block_size=block_size).compile()
        prefill = jax.jit(lambda p, b: model.prefill(
            p, b, max_len=prompt_len)).lower(
            params, {"tokens": jax.ShapeDtypeStruct((1, prompt_len),
                                                    i32)}).compile()
    res["decode_kernels"] = kernel_calls(decode.as_text())
    res["prefill_kernels"] = kernel_calls(prefill.as_text())
    del decode, prefill

    # references over one padded sequence per request: prompt + the
    # engine's first token; causal attention makes rows P-1 and P the
    # prefill and first-decode logits whatever follows them
    length = prompt_len + 1
    rows = {r.rid: r for r in out["requests"]}

    def ref_rows(ref_model, ref_params, precision=None):
        fwd = jax.jit(lambda p, t, at: jax.lax.dynamic_slice_in_dim(
            ref_model.forward(p, {"tokens": t})[0][0], at, 2, axis=0)
            [:, :cfg.vocab_size].astype(jnp.float32))
        got = {}
        with use_options(CompileOptions(target="xla")), \
                (jax.default_matmul_precision(precision) if precision
                 else contextlib.nullcontext()):
            for rid, r in rows.items():
                toks = np.zeros((1, length), np.int32)
                seq = np.concatenate([r.prompt, r.tokens[:1]])
                toks[0, :len(seq)] = seq
                got[rid] = np.asarray(fwd(ref_params, toks,
                                          np.int32(r.prompt_len - 1)))
        return got

    ref_bf16 = ref_rows(model, params)
    del params
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    model32 = build_model(cfg32)
    ref_f32 = ref_rows(model32, model32.init(seed), "highest")

    errs = {"xla_bf16": [0.0, 0.0], "f32": [0.0, 0.0]}
    compared = [0, 0]
    for rid, r in rows.items():
        for i, row in enumerate(r.logits[:2]):
            compared[i] += 1
            for key, ref in (("xla_bf16", ref_bf16), ("f32", ref_f32)):
                errs[key][i] = max(errs[key][i], rel_err(row, ref[rid][i]))
    res["compared"] = {"prefill": compared[0], "first_decode": compared[1]}
    res["err_vs_xla_bf16"] = {"prefill": errs["xla_bf16"][0],
                              "first_decode": errs["xla_bf16"][1]}
    res["err_vs_f32"] = {"prefill": errs["f32"][0],
                         "first_decode": errs["f32"][1]}
    return res


def stocf_like_csr(rows: int, nnz_mean: float, nnz_max: int, seed: int):
    """A CSR matrix with StocF-1465's row count and row-length statistics
    (mean and max nonzeros per row), columns in a band around the
    diagonal as in a FEM matrix.  Returns (indptr, indices, values)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.poisson(nnz_mean - 1.0, rows).astype(np.int32) + 1
    lens[rng.integers(rows)] = nnz_max
    lens = np.minimum(lens, nnz_max)
    indptr = np.zeros(rows + 1, np.int32)
    np.cumsum(lens, out=indptr[1:])
    nnz = int(indptr[-1])
    row_of = np.repeat(np.arange(rows, dtype=np.int64), lens)
    band = rng.integers(-4096, 4097, nnz)
    indices = ((row_of + band) % rows).astype(np.int32)
    values = rng.standard_normal(nnz, dtype=np.float32)
    return indptr, indices, values


def compiler_phase(*, seed: int, mlp_shape, stocf: dict,
                   options_for) -> dict:
    """``pipeline.compile`` on pallas against xla: a gemm-bearing MLP
    and an SpMV with a static ``max_nnz_row`` (so the ELL kernel runs,
    not ``spmv_reference``).  ``options_for(target)`` gives the
    CompileOptions per target."""
    import jax
    import numpy as np

    from repro.core import ops, pipeline
    from repro.launch.hlo import kernel_calls

    rng = np.random.default_rng(seed)
    m, d, h = mlp_shape
    mlp_args = (rng.standard_normal((m, d), dtype=np.float32),
                rng.standard_normal((d, h), dtype=np.float32)
                * np.float32(d ** -0.5),
                rng.standard_normal((m, h), dtype=np.float32),
                rng.standard_normal((h, d), dtype=np.float32)
                * np.float32(h ** -0.5))

    def mlp(x, w1, b1, w2):
        return ops.matmul(ops.relu(ops.add(ops.matmul(x, w1), b1)), w2)

    n = stocf["rows"]
    indptr, indices, values = stocf_like_csr(
        n, stocf["nnz_mean"], stocf["nnz_max"], seed)
    max_nnz_row = int(np.max(np.diff(indptr)))
    spmv_args = (indptr, indices, values,
                 rng.standard_normal(n, dtype=np.float32))

    def spmv(ip, ind, val, x):
        return ops.spmv_csr(ip, ind, val, x, n_rows=n,
                            max_nnz_row=max_nnz_row)

    res = {"mlp_bytes": int(sum(a.nbytes for a in mlp_args)),
           "spmv": {"rows": n, "nnz": int(indptr[-1]),
                    "nnz_mean": float(indptr[-1]) / n,
                    "max_nnz_row": max_nnz_row}}
    for name, fn, args in (("mlp", mlp, mlp_args),
                           ("spmv", spmv, spmv_args)):
        outs = {}
        for target in ("pallas", "xla"):
            dev_args = [jax.device_put(a) for a in args]
            # f32 semantics on both sides: XLA's default TPU matmul
            # precision would round operands to bf16
            with jax.default_matmul_precision("highest"):
                t0 = time.monotonic()
                mod = pipeline.compile(fn, *args,
                                       options=options_for(target),
                                       name=name)
                outs[target] = np.asarray(mod(*dev_args))
                t1 = time.monotonic()
                np.asarray(mod(*dev_args))
                res[f"{name}_{target}_s"] = {
                    "compile_and_first_call": t1 - t0,
                    "second_call": time.monotonic() - t1}
                if target == "pallas":
                    hlo = jax.jit(mod.forward).lower(
                        *dev_args).compile().as_text()
                    res[f"{name}_kernels"] = kernel_calls(hlo)
            del mod, dev_args
        res[f"{name}_err"] = rel_err(outs["pallas"], outs["xla"])
        res[f"{name}_shape"] = list(outs["xla"].shape)
    return res


def _check(failures: list, ok: bool, what: str) -> None:
    if not ok:
        failures.append(what)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="seed for weights, requests and compiler inputs")
    args = p.parse_args(argv)

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU: JAX found no devices ({e})",
              file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is "
              f"{devices[0].platform!r} ({devices[0].device_kind}); this "
              f"check runs only on a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.configs import get_config
        from repro.core.options import CompileOptions
        from repro.launch import env
    except ImportError as e:
        print(f"chip_smoke: cannot import the program from "
              f"{ROOT / 'src'}: {e}", file=sys.stderr)
        return 2

    device = env.device_info()
    print(env.device_line(), flush=True)
    print(f"[cache] {env.enable_compile_cache()}", flush=True)
    failures: list = []
    _check(failures, CompileOptions().resolve_interpret() is False,
           "default CompileOptions resolves interpret mode on the TPU")

    t0 = time.monotonic()
    try:
        cfg = get_config(SERVE["arch"])
        res = serve_phase(cfg, seed=args.seed,
                          **{k: v for k, v in SERVE.items() if k != "arch"})
        print(f"[serve] {json.dumps(res)}", flush=True)
        for kind, want, got in (
                ("decode", DECODE_KERNELS, res["decode_kernels"]),
                ("prefill", PREFILL_KERNELS, res["prefill_kernels"])):
            for k in want:
                _check(failures, got.get(k, 0) > 0,
                       f"{kind} step has no {k} tpu_custom_call")
        _check(failures, res["tokens"] > 0, "no tokens served")
        _check(failures, res["compared"]["prefill"] == SERVE["requests"],
               "a request has no prefill logits")
        _check(failures, res["compared"]["first_decode"] > 0,
               "no request reached a decode step")
        for ref, bound in (("xla_bf16", BOUND_VS_XLA_BF16),
                           ("f32", BOUND_VS_F32)):
            for row, err in res[f"err_vs_{ref}"].items():
                print(f"[serve] {row} logits vs {ref}: max|d|/max|ref| = "
                      f"{err!r} (bound {bound})", flush=True)
                _check(failures, err <= bound,
                       f"{row} logits vs {ref}: {err!r} > {bound}")
        peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
        print(f"[serve] tokens={res['tokens']} "
              f"compile_s={res['compile_s']!r} peak_bytes_in_use={peak} "
              f"decode tpu_custom_call="
              f"{sum(res['decode_kernels'].values())} "
              f"phase_s={time.monotonic() - t0!r}", flush=True)
    except Exception:  # noqa: BLE001 — report the phase, run the next
        traceback.print_exc()
        failures.append("serve phase raised")
    gc.collect()

    t0 = time.monotonic()
    try:
        res = compiler_phase(
            seed=args.seed, mlp_shape=MLP_SHAPE, stocf=STOCF_1465,
            options_for=lambda t: CompileOptions(target=t, interpret=False))
        print(f"[compiler] {json.dumps(res)}", flush=True)
        for name, kernel in (("mlp", "matmul"), ("spmv", "spmv_ell")):
            _check(failures, res[f"{name}_kernels"].get(kernel, 0) > 0,
                   f"pallas {name} has no {kernel} tpu_custom_call")
            print(f"[compiler] {name} pallas vs xla: max|d|/max|ref| = "
                  f"{res[name + '_err']!r} (bound {PIPELINE_RTOL})",
                  flush=True)
            _check(failures, res[f"{name}_err"] <= PIPELINE_RTOL,
                   f"{name}: pallas vs xla {res[name + '_err']!r}")
        print(f"[compiler] phase_s={time.monotonic() - t0!r}", flush=True)
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        failures.append("compiler phase raised")

    for f in failures:
        print(f"FAIL: {f}", flush=True)
    print(json.dumps({"ok": not failures, "device": device}))
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
