"""Record the small chip traces that ``bench/tests`` reduce on the CPU.

  python3 -m bench.tools.record_trace --out bench/tests/data

Runs on a TPU only.  Two short traced sections, each in its own
``.xplane.pb``: a two-layer cut of qwen2-1.5b (published widths) served
through ``serve_paged`` with the default options (Pallas flash attention,
decode attention, rmsnorm and page gather), and a small SpMV compiled by
``pipeline.compile`` on ``pallas``.  The benchmark's own spans
(``bench.*``) sit around the calls, as in a benchmark run.  Prints each
plane's lines with a few events, so the layout of a chip trace can be
read by eye.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import os
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _summarise(path: str) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:6]:
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                      f"stats={dict(e.stats)}")


def _traced(out_dir: pathlib.Path, name: str, fn) -> str:
    import jax
    tmp = out_dir / f"_{name}"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    src = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)[0]
    dst = out_dir / f"{name}.xplane.pb"
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"[trace] {dst} {os.path.getsize(dst)} bytes", flush=True)
    return str(dst)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", default="bench/tests/data")
    args = p.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import ops, pipeline
    from repro.core.options import CompileOptions
    from repro.launch import steps as steps_mod
    from repro.launch.serve import make_requests, serve_paged
    from repro.models.model import build_model

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    cfg = dataclasses.replace(get_config("qwen2-1.5b"), n_layers=2)
    model = build_model(cfg)
    params = steps_mod.cast_compute(model.init(0), cfg.compute_dtype)
    reqs = lambda: make_requests(4, prompt_len=64, gen_len=8,  # noqa: E731
                                 vocab=cfg.vocab_size, seed=0)
    kw = dict(n_slots=4, block_size=16, num_blocks=1 + 5 * 5)
    serve_paged(model, params, reqs(), **kw)          # compile outside

    def serve():
        with jax.profiler.TraceAnnotation("bench.traced"), \
                jax.profiler.TraceAnnotation("bench.serve_paged"):
            serve_paged(model, params, reqs(), **kw)
    _summarise(_traced(out, "serve_paged", serve))

    n = 4096
    rng = np.random.default_rng(0)
    lens = rng.integers(1, 17, n).astype(np.int32)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    nnz = int(indptr[-1])
    args_ = (indptr, rng.integers(0, n, nnz).astype(np.int32),
             rng.standard_normal(nnz).astype(np.float32),
             rng.standard_normal(n).astype(np.float32))
    mod = pipeline.compile(
        lambda a, b, c, x: ops.spmv_csr(a, b, c, x, n_rows=n,
                                        max_nnz_row=16),
        *args_, options=CompileOptions(target="pallas", interpret=False),
        name="spmv")
    dev = [jax.device_put(a) for a in args_]
    jax.block_until_ready(mod(*dev))

    def spmv():
        with jax.profiler.TraceAnnotation("bench.traced"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.call"):
                    jax.block_until_ready(mod(*dev))
    _summarise(_traced(out, "spmv", spmv))
    del jnp
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
