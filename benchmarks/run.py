"""Benchmark aggregator — one section per paper table/figure.
``PYTHONPATH=src python -m benchmarks.run [--only ...] [--targets ...]``
Prints ``name,us_per_call,derived`` CSV rows.

``--targets`` takes a comma list of registered backend names and runs each
pipeline-driven section once per backend (inside ``use_options``), so
backends are benchmarkable side by side — the paper's
library-vs-generated-loops comparison generalized to any plugin
(``--list-backends`` enumerates them).  Sections that drive kernels
directly (bgemm, roofline) are target-independent and run once; spmv
compiles the sparse pipeline per backend.  ``--smoke`` shrinks every
section to CI-sized problems (a pipeline-regression check, not a
measurement).
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma list: "
                        "gemm,fusion,autotune,spmv,bgemm,mala,resnet,"
                        "roofline")
    p.add_argument("--targets", default=None,
                   help="comma list of backend names to benchmark side by "
                        "side (default: the ambient target)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny problem sizes — CI pipeline-regression "
                        "check, not a measurement")
    p.add_argument("--list-backends", action="store_true",
                   help="list registered backends and exit")
    args = p.parse_args(argv)
    which = set(args.only.split(",")) if args.only else None

    from repro.core import backend as backend_mod
    from repro.core.options import CompileOptions, use_options
    from repro.launch import env

    if args.list_backends:
        for name in backend_mod.available_backends():
            print(name)
        return 0

    targets = args.targets.split(",") if args.targets else [None]
    for t in targets:
        if t is not None:
            try:
                backend_mod.resolve(t)   # fail fast on unknown names
            except backend_mod.UnknownBackendError as e:
                p.error(str(e))

    print(env.device_line())
    env.enable_compile_cache()
    from benchmarks import (autotune_bench, batched_gemm_bench,
                            fusion_bench, gemm_bench, mala_bench,
                            resnet_bench, spmv_bench)
    from benchmarks import roofline as roofline_bench

    # last column: section goes through pipeline.compile and honors the
    # ambient target (bgemm/roofline drive kernels directly, so re-running
    # them per backend would just relabel identical numbers; spmv compiles
    # the sparse pipeline per backend since PR 2)
    sections = [
        ("gemm", "Table 6.2 — SGEMM zero-overhead", gemm_bench.main, True),
        ("fusion", "kokkos.fused — launch count + wall, fused vs unfused",
         fusion_bench.main, True),
        ("autotune", "cost model — gated fusion, tuned tiling, tune cache",
         autotune_bench.main, False),     # pins the loops backend itself
        ("spmv", "Fig 6.1 — SpMV, 4 matrices", spmv_bench.main, True),
        ("bgemm", "Fig 6.3 — batched GEMM", batched_gemm_bench.main, False),
        ("mala", "Fig 6.2a — MALA DNN inference", mala_bench.main, True),
        ("resnet", "Fig 6.2b — ResNet18 inference + DualView ablation",
         resnet_bench.main, True),
        ("roofline", "§Roofline — dry-run derived terms",
         roofline_bench.main, False),
    ]
    failures = 0
    for key, title, fn, target_aware in sections:
        if which and key not in which:
            continue
        # every section main accepts smoke= — passed unconditionally so a
        # section that forgets the kwarg fails loudly instead of silently
        # running at full size under --smoke
        kwargs = {"smoke": True} if args.smoke else {}
        for target in (targets if target_aware else [None]):
            if target is not None:
                label = f" [target={target}]"
            elif targets != [None]:
                label = " [target-independent]"
            else:
                label = ""
            print(f"# {title}{label}")
            try:
                if target is None:
                    fn(print_rows=True, **kwargs)
                else:
                    with use_options(CompileOptions(target=target)):
                        fn(print_rows=True, **kwargs)
            except Exception as e:   # noqa: BLE001 — report all sections
                failures += 1
                tag = f"[{target}]" if target else ""
                print(f"{key}{tag},ERROR,{e!r}", file=sys.stderr)
            print()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
