"""What every benchmark run shares: the registry in ``BENCHMARK.json``,
the files each entry names, the device check, the compile cache, the
compile counter and the result line.

Nothing here imports the program; :func:`import_program` puts the
checkout's ``src/`` on the path when a runner needs it.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import pathlib
import re
import sys
import time
from typing import Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def process_start_monotonic() -> float:
    """``time.monotonic()`` at the moment this process started, read
    from ``/proc`` (Linux), so set-up counts the interpreter's start."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19]) / ticks
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.monotonic() - (uptime - start)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""

    name: str
    chips: int
    config_name: str
    config: dict          # the configuration's file
    traffic_name: str
    traffic: dict         # bench/traffic/<traffic>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    root: pathlib.Path = ROOT   # the checkout whose files the cell names


def _metric_applies(metric: dict, workload: str, e2e_names) -> bool:
    """A metric with ``workloads`` applies to those cells.  Without it an
    end-to-end metric applies to every cell, and a per-layer metric to
    every cell that reports the end-to-end metric it ``moves``."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    reg = benchmark(root)
    wl = next((w for w in reg["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in reg['workloads']]}")
    cfg = next(c for c in reg["configs"] if c["name"] == wl["config"])
    e2e = [m for m in reg["end_to_end"]
           if _metric_applies(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in reg["per_layer"]
                 if _metric_applies(m, name, e2e_names)]
    return Cell(name=name, chips=wl["chips"], config_name=cfg["name"],
                config=load_json(root / cfg["file"]),
                traffic_name=wl["traffic"],
                traffic=load_json(root / "bench" / "traffic" /
                                  f"{wl['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer, root=root)


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _file_module(kind: str, name: str, root: pathlib.Path):
    """``bench/<kind>/<name>.py``, loaded by its path."""
    return _load(root / "bench" / kind / f"{name}.py",
                 f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}")


@functools.lru_cache(maxsize=None)
def _reference_module(path: pathlib.Path):
    # once per file and process: its jitted functions keep their compiles
    return _load(path, "bench_reference_" + re.sub(r"\W", "_", str(path)))


def reference(cfg: dict, root: pathlib.Path = ROOT):
    """The plain reference module that a served configuration's file
    names under ``"reference"``, a path from the checkout's root.  It
    gives what depends on the architecture (``bench/serve.py``).  Refuses
    a file that names none, and a path outside the benchmark's
    ``paths``."""
    if "reference" not in cfg:
        raise KeyError("the configuration's file names no \"reference\"")
    paths = benchmark(root)["paths"]
    path = (root / cfg["reference"]).resolve()
    if path.suffix != ".py" or not any(
            path.is_relative_to((root / p).resolve()) for p in paths):
        raise ValueError(f"reference {cfg['reference']!r} is not a Python "
                         f"file under the benchmark's paths {paths}")
    return _reference_module(path)


def metric_reader(name: str, root: pathlib.Path = ROOT) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``: one file per per-layer
    metric, found by its name."""
    return _file_module("metrics", name, root).read


def program_maker(name: str, root: pathlib.Path = ROOT) -> Callable:
    """``make`` of ``bench/programs/<name>.py``: one file per input
    program of the compiler cells, found by the name its mix gives."""
    return _file_module("programs", name, root).make


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """The chip's published peaks (``bench/peaks.json``).  A device
    that is not in the table is an error, never a default."""
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# the device and the program
# ---------------------------------------------------------------------------

class NoAccelerator(RuntimeError):
    pass


def require_tpu(chips: int) -> dict:
    """The devices as JAX reports them.  Raises :class:`NoAccelerator`
    when the first device is not a TPU or there are fewer than
    ``chips``: the benchmark never falls back to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX found no devices: {e}") from None
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"JAX's first device is {devices[0].platform!r} "
            f"({devices[0].device_kind}); the benchmark runs only on a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the fixed ``.jax_cache/``
    of this checkout, for every program however short its compile, so
    that only a checkout's first run of a cell compiles.  The variable
    is set too, so that the program, which honours it, agrees."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def import_program() -> None:
    """Put the checkout's ``src/`` on the path and import the program;
    raises ImportError where it is missing."""
    src = ROOT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro  # noqa: F401


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.local_devices()]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


class CompileCounter:
    """Backend compiles (or persistent-cache fetches) seen by
    ``jax.monitoring`` while the counter is open."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self._open = False

    def _on_duration(self, event, duration, **_):
        if self._open and event == self.BACKEND_COMPILE:
            self.count += 1

    def __enter__(self):
        import jax
        self._open = True
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax
        self._open = False
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def checks_text(checks: List[dict]) -> str:
    return "\n".join(
        f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}, "
        f"{'ok' if c['ok'] else 'FAILED'})" for c in checks)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                breakdown: Optional[dict], checks: List[dict]) -> str:
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = [{"name": c["name"], "value": c["value"],
                      "limit": c["limit"]} for c in checks]
    return json.dumps(out)
