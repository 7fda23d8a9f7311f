"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* busy time: the union of the device's operation intervals inside the
  traced window (the host span ``bench.traced``), averaged over the
  devices;
* device time per operation, Pallas kernels under the ``name=`` their
  ``pallas_call`` carries, and per program (XLA module), with the
  kernels each program holds;
* idle gaps: the stretches inside the window with no operation on the
  device, each labelled with what the Python threads were doing at its
  middle: the innermost span there (a JAX dispatch, a transfer, or the
  benchmark's own ``bench.*`` spans), or ``host python, no span``:
  Python between traced calls, such as the engine's own loop.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.traced"
_INST = re.compile(r"%?([\w\-.]+) = ")
_SUFFIX = re.compile(r"\.\d+$")
_PALLAS = 'custom_call_target="tpu_custom_call"'
TOP = 10
NO_SPAN = "host python, no span"


@dataclasses.dataclass
class Event:
    name: str
    start: int          # ns
    end: int            # ns
    stats: dict

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> its operations
    modules: Dict[str, List[Event]]    # device plane -> program executions
    host: List[Event]                  # host spans, every thread
    python: List[Event]                # host spans of the Python threads


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") or \
        plane_name.startswith("/device:GPU:")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    python: List[Event] = []

    def events(line) -> List[Event]:
        return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                      {k: v for k, v in e.stats}) for e in line.events]

    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = events(line)
                elif line.name == "XLA Modules":
                    modules[plane.name] = events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = events(line)
                host.extend(evs)
                # the threads that run Python: JAX's dispatch spans and
                # the benchmark's own are recorded on them
                if line.name.startswith("python") or any(
                        e.name.startswith(("bench.", "PjitFunction"))
                        for e in evs):
                    python.extend(evs)
    return Trace(ops=ops, modules=modules, host=host, python=python)


def op_name(ev: Event) -> str:
    """The HLO instruction an operation event runs (its name is the
    instruction's text).  A Pallas kernel's instruction is named after
    the ``name=`` of its ``pallas_call``, so every call of one kernel
    gets that name, without the instruction's number."""
    m = _INST.match(ev.name)
    inst = m.group(1) if m else ev.name
    return _SUFFIX.sub("", inst) if _PALLAS in ev.name else inst


_CONTROL = (" while(", " conditional(", " call(")


def _encloses(ev: Event) -> bool:
    """A control-flow operation, whose interval holds its body's
    operations: busy, but not an operation of its own."""
    return any(c in ev.name for c in _CONTROL)


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(evs: Iterable[Event], lo: int, hi: int) -> List[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi), e.stats)
            for e in evs if e.end > lo and e.start < hi]


@dataclasses.dataclass
class Program:
    count: int
    seconds: float
    kernels: set


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_seconds: Dict[str, float]
    programs: Dict[str, Program]
    gaps: List[Tuple[str, float]]          # (host label, idle seconds)
    spans: Dict[str, int]                  # host span name -> count

    def kernel_seconds(self, names: Sequence[str]) -> Optional[float]:
        """Device seconds of the named operations, or None where none ran."""
        hit = [self.op_seconds[n] for n in names if n in self.op_seconds]
        return sum(hit) if hit else None

    def program_holding(self, kernel: str) -> Optional[Tuple[int, float]]:
        """(executions, device seconds) of the programs that hold
        ``kernel``, or None."""
        ps = [p for p in self.programs.values() if kernel in p.kernels]
        if not ps:
            return None
        return sum(p.count for p in ps), sum(p.seconds for p in ps)

    def span_count(self, name: str) -> int:
        return self.spans.get(name, 0)

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def _host_labels(host: List[Event], times: Sequence[int]) -> List[str]:
    """For each of the ascending ``times``, the innermost (shortest) host
    span covering it: one sweep with a heap of the spans begun so far."""
    heap: list = []
    out: List[str] = []
    i = 0
    for t in times:
        while i < len(host) and host[i].start <= t:
            heapq.heappush(heap, (host[i].dur, host[i].end, i))
            i += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        out.append(host[heap[0][2]].name if heap else NO_SPAN)
    return out


def reduce(trace: Trace, window_span: str = WINDOW_SPAN) -> Reduced:
    """The numbers of the traced window (the host span ``window_span``)."""
    win = [e for e in trace.host if e.name == window_span]
    if not win:
        raise ValueError(f"the trace has no {window_span!r} span")
    lo, hi = win[0].start, win[0].end
    if not trace.ops:
        raise ValueError("the trace has no device operations")
    busy_total = 0
    op_ns: Dict[str, int] = defaultdict(int)
    programs: Dict[str, Program] = {}
    gap_ns: Dict[str, int] = defaultdict(int)
    host = sorted((e for e in trace.python
                   if e.dur > 0 and e.name != window_span),
                  key=lambda e: e.start)
    for plane, evs in trace.ops.items():
        evs = _clip(evs, lo, hi)
        busy = union((e.start, e.end) for e in evs)
        busy_total += sum(b - a for a, b in busy)
        for e in evs:
            if not _encloses(e):
                op_ns[op_name(e)] += e.dur
        mods = _clip(trace.modules.get(plane, []), lo, hi)
        mstart = sorted((m.start, m.end, m.name) for m in mods)
        kernels_of: Dict[str, set] = defaultdict(set)
        for e in evs:
            k = bisect.bisect_right(mstart, (e.start, float("inf"), "")) - 1
            if k >= 0 and mstart[k][1] >= e.end:
                kernels_of[mstart[k][2]].add(op_name(e))
        for m in mods:
            p = programs.setdefault(m.name, Program(0, 0.0, set()))
            p.count += 1
            p.seconds += m.dur / 1e9
            p.kernels |= kernels_of[m.name]
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for (a, b), label in zip(idle, _host_labels(
                host, [(a + b) // 2 for a, b in idle])):
            gap_ns[label] += b - a
    n = len(trace.ops)
    spans: Dict[str, int] = defaultdict(int)
    for e in host:
        if e.start >= lo and e.end <= hi:
            spans[e.name] += 1
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / n / 1e9,
        op_seconds={k: v / n / 1e9 for k, v in op_ns.items()},
        programs=programs,
        gaps=sorted(((k, v / n / 1e9) for k, v in gap_ns.items()),
                    key=lambda kv: -kv[1]),
        spans=dict(spans))


def reduce_dir(trace_dir: str) -> Reduced:
    """Reduce the one ``.xplane.pb`` the profiler wrote under
    ``trace_dir``."""
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"want one trace under {trace_dir}, found "
                         f"{len(paths)}")
    return reduce(load(paths[0]))
