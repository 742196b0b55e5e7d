"""Profiler traces: record one window, and reduce it to device busy and
idle time, per-program and per-op device time, and the host's spans.

The reduction reads plain `Event`s, so it is tested on a small recorded
trace without a chip.  Device events come from the planes named
`/device:<kind>:<n>`: the line "XLA Ops" holds one event per executed
HLO op (busy time is their union), "XLA Modules" one per executed
program, named `jit_<python name>(<fingerprint>)`.  The benchmark's own
host spans are `jax.profiler.TraceAnnotation`s named `bench.<what>`;
`bench.window` marks the measured window.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def span(name: str):
    """A host span of the benchmark, written into the profiler's trace
    (free when no trace is being recorded)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def recording(enabled: bool) -> Iterator[Dict]:
    """Record a profiler trace of the enclosed block into a temporary
    directory (under TMPDIR); yields a dict that holds the `Event`s
    once the block has ended.  Python-level tracing is off."""
    out: Dict = {"events": None}
    if not enabled:
        yield out
        return
    import jax
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        out["events"] = list(read_xplane(files[0])) if files else []
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def read_xplane(path: str) -> Iterator[Event]:
    """Device events and the benchmark's host spans of one trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    for e in line.events:
                        yield Event(plane.name, line.name, e.name,
                                    e.start_ns, e.end_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        yield Event(plane.name, line.name, e.name,
                                    e.start_ns, e.end_ns)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] between disjoint busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def module_name(event_name: str) -> str:
    """`jit_body(7606946645078064340)` -> `jit_body`."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """`%fusion.4 = u32[...] fusion(...)` -> `fusion.4`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _program_of(modules: List[Tuple[float, float, str]], t: float) -> str:
    """The program whose execution holds device time t."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return "?"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over the devices
    n_devices: int
    modules: Dict[str, float]           # program -> device seconds
    module_calls: Dict[str, int]
    ops: Dict[str, float]               # program/op -> device seconds
    idle_by_host: Dict[str, float]      # host span -> idle device seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in idle]}


def summarize(events: List[Event]) -> Optional[Summary]:
    """Reduce one traced window; None when the trace holds no device op
    inside it."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        return None
    lo = min(e.start_ns for e in windows)
    hi = max(e.end_ns for e in windows)
    by_plane: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    modules: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    runs: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    inside = [e for e in events if e.line in (OPS_LINE, MODULES_LINE)
              and e.end_ns > lo and e.start_ns < hi]
    for e in inside:
        if e.line == MODULES_LINE:
            name = module_name(e.name)
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            modules[name] += (t - s) / 1e9
            calls[name] += 1
            runs[e.plane].append((e.start_ns, e.end_ns, name))
    for r in runs.values():
        r.sort()
    for e in inside:
        if e.line == OPS_LINE:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            by_plane[e.plane].append((s, t))
            name = (_program_of(runs[e.plane], e.start_ns) + "/"
                    + op_name(e.name))
            ops[name] += (t - s) / 1e9
    if not by_plane:
        return None
    busy_union = {p: union(iv) for p, iv in by_plane.items()}
    busy = sum(sum(e - s for s, e in iv) for iv in busy_union.values()) \
        / len(busy_union) / 1e9
    spans = [(e.start_ns, e.end_ns, e.name[len(SPAN_PREFIX):])
             for e in events if e.name.startswith(SPAN_PREFIX)
             and e.name != WINDOW_SPAN]
    idle: Dict[str, float] = defaultdict(float)
    first = sorted(busy_union)[0]
    for s, t, label in _overlap(gaps(busy_union[first], lo, hi),
                                host_activity(spans, lo, hi)):
        idle[label] += (t - s) / 1e9
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy,
                   n_devices=len(busy_union), modules=dict(modules),
                   module_calls=dict(calls), ops=dict(ops),
                   idle_by_host=dict(idle))


OUTSIDE = "outside the benchmark's spans"


def host_activity(spans, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into segments, each labelled with the innermost
    benchmark span open in it (spans nest, as `with` blocks do)."""
    bounds = sorted({lo, hi, *(b for s, e, _ in spans for b in (s, e)
                               if lo < b < hi)})
    starts = sorted(spans, key=lambda x: (x[0], -x[1]))
    out, stack, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i][0] <= a:
            stack.append(starts[i])
            i += 1
        stack = [x for x in stack if x[1] > a]
        out.append((a, b, max(stack, key=lambda x: x[0])[2] if stack
                    else OUTSIDE))
    return out


def _overlap(a, b):
    """Pairwise intersections of two sorted lists of disjoint intervals,
    labelled by the second."""
    i = j = 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < t:
            yield s, t, b[j][2]
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
