#!/usr/bin/env python3
"""The program's own host spans (`drim.*`) beside the benchmark's
(`bench.*`): a cell's window traced with both kept, reduced to each
span's durations and self time, and the device's idle time by the
innermost span the host was in.

    python3 bench/host_spans.py --workload <cell> --seed <n> --seconds <s>
        [--units <n>] [--fixture <path.json.gz>]

It sets the cell up as `bench/run.py` does, records a profiler trace of
a window (`--units` closed-loop units, or `--seconds` of them), and
prints one JSON line: the window, its device busy time, per span name
its count, median milliseconds and self seconds, and the idle gaps.
`--fixture` also writes the window's events, as the reduction tests
read them.  The program's spans are `drim.obs` spans, which write a
`jax.profiler.TraceAnnotation` named `drim.<name>` while a trace
records; a program without them reduces as `bench/tracing.py` does.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from collections import defaultdict
from typing import Dict, Iterator, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import tracing  # noqa: E402
from bench.tracing import Event  # noqa: E402
from bench.window import percentile  # noqa: E402

PROGRAM_PREFIX = "drim."


def read_xplane(path: str) -> Iterator[Event]:
    """What `tracing.read_xplane` keeps, and the program's host spans."""
    from jax.profiler import ProfileData
    yield from tracing.read_xplane(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        yield Event(plane.name, line.name, e.name,
                                    e.start_ns, e.end_ns)


def _label(name: str) -> str:
    """Idle gaps keep a program span's full name and a benchmark span's
    short one, as `tracing.summarize` gives it."""
    return (name[len(tracing.SPAN_PREFIX):]
            if name.startswith(tracing.SPAN_PREFIX) else name)


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    busy_s: float                       # the first device's op union
    durations: Dict[str, List[float]]   # span -> seconds of each one
    self_s: Dict[str, float]            # span -> seconds not in a child
    idle_by_span: Dict[str, float]      # innermost span -> idle seconds

    def breakdown(self) -> Dict:
        spans = {n: {"count": len(d),
                     "median_ms": 1e3 * percentile(d, 50),
                     "self_s": self.self_s[n]}
                 for n, d in sorted(self.durations.items())}
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "spans": spans, "idle_gaps": [[n, s] for n, s in idle]}


def summarize(events: List[Event]) -> Optional[SpanSummary]:
    """Reduce one traced window; None without a window or a device op
    inside it.  Spans count when they start inside the window."""
    windows = [e for e in events if e.name == tracing.WINDOW_SPAN]
    if not windows:
        return None
    lo = min(e.start_ns for e in windows)
    hi = max(e.end_ns for e in windows)
    ops: Dict[str, List] = defaultdict(list)
    for e in events:
        if e.line == tracing.OPS_LINE and e.end_ns > lo and e.start_ns < hi:
            ops[e.plane].append((max(e.start_ns, lo), min(e.end_ns, hi)))
    if not ops:
        return None
    busy = tracing.union(ops[sorted(ops)[0]])
    host = sorted(((e.start_ns, e.end_ns, e.name) for e in events
                   if e.plane.startswith("/host:")
                   and e.name != tracing.WINDOW_SPAN
                   and (e.name.startswith(tracing.SPAN_PREFIX)
                        or e.name.startswith(PROGRAM_PREFIX))),
                  key=lambda x: (x[0], -x[1]))
    durations: Dict[str, List[float]] = defaultdict(list)
    self_s: Dict[str, float] = defaultdict(float)
    stack: List = []
    for s, t, name in host:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if lo <= s < hi:
            durations[name].append((t - s) / 1e9)
            self_s[name] += (t - s) / 1e9
            if stack and lo <= stack[-1][0]:
                self_s[stack[-1][2]] -= (min(t, stack[-1][1]) - s) / 1e9
        stack.append((s, t, name))
    idle: Dict[str, float] = defaultdict(float)
    labelled = [(s, t, _label(name)) for s, t, name in host]
    for s, t, label in tracing._overlap(
            tracing.gaps(busy, lo, hi),
            tracing.host_activity(labelled, lo, hi)):
        idle[label] += (t - s) / 1e9
    return SpanSummary(window_s=(hi - lo) / 1e9,
                       busy_s=sum(t - s for s, t in busy) / 1e9,
                       durations=dict(durations), self_s=dict(self_s),
                       idle_by_span=dict(idle))


def record(block) -> List[Event]:
    """Run `block()` under a profiler trace (temporary directory under
    TMPDIR, Python-level tracing off); returns the kept events."""
    import glob
    import shutil
    import tempfile
    import jax
    tmp = tempfile.mkdtemp(prefix="bench_spans_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            block()
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        return list(read_xplane(files[0])) if files else []
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT,
         bench_dir=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--units", type=int, default=0,
                    help="closed-loop units to trace instead of --seconds")
    ap.add_argument("--fixture", default="")
    args = ap.parse_args(argv)
    import gc
    import time
    from bench import device, spec, window
    from bench.run import Context
    bench = spec.Benchmark(root, bench_dir or spec.BENCH_DIR)
    cell = bench.cell(args.workload)
    config, traffic = bench.config(cell.config), bench.traffic(cell.traffic)
    entry_mod = bench.entry(traffic["entry"])
    device.start_jax()
    try:
        device.devices(cell.chips, require_tpu)
    except device.NoChip as e:
        print(f"host_spans: {e}", file=sys.stderr)
        return 1
    entry = entry_mod.Entry(Context(args.seed, config, traffic, cell, bench))
    gc.collect()
    gc.freeze()
    found: Dict = {}

    def traced():
        with tracing.span("window"):
            if args.units:
                w = window.Window(time.perf_counter(), [])
                for _ in range(args.units):
                    w.calls.extend(entry.unit())
                found["window"] = w
            else:
                found["window"] = window.run_window(entry.unit,
                                                    args.seconds)

    events = record(traced)
    gc.unfreeze()
    win = found["window"]
    summary = summarize(events)
    line = {"workload": cell.name, "seed": args.seed,
            "calls": len(win.calls), "correct": None}
    entry.release()
    result = entry.check()
    line["correct"] = (result["failed"] == 0 and all(
        v <= lim for v, lim in result["checks"].values()))
    if summary is not None:
        line.update(summary.breakdown())
    if args.fixture:
        import gzip
        lo = min(e.start_ns for e in events
                 if e.name == tracing.WINDOW_SPAN)
        hi = max(e.end_ns for e in events
                 if e.name == tracing.WINDOW_SPAN)
        kept = [dataclasses.asdict(dataclasses.replace(e, name=e.name[:120]))
                for e in events if e.end_ns > lo and e.start_ns < hi]
        os.makedirs(os.path.dirname(os.path.abspath(args.fixture)),
                    exist_ok=True)
        with gzip.open(args.fixture, "wt") as f:
            json.dump({"min_bytes": win.total("min_bytes"),
                       "events": kept}, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
