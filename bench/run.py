#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout; the program under test is imported
from `src/`.  A run sets up the cell's entry (data and weights made from
the seed on the device, every shape it uses compiled and warmed), calls
the entry in a closed loop for `--seconds`, then checks the answers of
the window against a plain reference.  The last line of standard output
is one JSON object:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of the
window.  `checks` holds each number compared with its limit; they are
also the last lines of standard error.  A backend that is not a TPU, or
fewer chips than the cell asks for, exits 1 with no result line; a
checkout without the program exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


@dataclasses.dataclass
class Context:
    """What an entry is built from."""
    seed: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    cell: Any
    bench: Any


@dataclasses.dataclass
class Readings:
    """What a metric reader reads."""
    cell: Any
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    peaks: Dict[str, float]
    setup_s: float
    setup_compile: Dict[str, float]
    window: Any
    trace: Optional[Any]


def _say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def main(argv=None, *, require_tpu: bool = True,
         peaks: Optional[Dict[str, float]] = None,
         root: str = ROOT, bench_dir: Optional[str] = None) -> int:
    """`require_tpu=False`, `peaks`, `root` and `bench_dir` let a test
    drive a whole run on the CPU over cells of its own; the command line
    always requires the chip and reads this checkout."""
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        _say(f"bench: no program under {SRC}; run from a full checkout")
        return 2
    for p in (SRC, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import device, spec, tracing, window

    try:
        bench = spec.Benchmark(root, bench_dir or spec.BENCH_DIR)
        cell = bench.cell(args.workload)
        config = bench.config(cell.config)
        traffic = bench.traffic(cell.traffic)
        kind = "per_layer" if args.trace else "end_to_end"
        metrics = bench.metrics_for(cell.name, kind)
        readers = {m.name: bench.reader(m.name) for m in metrics}
        entry_mod = bench.entry(traffic["entry"])
    except spec.SpecError as e:
        _say(f"bench: {e}")
        return 2

    device.start_jax()
    try:
        devs = device.devices(cell.chips, require_tpu)
    except device.NoChip as e:
        _say(f"bench: {e}; nothing was run")
        return 1
    if peaks is None:
        peaks = device.peaks(devs[0].device_kind)
    clock = device.CompileClock()

    from repro.pim.compiler import LOWER_CACHE_STATS
    ctx = Context(args.seed, config, traffic, cell, bench)
    with tracing.span("setup"):
        entry = entry_mod.Entry(ctx)
    setup_s = time.perf_counter() - T_START
    setup_compile = clock.snapshot()
    lowerings = LOWER_CACHE_STATS["misses"]

    # What set-up left behind is not the window's garbage to collect.
    gc.collect()
    gc.freeze()
    with tracing.recording(bool(args.trace)) as rec:
        with tracing.span("window"):
            win = window.run_window(entry.unit, args.seconds)
    gc.unfreeze()
    end = clock.snapshot()
    inside = {"compiles": end["compiles"] - setup_compile["compiles"],
              "traces": end["traces"] - setup_compile["traces"],
              "lowerings": LOWER_CACHE_STATS["misses"] - lowerings}
    durations = [c.t_end - c.t_start for c in win.calls]
    print(json.dumps({"inside_window": inside, "calls": len(win.calls),
                      "window_s": win.seconds, "setup_s": setup_s,
                      "call_s": {"median": window.percentile(durations, 50),
                                 "max": max(durations),
                                 "slowest": durations.index(max(durations))}}),
          flush=True)

    dev = device.describe(devs)
    dev["memory_peak_bytes"] = device.memory_peak_bytes(devs)
    entry.release()
    summary = tracing.summarize(rec["events"]) if args.trace else None
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s

    found = Readings(cell=cell, config=config, traffic=traffic, peaks=peaks,
                     setup_s=setup_s, setup_compile=setup_compile,
                     window=win, trace=summary)
    values = {}
    for m in metrics:
        v = readers[m.name].read(found)
        if v is not None:
            values[m.name] = {"value": v, "unit": m.unit}

    result = entry.check()
    checks = {name: {"value": v, "limit": lim}
              for name, (v, lim) in result["checks"].items()}
    correct = (result["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": values, "device": dev}
    if summary is not None:
        line["breakdown"] = summary.breakdown()
    line["checks"] = checks
    for name, v in result.get("readings", {}).items():
        _say(f"reading {name}: {v}")
    for name, c in checks.items():
        _say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
