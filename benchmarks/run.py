"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only a,b] [--json-dir DIR]
    PYTHONPATH=src python benchmarks/run.py serve      # positional subset
                                                       # ("serve" is short
                                                       # for "fig_serve")

Runs:
    fig8_throughput     Fig. 8  — bulk bit-wise throughput, 8 platforms
    fig9_energy         Fig. 9  — DRAM chip energy per KB
    fig_fusion          fusion  — fused graphs vs unfused op chains
    fig_fleet           fleet   — weak-scaling sweep, vmap vs shard_map
                                  vs donated execution paths
    fig_queue           queue   — per-bank async command queues: SIMD
                                  ripple vs MIMD carry-save popcount
    fig_serve           serve   — BNN LM decode tok/s + tail latency
                                  per engine vs the TPU-roofline
                                  Verdict (BENCH_serve.json)
    fig_chaos           chaos   — Table-3 corner fault injection bare
                                  vs TMR/ECC, redundancy AAP pricing,
                                  queue-kill recovery latency
                                  (BENCH_chaos.json)
    table3_reliability  Table 3 — Monte-Carlo process-variation error
                                  rates -> BENCH_reliability.json
    roofline            brief   — 3-term roofline from the dry-run
    kernel_adjusted     brief   — kernel-adjusted memory roofline
                                  (GPU/TPU baselines; needs dry-run
                                  artifacts, skips gracefully without)

Prints each report plus a final ``name,us_per_call,derived`` CSV block,
and writes one machine-readable ``BENCH_<bench>.json`` per bench that
recorded data (see `benchmarks.record` for the schema: op, geometry,
path, rows/s, simulated throughput) so the perf trajectory is tracked
across PRs.  DRIM simulation and the GPU/TPU baselines share this one
CLI and one output format.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

from benchmarks import (fig8_throughput, fig9_energy, fig_chaos,
                        fig_fleet, fig_fusion, fig_queue, fig_serve,
                        kernel_adjusted, record, table3_reliability,
                        roofline)

MODULES = (
    ("fig8_throughput", fig8_throughput),
    ("fig9_energy", fig9_energy),
    ("fig_fusion", fig_fusion),
    ("fig_fleet", fig_fleet),
    ("fig_queue", fig_queue),
    ("fig_serve", fig_serve),
    ("fig_chaos", fig_chaos),
    ("table3_reliability", table3_reliability),
    ("roofline", roofline),
    ("kernel_adjusted", kernel_adjusted),
)


def _resolve(name: str):
    """Accept both the full module name and the short figure alias
    ('serve' -> 'fig_serve', 'queue' -> 'fig_queue', ...)."""
    known = {n for n, _ in MODULES}
    if name in known:
        return name
    if f"fig_{name}" in known:
        return f"fig_{name}"
    return None


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("benches", nargs="*", default=[],
                    help="benchmark names to run (default: all); short "
                    "aliases accepted, e.g. 'serve' for 'fig_serve'")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks to run")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_*.json records")
    ap.add_argument("--list", action="store_true",
                    help="list benchmark names and exit")
    ap.add_argument("--telemetry", action="store_true",
                    help="arm the DRIM telemetry registry + span tracer; "
                    "every BENCH_*.json gains a 'telemetry' snapshot key")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                    "to PATH (implies --telemetry)")
    ap.add_argument("--no-verify", action="store_true",
                    help="turn the static verifier pass off for this run "
                    "(DRIM_VERIFY=0); BENCH_*.json records are then "
                    "stamped 'verified': false")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.no_verify:
        os.environ["DRIM_VERIFY"] = "0"
    if args.trace_out:
        args.telemetry = True
    if args.telemetry:
        from repro.runtime import telemetry
        telemetry.arm()

    if args.list:
        for name, _ in MODULES:
            print(name)
        return
    selected = MODULES
    wanted = set(args.benches)
    if args.only:
        wanted |= {w.strip() for w in args.only.split(",") if w.strip()}
    if wanted:
        resolved = {w: _resolve(w) for w in wanted}
        unknown = sorted(w for w, r in resolved.items() if r is None)
        if unknown:
            ap.error(f"unknown benchmarks: {unknown}")
        names = {r for r in resolved.values()}
        selected = [(n, m) for n, m in MODULES if n in names]

    from repro.pim import verify as _verify
    print("static verification (drim.verify): "
          + ("on — every lowering is certified before it is timed"
             if _verify.default_enabled() else "OFF (--no-verify)"))

    csv_rows = []
    failures = []
    for name, mod in selected:
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}")
        try:
            mod.run(csv_rows)
        except Exception:  # noqa: BLE001 — report all, fail at the end
            failures.append(name)
            traceback.print_exc()

    print(f"\n{'=' * 72}\n== CSV summary (name,us_per_call,derived)\n"
          f"{'=' * 72}")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")

    for path in record.flush(args.json_dir):
        print(f"wrote {path}")

    if args.trace_out:
        from repro.runtime import telemetry
        print(f"wrote {telemetry.export_trace(args.trace_out)}")

    if failures:
        print(f"\nFAILED benchmarks: {failures}", file=sys.stderr)
        sys.exit(1)
    print("\nAll benchmarks completed.")


if __name__ == "__main__":
    main()
