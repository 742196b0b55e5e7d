#!/usr/bin/env python3
"""Readings of the program and of its control, on several seeds in one
process, to set the limits of a cell's comparison.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed it sets the cell up, runs a short window, and prints one
JSON line: the numbers the run compares (`program`) and the same numbers
read from the control (`control`): the reference put in the program's
place at the nearest precision below the configuration's, or, where the
configuration states no precision, with one of its guarantees broken.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT,
         bench_dir=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
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
        print(f"control: {e}", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        entry = entry_mod.Entry(Context(seed, config, traffic, cell, bench))
        win = window.run_window(entry.unit, args.seconds)
        entry.release()
        program = entry.check()
        line = {"workload": cell.name, "seed": seed,
                "calls": len(win.calls),
                "program": {k: v for k, (v, _) in program["checks"].items()},
                "program_readings": program.get("readings", {}),
                "control": entry.control()}
        print(json.dumps(line), flush=True)
        del entry
    return 0


if __name__ == "__main__":
    sys.exit(main())
