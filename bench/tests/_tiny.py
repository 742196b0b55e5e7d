"""A benchmark of tiny cells that a CPU test can run end to end: its own
BENCHMARK.json, configurations and traffic, and the real entries,
metric readers and references of `bench/`."""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_GEOM = {"chips": 1, "banks": 2, "subarrays_per_bank": 8, "row_bits": 64}
PEAKS = {"hbm_bytes_per_s": 819e9}

CONFIGS = {
    "tiny-drim": {"source": "https://arxiv.org/abs/1904.05782",
                  "reduced": ["geometry"], "geometry": TINY_GEOM,
                  "reference": "fleet"},
}
TRAFFIC = {
    "tiny-k8": {"entry": "fleet", "program": "bitlinear", "m": 8, "n": 128,
                "k_bits": 8, "engine": None, "pool": 2, "check": 3},
    "tiny-xnor2": {"entry": "fleet", "program": "xnor2", "n_bits": 4096,
                   "engine": None, "pool": 2, "check": 3},
    "tiny-gemms": {"entry": "bitlinear_offload",
                   "gemms": [[2, 16, 24], [2, 24, 16]],
                   "engine": "resident"},
}
CELLS = [("tiny.k8", "tiny-drim", "tiny-k8"),
         ("tiny.xnor2", "tiny-drim", "tiny-xnor2"),
         ("tiny.gemms", "tiny-drim", "tiny-gemms")]


def keep_jax_as_it_is(monkeypatch):
    """A test process has started JAX already: the harness must not set
    XLA flags or the compilation cache in it."""
    from bench import device
    monkeypatch.setattr(device, "start_jax", lambda: None)


def make_bench(root: str, cells=CELLS, configs=CONFIGS, traffic=TRAFFIC):
    """Write a checkout-like tree under `root`; returns its bench dir."""
    bench = os.path.join(root, "bench")
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for sub in ("entries", "metrics", "reference"):
        os.symlink(os.path.join(BENCH, sub), os.path.join(bench, sub))
    for name, doc in configs.items():
        with open(os.path.join(bench, "configs", f"{name}.json"), "w") as f:
            json.dump(doc, f)
    for name, doc in traffic.items():
        with open(os.path.join(bench, "traffic", f"{name}.json"), "w") as f:
            json.dump(doc, f)
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        real = json.load(f)
    doc = {"command": real["command"], "paths": real["paths"],
           "run_seconds": 1, "configs": [],
           "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1,
                          "why": "CPU test"} for n, c, t in cells],
           "end_to_end": [dict(m, workloads=[c[0] for c in cells])
                          if m["name"] != "setup_s" else m
                          for m in real["end_to_end"]],
           "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                         for m in real["per_layer"]]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return bench
