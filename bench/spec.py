"""Discovery: cells, configurations, traffic mixes, entries and metric
readers, each found by the name `BENCHMARK.json` gives it."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """A name that no file or entry answers to."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    kind: str                       # "end_to_end" | "per_layer"
    moves: Optional[str] = None
    workloads: Optional[tuple] = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    why: str


class Benchmark:
    """`BENCHMARK.json` and the files under `bench/` it names."""

    def __init__(self, root: str = ROOT, bench_dir: str = BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            self.doc = json.load(f)
        self.cells = {w["name"]: Cell(w["name"], w["config"], w["traffic"],
                                      int(w["chips"]), w["why"])
                      for w in self.doc["workloads"]}
        self.metrics: List[Metric] = []
        for kind in ("end_to_end", "per_layer"):
            for m in self.doc[kind]:
                self.metrics.append(Metric(
                    name=m["name"], unit=m["unit"], kind=kind,
                    moves=m.get("moves"),
                    workloads=(tuple(m["workloads"]) if "workloads" in m
                               else None)))

    def cell(self, name: str) -> Cell:
        try:
            return self.cells[name]
        except KeyError:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                            f"known: {sorted(self.cells)}") from None

    def metrics_for(self, cell: str, kind: str) -> List[Metric]:
        """The metrics of one kind that a cell reports: those listing it,
        and those with no list whose moved metric the cell reports (for
        an end-to-end metric with no list: every cell)."""
        e2e = [m for m in self.metrics if m.kind == "end_to_end"
               and (m.workloads is None or cell in m.workloads)]
        if kind == "end_to_end":
            return e2e
        names = {m.name for m in e2e}
        return [m for m in self.metrics if m.kind == "per_layer"
                and ((cell in m.workloads) if m.workloads is not None
                     else m.moves in names)]

    # -- files found by name ------------------------------------------------
    def _json(self, sub: str, name: str) -> Dict[str, Any]:
        path = os.path.join(self.bench_dir, sub, f"{name}.json")
        if not os.path.isfile(path):
            raise SpecError(f"no file {os.path.relpath(path, self.root)}")
        with open(path) as f:
            return json.load(f)

    def config(self, name: str) -> Dict[str, Any]:
        return self._json("configs", name)

    def traffic(self, name: str) -> Dict[str, Any]:
        return self._json("traffic", name)

    def entry(self, kind: str):
        return load_module(self.bench_dir, "entries", kind)

    def reader(self, metric: str):
        return load_module(self.bench_dir, "metrics", metric)

    def reference(self, module: str):
        return load_module(self.bench_dir, "reference", module)


def load_module(bench_dir: str, sub: str, name: str):
    """Import `bench/<sub>/<name>.py` by path (names may hold dots)."""
    path = os.path.join(bench_dir, sub, f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"no file bench/{sub}/{name}.py")
    mod_name = f"bench_{sub}_{name.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
