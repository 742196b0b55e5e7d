"""Entry: BitLinear GEMMs offloaded to the DRIM fleet the way the
serving layer offloads them, `pim.bnn.serve_bnn_matmul(x_bits, w_bits,
engine=...)`, with host sign bits in and host int32 dot products out.

Traffic parameters:
  gemms   [[M, K, N], ...] called in turn; one pass over the list is one
          unit of the closed loop (a layer's FFN), so every run does the
          same mix
  engine  the DRIM engine

Each GEMM has one fixed [N, K] sign-bit weight matrix drawn from the
seed; its [M, K] activations are drawn from the seed for every call.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import seeds, work
from bench.tracing import span
from bench.window import Call


class Entry:
    def __init__(self, ctx):
        from repro.core import DrimGeometry
        from repro.pim import bnn
        self.ctx = ctx
        self.bnn = bnn
        self.geom = DrimGeometry(**ctx.config["geometry"])
        self.engine = ctx.traffic["engine"]
        self.gemms = [tuple(int(v) for v in g) for g in ctx.traffic["gemms"]]
        self.ref = ctx.bench.reference(ctx.config["reference"])
        wr = seeds.rng(ctx.seed, 0)
        self.weights = [wr.integers(0, 2, (n, k), dtype=np.uint8)
                        for _, k, n in self.gemms]
        self.acts = seeds.rng(ctx.seed, 1)
        self.done: List = []            # (gemm index, x_bits, result)
        self.n_calls = 0
        for g in range(len(self.gemms)):      # warm up every shape
            self._call(g, self._activations(g))
        # the lowering of each chunk width, whose schedule every run
        # of that width updates (`serving_lowering` is memoized)
        self.lowered = {kc: bnn.serving_lowering(kc, engine=self.engine,
                                                 geom=self.geom)
                        for _, k, _ in self.gemms
                        for kc in bnn.k_chunks(k)}

    def _activations(self, g: int) -> np.ndarray:
        m, k, _ = self.gemms[g]
        return self.acts.integers(0, 2, (m, k), dtype=np.uint8)

    def _call(self, g: int, x: np.ndarray) -> np.ndarray:
        return self.bnn.serve_bnn_matmul(x, self.weights[g],
                                         engine=self.engine, geom=self.geom)

    def unit(self) -> List[Call]:
        calls = []
        for g, (m, k, n) in enumerate(self.gemms):
            x = self._activations(g)
            t0 = time.perf_counter()
            with span("call"):
                y = self._call(g, x)
            t1 = time.perf_counter()
            self.done.append((g, x, y))
            self.n_calls += 1
            chunks = self.bnn.k_chunks(k)
            executed = sum(self.lowered[kc].schedule.waves
                           * self.lowered[kc].schedule.slots
                           for kc in chunks)
            calls.append(Call(t0, t1, {
                "bitops": work.bnn_dot_bitops(m, n, k),
                "min_bytes": sum(work.bnn_dot_min_bytes(m, n, kc)
                                 for kc in chunks),
                "tiles_occupied": len(chunks) * work.occupied_tiles(
                    m * n, self.geom.row_bits),
                "tiles_executed": executed}))
        return calls

    def release(self) -> None:
        self.lowered = None

    def check(self) -> Dict:
        """Every GEMM of the window against the numpy +-1 dot: entries
        that differ (limit 0, exact)."""
        bad, bad_calls = 0, 0
        for g, x, y in self.done:
            want = self.ref.pm1_dot(x, self.weights[g])
            got = np.asarray(y)
            n = (int(np.count_nonzero(got != want)) if got.shape == want.shape
                 else want.size)
            bad += n
            bad_calls += n > 0
        return {"checks": {"wrong_entries": (bad, 0)},
                "attempted": self.n_calls, "failed": bad_calls,
                "readings": {"calls_checked": len(self.done)}}

    def control(self) -> Dict:
        """The reference put in the program's place with its guarantee
        broken: one entry of every product off by 2, the least change a
        +-1 dot can show."""
        wrong = 0
        for g, x, _ in self.done:
            want = self.ref.pm1_dot(x, self.weights[g])
            c = want.copy()
            c.flat[0] += 2
            wrong += int(np.count_nonzero(c != want))
        return {"wrong_entries": wrong}
