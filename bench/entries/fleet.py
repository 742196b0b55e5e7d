"""Entry: one DRIM program on the simulated fleet, called as a user calls
it, `drim.compile(src, geom=...).lower(engine=...).run(*planes)`, with
device-resident operand planes.

Traffic parameters (`bench/traffic/<name>.json`):
  program   "xnor2" (Table-2 op over two n_bits operands) or "bitlinear"
            (`pim.bnn.bitlinear_kernel(k_bits)`: the carry-save popcount
            of an [m, k_bits] x [n, k_bits] binary dot, output (i, j) on
            lane i * n + j)
  n_bits | m, n, k_bits   operand sizes
  engine    engine name, or null for the pipeline's default
  pool      operand sets made from the seed, called round robin
  check     calls kept, by a seeded reservoir, for the comparison
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import seeds, work
from bench.tracing import span
from bench.window import Call


class Entry:
    def __init__(self, ctx):
        import jax
        import drim
        from repro.core import DrimGeometry
        self.ctx = ctx
        t = ctx.traffic
        self.geom = DrimGeometry(**ctx.config["geometry"])
        self.program = t["program"]
        self.ref = ctx.bench.reference(ctx.config["reference"])
        if self.program == "xnor2":
            src = "xnor2"
            self.lanes = int(t["n_bits"])
            self.bitops = work.xnor2_bitops(self.lanes)
            self.min_bytes = work.xnor2_min_bytes(self.lanes)
        elif self.program == "bitlinear":
            from repro.pim.bnn import bitlinear_kernel
            m, n, k = int(t["m"]), int(t["n"]), int(t["k_bits"])
            src = bitlinear_kernel(k).trace()
            self.lanes = m * n
            self.bitops = work.bnn_dot_bitops(m, n, k)
            self.min_bytes = work.bnn_dot_min_bytes(m, n, k)
        else:
            raise ValueError(f"unknown fleet program {self.program!r}")
        with span("lower"):
            self.low = drim.compile(src, geom=self.geom).lower(
                engine=t.get("engine"))
        self.pool = [self._operands(i) for i in range(int(t["pool"]))]
        jax.block_until_ready([p["planes"] for p in self.pool])
        self.kept = seeds.Reservoir(int(t["check"]), ctx.seed, stream=1)
        self.n_calls = 0
        for i in range(len(self.pool)):       # warm up: compile, stage
            jax.block_until_ready(self.low.run(*self.pool[i]["planes"]))

    # -- operands, made on the device from the seed --------------------------
    def _operands(self, i: int) -> Dict:
        key = seeds.jax_key(self.ctx.seed, i)
        t = self.ctx.traffic
        if self.program == "xnor2":
            a, b = _jitted(_xnor2_operands, "words")(key,
                                                     -(-self.lanes // 32))
            return {"planes": (a, b)}
        m, n, k = int(t["m"]), int(t["n"]), int(t["k_bits"])
        a_bits, b_bits, planes = _jitted(_bnn_operands, "m", "n", "k")(
            key, m, n, k)
        return {"planes": tuple(planes), "a_bits": a_bits,
                "b_bits": b_bits}

    # -- the timed path ------------------------------------------------------
    def unit(self) -> List[Call]:
        import jax
        import time
        i = self.n_calls % len(self.pool)
        t0 = time.perf_counter()
        with span("call"):
            out = self.low.run(*self.pool[i]["planes"])
        with span("sync"):
            jax.block_until_ready(out)
        t1 = time.perf_counter()
        self.kept.offer((i, out))
        self.n_calls += 1
        return [Call(t0, t1, {"bitops": self.bitops,
                              "min_bytes": self.min_bytes})]

    def release(self) -> None:
        self.low = None

    # -- the comparison ------------------------------------------------------
    def check(self) -> Dict:
        """Every kept call's planes against the numpy answer of its
        operand set: words that differ (limit 0, exact)."""
        expected = {}
        bad_words, bad_calls = 0, 0
        for i, out in self.kept.items:
            if i not in expected:
                expected[i] = self._expected(i)
            got = [np.asarray(o, np.uint32).reshape(-1)
                   for o in (out if isinstance(out, (tuple, list))
                             else (out,))]
            want = expected[i]
            if len(got) != len(want):
                bad = sum(w.size for w in want)
            else:
                bad = sum(int(np.count_nonzero(g != w)) if g.shape == w.shape
                          else w.size for g, w in zip(got, want))
            bad_words += bad
            bad_calls += bad > 0
        return {"checks": {"wrong_words": (bad_words, 0)},
                "attempted": self.n_calls, "failed": bad_calls,
                "readings": {"calls_checked": len(self.kept.items)}}

    def control(self) -> Dict:
        """The reference put in the program's place with its guarantee
        broken: the last word of every plane inverted."""
        wrong = 0
        for i, _ in self.kept.items:
            for w in self._expected(i):
                c = w.copy()
                c[-1] = ~c[-1]
                wrong += int(np.count_nonzero(c != w))
        return {"wrong_words": wrong}

    def _expected(self, i: int):
        p = self.pool[i]
        if self.program == "xnor2":
            a, b = (np.asarray(x) for x in p["planes"])
            return [self.ref.xnor2(a, b)]
        a_bits, b_bits = np.asarray(p["a_bits"]), np.asarray(p["b_bits"])
        counts = self.ref.xnor_popcounts(a_bits, b_bits)
        return self.ref.counter_planes(
            counts, work.counter_planes(a_bits.shape[1]))


_JITTED = {}


def _jitted(fn, *static):
    """One jitted function per maker, so every pool set reuses it."""
    import jax
    if fn not in _JITTED:
        _JITTED[fn] = jax.jit(fn, static_argnames=static)
    return _JITTED[fn]


def _xnor2_operands(key, words: int):
    import jax
    ab = jax.random.bits(key, (2, words), np.uint32)
    return ab[0], ab[1]


def _bnn_operands(key, m: int, n: int, k: int):
    """Sign bits A [m, k], B [n, k] and the 2k lane planes of their dot
    (a-planes then b-planes, lane m*n + n)."""
    import jax
    import jax.numpy as jnp
    ka, kb = jax.random.split(key)
    a = jax.random.bernoulli(ka, 0.5, (m, k)).astype(jnp.uint8)
    b = jax.random.bernoulli(kb, 0.5, (n, k)).astype(jnp.uint8)
    a_lanes = jnp.repeat(a.T, n, axis=1)                # [k, m*n]
    b_lanes = jnp.tile(b.T, (1, m))                     # [k, m*n]
    planes = _pack(jnp.concatenate([a_lanes, b_lanes], 0))
    return a, b, tuple(planes[j] for j in range(2 * k))


def _pack(bits):
    """[R, L] {0, 1} -> [R, ceil(L / 32)] little-endian uint32 words."""
    import jax.numpy as jnp
    r, lanes = bits.shape
    pad = (-lanes) % 32
    bits = jnp.pad(bits, ((0, 0), (0, pad))).reshape(r, -1, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (bits.astype(jnp.uint32) << shifts).sum(-1, dtype=jnp.uint32)
