"""Fused BNN dot-product on the DRIM fleet: XNOR -> popcount-accumulate.

The dominant consumer of bulk X(N)OR is the binarized matmul
(`kernels/xnor_popcount.py`):  C[m,n] = 2*popcount(XNOR(a, b)) - K.  On
DRIM the natural layout is *vertical* (bit-serial): lane ℓ — one bit-line
position across the fleet's rows — holds one output element (m, n), and
row k holds bit k of every lane's operand pair.  Two popcount dataflows:

  * RIPPLE (PR 2, `bnn_dot_graph`):

        for k in 0..K-1:   p_k = xnor2(a_k, b_k)      # 1 AAP (fused DRA)
                           counter += p_k             # ripple-carry

    with a ceil(log2(K+1))-plane resident counter — every plane costs a
    FULL ripple (nbits Table-2 `add` slices, 7 AAPs each), so the
    stream grows as K * (1 + 7*nbits).

  * CARRY-SAVE (`bnn_dot_graph_carrysave`): a 3:2-compressor counter
    network.  A Table-2 full adder takes THREE weight-w planes and
    produces one weight-w sum plus one weight-(w+1) carry, so each
    adder retires a whole plane instead of one counter bit: the K XNOR
    planes compress level by level until every weight holds a single
    plane — the binary popcount.  ~K adders total (vs K*nbits), and the
    tree exposes graph-level parallelism the ripple chain cannot:
    `pim.queue.execute_partitioned` runs disjoint subtrees on different
    bank queues concurrently (MIMD), shrinking the critical path again.

Either way the whole thing is ONE AAP stream per slot (or one per bank
queue); the 2K+1 operand planes are loaded once per tile and only the
counter planes are read back — the operand-locality win the paper
claims for in-situ X(N)OR chains.  `bnn_dot_drim()` runs it end-to-end
on the simulator and returns the int32 dot products, bit-exact vs
`kernels/ref.py:xnor_gemm_ref`.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import DRIM_R, DrimGeometry
from repro.core.subarray import WORD_BITS
from repro.pim.graph import BulkGraph, FusedSchedule
from repro.runtime import telemetry

# Serving reduction tile: the carry-save graph keeps ~2K+1 data rows
# simultaneously live at the XNOR level, so K beyond the ~500-row
# sub-array budget cannot lower (K=256 needs 513 live rows).  The
# serving path tiles the reduction dim into <=128-column chunks — chunk
# dots sum exactly (dot is linear in K) — and each distinct chunk width
# is one cached kernel for the whole process.
DEFAULT_K_TILE = 128


def counter_bits(k_bits: int) -> int:
    """Bit-planes needed to count K ones: ceil(log2(K+1))."""
    return max(1, math.ceil(math.log2(k_bits + 1)))


def bnn_dot_graph(k_bits: int) -> BulkGraph:
    """XNOR -> popcount-accumulate dataflow over K bit-plane inputs.

    Inputs: a0..a{K-1}, b0..b{K-1} (operand bit-planes) and `zero` (the
    constant third full-adder operand).  Outputs: c0..c{nbits-1}, the
    popcount as resident counter bit-planes.  Each XNOR plane dies into
    its first accumulate slice, so the fused compiler issues it as a
    single in-place DRA — the paper's headline op, chained K deep.
    """
    if k_bits < 1:
        raise ValueError("k_bits must be positive")
    nbits = counter_bits(k_bits)
    g = BulkGraph()
    a = [g.input(f"a{k}") for k in range(k_bits)]
    b = [g.input(f"b{k}") for k in range(k_bits)]
    zero = g.input("zero")
    acc = [zero] * nbits
    for k in range(k_bits):
        carry = g.op("xnor2", a[k], b[k])
        # counter += plane: full-adder per counter bit, carry ripples up
        # (the counter cannot overflow nbits by construction, so the
        # final carry is dead and its row is recycled immediately).
        for i in range(nbits):
            acc[i], carry = g.op("add", acc[i], carry, zero)
    for i in range(nbits):
        g.output(f"c{i}", acc[i])
    return g


def bnn_dot_graph_carrysave(k_bits: int) -> Tuple[BulkGraph, int]:
    """Carry-save 3:2-compressor tree popcount over K bit-plane inputs.

    Same inputs/outputs as `bnn_dot_graph` (a0.., b0.., `zero`; counter
    planes c0..c{nbits-1}), different dataflow: the K XNOR planes sit at
    weight 0; while any weight level holds >= 3 planes a full adder
    compresses three into sum (same weight) + carry (next weight), a
    final half adder (`add` with the zero plane) settles levels left
    with two.  Every level ends with exactly one plane — bit w of the
    popcount.  Returns (graph, nbits); nbits always equals
    `counter_bits(k_bits)` (the tree computes the exact sum, and its
    level count is the binary width of K).
    """
    if k_bits < 1:
        raise ValueError("k_bits must be positive")
    g = BulkGraph()
    a = [g.input(f"a{k}") for k in range(k_bits)]
    b = [g.input(f"b{k}") for k in range(k_bits)]
    zero = g.input("zero")
    levels: List[List] = [[g.op("xnor2", a[k], b[k])
                           for k in range(k_bits)]]
    w = 0
    while w < len(levels):
        vals = levels[w]
        carries: List = []
        while len(vals) >= 3:
            s, c = g.op("add", vals[0], vals[1], vals[2])
            vals = vals[3:] + [s]
            carries.append(c)
        if len(vals) == 2:
            s, c = g.op("add", vals[0], vals[1], zero)
            vals = [s]
            carries.append(c)
        levels[w] = vals
        if carries:
            if w + 1 < len(levels):
                levels[w + 1].extend(carries)
            else:
                levels.append(carries)
        w += 1
    for i, vals in enumerate(levels):
        g.output(f"c{i}", vals[0])
    return g, len(levels)


def stage_bnn_planes(a_bits: np.ndarray, b_bits: np.ndarray,
                     ) -> Tuple[Dict[str, np.ndarray], int]:
    """Lay out an [M, K] x [N, K] binary GEMM as vertical bit-planes.

    a_bits/b_bits hold sign bits in {0, 1}.  Lane m*N + n computes
    output element (m, n); plane a_k broadcasts A[:, k] across the N
    columns, plane b_k tiles B[:, k] across the M rows.  Lanes are
    packed into uint32 words (padded with zero lanes; callers pass
    n_bits = M*N to `execute_graph` to mark the ragged tail).
    Returns (feeds, n_lanes).
    """
    k_bits = a_bits.shape[1]
    if k_bits != b_bits.shape[1]:
        raise ValueError("operand K dimensions differ")
    planes, lanes = _stage_chunk_planes(a_bits, b_bits)
    names = ([f"a{k}" for k in range(k_bits)]
             + [f"b{k}" for k in range(k_bits)])
    feeds: Dict[str, np.ndarray] = dict(zip(names, planes))
    feeds["zero"] = np.zeros_like(planes[0])
    return feeds, lanes


def decode_counts(outs: Dict[str, jax.Array], nbits: int,
                  lanes: int) -> np.ndarray:
    """Counter bit-planes -> per-lane popcount (int32)."""
    count = np.zeros(lanes, np.int32)
    for i in range(nbits):
        words = np.asarray(outs[f"c{i}"]).view(np.uint32)
        bits = np.unpackbits(words.view(np.uint8), bitorder="little")
        count += bits[:lanes].astype(np.int32) << i
    return count


def bnn_dot_drim(a_bits: np.ndarray, b_bits: np.ndarray, *,
                 geom: DrimGeometry = DRIM_R,
                 accumulate: str = "ripple", engine: str = "resident",
                 mesh=None, n_queues: Optional[int] = None,
                 ) -> Tuple[np.ndarray, FusedSchedule]:
    """Full fused BNN dot-product on the simulated fleet.

    a_bits [M, K], b_bits [N, K] sign bits in {0, 1}.  Returns
    (C [M, N] int32 with C = 2*popcount(XNOR) - K, schedule).

    `accumulate` picks the popcount dataflow: "ripple" (the PR 2
    counter) or "carrysave" (the 3:2-compressor tree — strictly fewer
    AAPs on the critical path); `engine`/`mesh`/`n_queues` thread
    through the `pim.compiler` pipeline lowering.
    """
    from repro.pim.compiler import compile as drim_compile
    m, k_bits = a_bits.shape
    n = b_bits.shape[0]
    if accumulate == "ripple":
        graph, nbits = bnn_dot_graph(k_bits), counter_bits(k_bits)
    elif accumulate == "carrysave":
        graph, nbits = bnn_dot_graph_carrysave(k_bits)
    else:
        raise ValueError(f"unknown accumulate mode {accumulate!r}")
    feeds, lanes = stage_bnn_planes(a_bits, b_bits)
    low = drim_compile(graph, geom=geom).lower(engine=engine, mesh=mesh,
                                               n_queues=n_queues)
    outs = low.run(feeds, n_bits=lanes)
    count = decode_counts(outs, nbits, lanes)
    return (2 * count - k_bits).reshape(m, n), low.schedule


def bnn_dot_partitioned(a_bits: np.ndarray, b_bits: np.ndarray, *,
                        geom: DrimGeometry = DRIM_R,
                        n_queues: Optional[int] = None, mesh=None,
                        ) -> Tuple[np.ndarray, "QueueSchedule"]:
    """The first MIMD workload: the carry-save popcount tree split
    across per-bank command queues.

    Disjoint compressor subtrees run on different bank queues
    concurrently (`lower(partition=True)` — the `pim.queue` MIMD
    runner), with cross-bank fences where subtrees merge — the critical
    path is the fence-staged slowest queue, not the whole tree.
    Bit-exact vs `kernels/ref.py:xnor_gemm_ref` like every other path.
    """
    from repro.pim.compiler import compile as drim_compile
    m, k_bits = a_bits.shape
    n = b_bits.shape[0]
    graph, nbits = bnn_dot_graph_carrysave(k_bits)
    feeds, lanes = stage_bnn_planes(a_bits, b_bits)
    low = drim_compile(graph, geom=geom).lower(partition=True,
                                               n_queues=n_queues,
                                               mesh=mesh)
    outs = low.run(feeds, n_bits=lanes)
    count = decode_counts(outs, nbits, lanes)
    return (2 * count - k_bits).reshape(m, n), low.schedule


# ---------------------------------------------------------------------------
# The serving path: BitLinear decode GEMMs routed through drim.jit
# ---------------------------------------------------------------------------

def k_chunks(k_bits: int, k_tile: Optional[int] = None) -> Tuple[int, ...]:
    """Split a reduction width into row-budget-sized kernel chunks."""
    tile = k_tile or DEFAULT_K_TILE
    if k_bits < 1:
        raise ValueError("k_bits must be positive")
    if tile < 1:
        raise ValueError("k_tile must be positive")
    chunks = [tile] * (k_bits // tile)
    if k_bits % tile:
        chunks.append(k_bits % tile)
    return tuple(chunks)


@functools.lru_cache(maxsize=None)
def bitlinear_kernel(k_bits: int):
    """The serving kernel for one reduction width, traced ONCE.

    A `drim.jit` function over 2K bit-planes (a0..a{K-1}, b0..b{K-1})
    returning the carry-save popcount of the XNOR planes — node for
    node the dataflow of `bnn_dot_graph_carrysave`, but arriving
    through the same front door a user program would.  lru-cached so a
    decode loop traces each (layer-shape) K exactly once per process.
    """
    from repro.pim import frontend

    def body(*planes):
        xn = [frontend.xnor(a, b)
              for a, b in zip(planes[:k_bits], planes[k_bits:])]
        return frontend.popcount(xn)

    names = [f"a{i}" for i in range(k_bits)] \
        + [f"b{i}" for i in range(k_bits)]
    return frontend.jit(body, arg_names=names,
                        name=f"bitlinear_dot[K={k_bits}]")


def serving_lowering(k_bits: int, *, engine: str = "resident",
                     geom: Optional[DrimGeometry] = None, mesh=None,
                     n_queues: Optional[int] = None):
    """compile→lower the serving kernel once per (K, engine, geometry,
    mesh, queues) via the process-wide `compiler.lower_cached` memo —
    shared with `offload.serving_verdict`, so serving execution and
    pricing read the same `Lowered`."""
    from repro.pim import compiler
    return compiler.lower_cached(
        bitlinear_kernel(k_bits).trace(),
        key=("bitlinear_dot", k_bits), geom=geom, engine=engine,
        mesh=mesh, n_queues=n_queues)


# Bytes of unpacked lane bits (one uint8 a lane) `_stage_chunk_planes`
# fills at once: a decode GEMM's group stages in one pass per operand,
# a prefill-sized GEMM in slabs of planes, so the transient stays small
# beside the packed planes.
_STAGE_SLAB_BYTES = 1 << 25

# Always-on counters of `serve_bnn_matmul` (registry namespace
# "offload", beside `compiler.RUN_STATS`): "offload.chunks" per K chunk
# served and "offload.runs" per `Lowered.run` it issues.
OFFLOAD_STATS = telemetry.REGISTRY.counters("offload")


def _stage_chunk_planes(a_bits: np.ndarray, b_bits: np.ndarray,
                        group: int = 1) -> Tuple[List[np.ndarray], int]:
    """`stage_bnn_planes` layout as the positional plane list the traced
    kernel takes (a-planes then b-planes), for `group` K chunks of equal
    width kc laid side by side: a_bits [M, group*kc], b_bits
    [N, group*kc].  Lane c*M*N + m*N + n holds output (m, n) of chunk
    c; plane a_k holds A[:, c*kc + k] repeated N times and plane b_k
    holds B[:, c*kc + k] tiled M times, for each c.  Returns (planes,
    lanes = group*M*N)."""
    m, width = a_bits.shape
    n = b_bits.shape[0]
    kc = width // group
    lanes = group * m * n
    n_words = -(-lanes // WORD_BITS)
    # [kc, group, M, 1] and [kc, group, 1, N], broadcast over the lanes
    a_t, b_t = (np.ascontiguousarray(x.T).reshape(group, kc, -1)
                .swapaxes(0, 1) for x in (a_bits, b_bits))
    sides = (a_t[..., None], b_t[:, :, None])
    step = max(1, min(kc, _STAGE_SLAB_BYTES // (n_words * WORD_BITS)))
    bits = np.zeros((step, n_words * WORD_BITS), np.uint8)
    words = np.empty((2, kc, n_words), np.uint32)
    for side, src in enumerate(sides):
        for k0 in range(0, kc, step):
            k1 = min(kc, k0 + step)
            # a view of the slab's lanes: the operand bits land in place
            bits[:k1 - k0, :lanes].reshape(k1 - k0, group, m, n)[...] = \
                src[k0:k1]
            words[side, k0:k1] = np.packbits(
                bits[:k1 - k0], axis=-1, bitorder="little").view(np.uint32)
    return list(words.reshape(2 * kc, n_words)), lanes


def _chunks_per_run(lanes: int, geom: DrimGeometry) -> int:
    """K chunks of one GEMM that share a `Lowered.run`: as many
    `lanes`-wide chunks as one wave holds, so a group never needs more
    waves than one chunk; 1 once a chunk alone fills a wave."""
    return max(1, geom.parallel_bits // lanes)


def serve_bnn_matmul(a_bits: np.ndarray, b_bits: np.ndarray, *,
                     engine: str = "resident",
                     geom: Optional[DrimGeometry] = None, mesh=None,
                     n_queues: Optional[int] = None,
                     k_tile: Optional[int] = None) -> np.ndarray:
    """Serving-path binary GEMM on the DRIM fleet.

    a_bits [M, K], b_bits [N, K] sign bits in {0, 1}; returns C [M, N]
    int32 = the ±1 dot, bit-exact vs `kernels/ref.py:xnor_gemm_ref`.
    The reduction dim tiles into `k_chunks` (sub-array row budget),
    each width one cached carry-save `drim.jit` kernel.  Chunks of one
    width run in groups of `_chunks_per_run`, side by side in the lanes
    of one run (`_stage_chunk_planes`), so a decode GEMM whose M*N lanes
    fill a sliver of a wave pays for one run, not one per chunk; a
    ragged last chunk runs alone.  The partial dots sum exactly: sum
    over chunks of (2*pop_c - K_c) == 2*popcount(XNOR) - K.
    """
    a_bits = np.asarray(a_bits, np.uint8)
    b_bits = np.asarray(b_bits, np.uint8)
    if a_bits.ndim != 2 or b_bits.ndim != 2:
        raise ValueError("serve_bnn_matmul takes 2-D sign-bit operands")
    m, k_bits = a_bits.shape
    n, kb2 = b_bits.shape
    if k_bits != kb2:
        raise ValueError("operand K dimensions differ")
    lanes = m * n
    total = np.zeros(lanes, np.int32)
    offset = 0
    with telemetry.span("offload", cat="offload", tid="run", m=m, n=n,
                        k=k_bits, engine=engine):
        widths = collections.Counter(k_chunks(k_bits, k_tile))
        for kc, n_chunks in widths.items():
            low = serving_lowering(kc, engine=engine, geom=geom, mesh=mesh,
                                   n_queues=n_queues)
            per_run = _chunks_per_run(lanes, low.geom)
            for first in range(0, n_chunks, per_run):
                group = min(per_run, n_chunks - first)
                end = offset + group * kc
                with telemetry.span("offload.pack", cat="offload",
                                    tid="run"):
                    planes, run_lanes = _stage_chunk_planes(
                        a_bits[:, offset:end], b_bits[:, offset:end], group)
                outs = low.run(*planes, n_bits=run_lanes)
                OFFLOAD_STATS["runs"] += 1
                OFFLOAD_STATS["chunks"] += group
                with telemetry.span("offload.unpack", cat="offload",
                                    tid="run"):
                    pop = np.zeros(run_lanes, np.int32)
                    for i, plane in enumerate(outs):
                        bits = np.unpackbits(
                            np.asarray(plane).view(np.uint8),
                            bitorder="little")
                        pop += bits[:run_lanes].astype(np.int32) << i
                    total += (2 * pop.reshape(group, lanes)
                              - kc).sum(axis=0, dtype=np.int32)
                offset = end
    return total.reshape(m, n)
