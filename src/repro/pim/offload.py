"""PIM offload pricing: the unified DRIM-vs-TPU placement `Verdict`.

Given any lowered program (`pim.compiler.Lowered` — a Table-2 op, a
fused BulkGraph, or a fence-staged MIMD partition), `build_verdict`
prices every contender with the SAME row fields — compute seconds, DDR
traffic seconds (one shared clock: `core.timing.ddr_rows_s`), energy,
AAP cycles, rows moved — and picks the winner by end-to-end latency:

    DRIM-fused    one resident AAP stream per slot, DMA serialized
    DRIM-queued   per-bank queues: contention stalls + DMA overlapped
    DRIM-unfused  the op-at-a-time chain (host round trip per node)
    TPU           roofline comparator (HBM boundary traffic, VPU floor)

This replaces the three per-path verdict dicts (`plan` / `plan_fused` /
`plan_queued`, PRs 1-4) whose DDR-traffic accounting had drifted apart:
`plan_fused` ignored DMA time on the DRIM rows while `plan_queued`
priced it inline with its own formula.  Those functions remain as
deprecated shims with their historical field layouts and winner rules;
new code calls `Lowered.verdict(n_bits)`.

Verdict logic: bulk bit-ops are BANDWIDTH-bound on the TPU (arithmetic
intensity ~0.1 flop/byte), so DRIM wins whenever operands already live
in DRAM and the result stays there; the TPU wins when operands are
already in HBM/VMEM for adjacent matmuls.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Literal, Optional, Tuple

import numpy as np

from repro.core import DRIM_R, DrimGeometry
from repro.core.energy import E_ACCESS_NJ_PER_KB, E_IO_NJ_PER_KB
from repro.core.subarray import WORD_BITS
from repro.pim.graph import (BulkGraph, FusedSchedule, _make_fused_schedule,
                             plan_graph_schedule)
from repro.pim.scheduler import (OP_ARITY, RESULT_ROWS, _ceil_div,
                                 random_operands)

# TPU v5e roofline constants (brief §Roofline)
TPU_HBM_BW = 819e9          # bytes/s
TPU_VPU_BITOPS = 4 * 8 * 128 * 940e6 * 32  # lanes x clock x bits: ~1.2e15

OpName = Literal["xnor2", "xor2", "not", "maj3", "add", "copy"]

# Payloads above this are priced from the closed-form schedule even when
# simulation is requested — executing them row-by-row would be pointless
# (the schedule math is exactly what execution measures).
SIMULATE_MAX_BITS = 1 << 21

# TPU DRAM access energy when operands must stream HBM<->compute
_TPU_PJ_PER_BYTE = 1.3

_BYTES_MOVED = {"not": 2, "xnor2": 3, "xor2": 3, "maj3": 4, "add": 5,
                "copy": 2}


# ---------------------------------------------------------------------------
# The unified Verdict
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class VerdictRow:
    """One contender, priced with the same fields as every other."""

    contender: str          # "DRIM-fused" | "DRIM-queued" | ... | "TPU"
    latency_s: float        # end-to-end (compute and DMA composed per
                            # the contender's own overlap model)
    compute_s: float
    dma_s: float            # boundary traffic on the shared DDR clock
    energy_j: float
    aaps: int               # serialized AAP cycles (0 for the TPU)
    ddr_rows_moved: int


@dataclasses.dataclass(frozen=True)
class Verdict:
    """Placement verdict for one lowered program at one payload size."""

    workload: str
    n_bits: int
    n_nodes: int
    rows: Tuple[VerdictRow, ...]
    simulated: bool = False

    @property
    def winner(self) -> str:
        return min(self.rows, key=lambda r: r.latency_s).contender

    def row(self, contender: str) -> VerdictRow:
        for r in self.rows:
            if r.contender == contender:
                return r
        raise KeyError(f"no {contender!r} row (have: "
                       f"{', '.join(r.contender for r in self.rows)})")

    def speedup(self, contender: str, over: str) -> float:
        return (self.row(over).latency_s
                / max(self.row(contender).latency_s, 1e-30))

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class TpuCost:
    """Roofline cost of the "tpu" comparator engine: boundary planes
    over HBM, a VPU bit-op floor, DRAM access energy per byte."""

    n_bits: int
    boundary_bytes: float
    compute_s: float
    dma_s: float
    energy_j: float

    @property
    def latency_s(self) -> float:
        return max(self.compute_s, self.dma_s)


def _tpu_row(n_io_planes: int, n_node_bitops: int,
             n_bits: int) -> VerdictRow:
    """THE TPU contender — previously computed three slightly different
    ways across plan/plan_fused/plan_queued; now once."""
    boundary = n_io_planes * n_bits / 8.0
    compute = n_node_bitops * n_bits / TPU_VPU_BITOPS
    dma = boundary / TPU_HBM_BW
    return VerdictRow(
        contender="TPU", latency_s=max(compute, dma), compute_s=compute,
        dma_s=dma, energy_j=boundary * _TPU_PJ_PER_BYTE * 1e-12,
        aaps=0, ddr_rows_moved=0)


def _boundary_planes(lowered) -> Tuple[int, int]:
    """(io planes, node bit-ops) of a lowering, for the TPU row."""
    if lowered.kind == "op":
        return OP_ARITY[lowered.op] + len(RESULT_ROWS[lowered.op]), 1
    fp = lowered.fp
    return len(fp.loaded_inputs) + len(fp.readback_rows), fp.n_nodes


def tpu_cost(lowered, n_bits: int) -> TpuCost:
    """Closed-form cost of the "tpu" engine for `Lowered.cost()`."""
    n_io, n_ops = _boundary_planes(lowered)
    row = _tpu_row(n_io, n_ops, n_bits)
    return TpuCost(n_bits=n_bits, boundary_bytes=n_io * n_bits / 8.0,
                   compute_s=row.compute_s, dma_s=row.dma_s,
                   energy_j=row.energy_j)


def _fused_rows(sched: FusedSchedule) -> Tuple[VerdictRow, VerdictRow]:
    """(DRIM-fused, DRIM-unfused) rows from one fused schedule — DMA
    serialized after compute, both sides on the shared DDR clock."""
    fused = VerdictRow(
        contender="DRIM-fused",
        latency_s=sched.latency_s + sched.dma_s,
        compute_s=sched.latency_s, dma_s=sched.dma_s,
        energy_j=sched.total_energy_j, aaps=sched.aaps_sequential,
        ddr_rows_moved=sched.ddr_rows_moved)
    unfused = VerdictRow(
        contender="DRIM-unfused",
        latency_s=sched.unfused_latency_s + sched.unfused_dma_s,
        compute_s=sched.unfused_latency_s, dma_s=sched.unfused_dma_s,
        energy_j=sched.unfused_total_energy_j,
        aaps=sched.unfused_aaps_sequential,
        ddr_rows_moved=sched.unfused_ddr_rows_moved)
    return fused, unfused


def _queued_row(qsched) -> VerdictRow:
    """The DRIM-queued contender: fence-staged critical path plus
    measured contention stalls, host DMA double-buffered behind
    compute (`overlapped_latency_s`)."""
    return VerdictRow(
        contender="DRIM-queued", latency_s=qsched.overlapped_latency_s,
        compute_s=qsched.latency_s,
        dma_s=qsched.dma_s + qsched.fence_dma_s,
        energy_j=qsched.total_energy_j, aaps=qsched.critical_path_aaps,
        ddr_rows_moved=qsched.ddr_rows_moved)


def _measured_schedule(lowered, n_bits: int):
    """Actually execute the lowering on the functional fleet with
    seeded random operands and return the measured schedule."""
    n_words = -(-n_bits // WORD_BITS)
    if lowered.kind == "op":
        args = random_operands(lowered.op, n_words, seed=n_bits & 0xFFFF)
        lowered.run(*args, n_bits=n_bits)
    else:
        rng = np.random.default_rng(n_bits & 0xFFFF)
        # Reserved constant planes keep their contract (all-zero words)
        # even under random feeds — a traced `a & b` is maj3(a, b, 0).
        consts = set(lowered.traced.const_names) \
            if lowered.traced is not None else set()
        feeds = {name: (np.zeros(n_words, np.uint32) if name in consts
                        else rng.integers(0, 1 << 32, n_words,
                                          dtype=np.uint32))
                 for name in lowered.graph.input_names}
        lowered.run(feeds, n_bits=n_bits)
    return lowered.schedule


def build_verdict(lowered, n_bits: int, *,
                  simulate: bool = False) -> Verdict:
    """Price a lowered program against every contender.

    With `simulate=True` (payloads up to SIMULATE_MAX_BITS) the AAP
    streams actually run on the functional fleet and the DRIM rows
    carry the measured schedule; the closed form prices identical
    numbers otherwise.
    """
    if n_bits <= 0:
        raise ValueError("n_bits must be positive")
    simulated = (simulate and n_bits <= SIMULATE_MAX_BITS
                 and lowered.engine.device)
    sched = (_measured_schedule(lowered, n_bits) if simulated
             else lowered.cost(n_bits))
    n_io, n_ops = _boundary_planes(lowered)
    tpu = _tpu_row(n_io, n_ops, n_bits)

    if lowered.kind == "op":
        arity = OP_ARITY[lowered.op]
        n_res = len(RESULT_ROWS[lowered.op])
        ddr_rows = sched.tiles * (arity + n_res)
        if hasattr(sched, "overlapped_latency_s"):
            drim = dataclasses.replace(_queued_row(sched),
                                       ddr_rows_moved=ddr_rows)
        else:
            # Operands already resident in DRAM, result stays — the
            # paper's premise — so the op row pays no boundary DMA.
            drim = VerdictRow(
                contender=f"DRIM-{lowered.engine.name}",
                latency_s=sched.latency_s, compute_s=sched.latency_s,
                dma_s=0.0, energy_j=sched.energy_j,
                aaps=sched.aaps_sequential, ddr_rows_moved=ddr_rows)
        rows = (drim, tpu)
        name = lowered.op
    else:
        if hasattr(sched, "overlapped_latency_s") or not simulated:
            # The SIMD fused contender did not run (queued/partitioned
            # lowering, or closed-form pricing): rebuild it analytically.
            geom = lowered.geom
            tiles = _ceil_div(n_bits, geom.row_bits)
            waves = _ceil_div(tiles, geom.n_subarrays)
            base = _make_fused_schedule(lowered.fp, n_bits, tiles, waves,
                                        geom)
        else:
            base = sched                  # the measured fused schedule
        fused, unfused = _fused_rows(base)
        rows = (fused, unfused, tpu)
        if hasattr(sched, "overlapped_latency_s"):
            rows = (_queued_row(sched),) + rows
        name = (lowered.traced.name if lowered.traced is not None
                else f"graph[{base.n_nodes}]")
        if getattr(lowered, "harden", None):
            # The redundancy AAPs are in every row above — make the
            # workload say so, or hardened vs bare verdicts look like
            # the same program priced inconsistently.
            name = f"{name}+{lowered.harden}"
        n_ops = base.n_nodes
    return Verdict(workload=name, n_bits=n_bits, n_nodes=n_ops,
                   rows=rows, simulated=simulated)


# ---------------------------------------------------------------------------
# Legacy reports (deprecated shims over the pipeline)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OffloadReport:
    op: str
    n_bits: int
    drim_latency_s: float
    drim_energy_j: float
    drim_aaps: int              # serialized AAP cycles (waves x per-tile)
    tpu_latency_s: float
    tpu_energy_j: float
    winner: str
    speedup: float
    # parallelism breakdown (measured from the schedule)
    tiles: int = 0
    waves: int = 0
    active_subarrays: int = 0   # slots busy in the fullest wave
    occupancy: float = 0.0      # tiles / (waves x slots)
    aaps_issued: int = 0        # total AAPs across active sub-arrays
    simulated: bool = False     # True when the streams actually ran

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def plan(op: OpName, n_bits: int, *, geom: DrimGeometry = DRIM_R,
         operands_in_dram: bool = True,
         simulate: bool = False, mesh=None) -> OffloadReport:
    """DEPRECATED shim: use `compile(op).lower(...).verdict(n_bits)`.

    Keeps the historical OffloadReport layout and winner rule (DRIM
    compute latency vs the TPU roofline, with an explicit host-staging
    penalty when operands are not already in DRAM)."""
    from repro.pim.compiler import _warn_deprecated, compile as _compile
    _warn_deprecated("offload.plan",
                     "compile(op).lower(...).verdict(n_bits)")
    if op not in OP_ARITY or op not in _BYTES_MOVED:
        raise ValueError(f"unknown bulk op {op!r}")
    if n_bits <= 0:
        raise ValueError("n_bits must be positive")
    low = _compile(op, geom=geom).lower(mesh=mesh)
    simulated = simulate and n_bits <= SIMULATE_MAX_BITS
    sched = (_measured_schedule(low, n_bits) if simulated
             else low.cost(n_bits))
    drim_lat = sched.latency_s
    drim_e = sched.energy_j
    kb = n_bits / 8.0 / 1024.0

    tpu = _tpu_row(_BYTES_MOVED[op], 1, n_bits)
    moved_bytes = _BYTES_MOVED[op] * n_bits / 8.0
    if not operands_in_dram:
        # host->DRAM round trip to stage operands for PIM
        drim_e += 2 * (E_ACCESS_NJ_PER_KB + E_IO_NJ_PER_KB) * kb * 1e-9
        drim_lat += moved_bytes / TPU_HBM_BW

    winner = "DRIM" if drim_lat < tpu.latency_s else "TPU"
    return OffloadReport(op=op, n_bits=n_bits, drim_latency_s=drim_lat,
                         drim_energy_j=drim_e,
                         drim_aaps=sched.aaps_sequential,
                         tpu_latency_s=tpu.latency_s,
                         tpu_energy_j=tpu.energy_j,
                         winner=winner,
                         speedup=tpu.latency_s / max(drim_lat, 1e-30),
                         tiles=sched.tiles, waves=sched.waves,
                         active_subarrays=sched.active_subarrays,
                         occupancy=sched.occupancy,
                         aaps_issued=sched.aaps_issued,
                         simulated=simulated)


@dataclasses.dataclass(frozen=True)
class FusedOffloadReport:
    """Placement verdict for a whole fused dataflow graph (legacy
    layout; winner compares DRIM COMPUTE latencies against the TPU —
    the accounting inconsistency `Verdict` fixes)."""

    n_nodes: int
    n_bits: int
    fused_latency_s: float
    fused_energy_j: float
    fused_aaps: int                 # serialized cycles, waves x per-tile
    unfused_latency_s: float
    unfused_energy_j: float
    unfused_aaps: int
    ddr_rows_moved: int
    unfused_ddr_rows_moved: int
    tpu_latency_s: float
    tpu_energy_j: float
    winner: str
    speedup_vs_unfused: float
    speedup_vs_tpu: float
    rows_used: int
    waves: int
    simulated: bool = False

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def plan_fused(graph: BulkGraph, n_bits: int, *,
               geom: DrimGeometry = DRIM_R,
               simulate: bool = False, mesh=None) -> FusedOffloadReport:
    """DEPRECATED shim: use `compile(graph).lower(...).verdict(n_bits)`.

    TPU model: intermediates stay in VMEM, so HBM traffic is the graph
    boundary only (inputs + outputs x n_bits), with a VPU floor of one
    bit-op per node per bit; energy charges DRAM access per byte moved.
    """
    from repro.pim.compiler import _warn_deprecated, compile as _compile
    _warn_deprecated("offload.plan_fused",
                     "compile(graph).lower(...).verdict(n_bits)")
    low = _compile(graph, geom=geom).lower(mesh=mesh)
    if n_bits <= 0:
        raise ValueError("n_bits must be positive")
    simulated = simulate and n_bits <= SIMULATE_MAX_BITS
    sched = (_measured_schedule(low, n_bits) if simulated
             else low.cost(n_bits))
    tpu = _tpu_row(sched.n_inputs + sched.n_outputs, sched.n_nodes,
                   n_bits)

    fused_lat = sched.latency_s
    unfused_lat = sched.unfused_latency_s
    lats = {"DRIM-fused": fused_lat, "DRIM-unfused": unfused_lat,
            "TPU": tpu.latency_s}
    return FusedOffloadReport(
        n_nodes=sched.n_nodes, n_bits=n_bits,
        fused_latency_s=fused_lat, fused_energy_j=sched.total_energy_j,
        fused_aaps=sched.aaps_sequential,
        unfused_latency_s=unfused_lat,
        unfused_energy_j=sched.unfused_total_energy_j,
        unfused_aaps=sched.unfused_aaps_sequential,
        ddr_rows_moved=sched.ddr_rows_moved,
        unfused_ddr_rows_moved=sched.unfused_ddr_rows_moved,
        tpu_latency_s=tpu.latency_s, tpu_energy_j=tpu.energy_j,
        winner=min(lats, key=lats.get),
        speedup_vs_unfused=unfused_lat / max(fused_lat, 1e-30),
        speedup_vs_tpu=tpu.latency_s / max(fused_lat, 1e-30),
        rows_used=sched.rows_used, waves=sched.waves,
        simulated=simulated)


@dataclasses.dataclass(frozen=True)
class QueuedOffloadReport:
    """Placement verdict for a graph run through per-bank MIMD queues
    (legacy layout).  Queued latency is the OVERLAPPED model; the
    serialized figure and the stall count are reported so the verdict's
    ingredients are auditable."""

    n_nodes: int
    n_bits: int
    n_queues: int
    fence_stages: int
    critical_path_aaps: int
    issued_aaps: int
    contention_stall_aaps: int
    queued_latency_s: float
    queued_serialized_latency_s: float
    dma_overlap_speedup: float
    cross_fence_rows: int
    fused_latency_s: float          # SIMD fused compute + serialized DMA
    fused_aaps: int
    tpu_latency_s: float
    tpu_energy_j: float
    winner: str
    speedup_vs_fused: float
    speedup_vs_tpu: float
    rows_used: int
    waves: int
    simulated: bool = False

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def plan_queued(graph: BulkGraph, n_bits: int, *,
                n_queues: Optional[int] = None,
                geom: DrimGeometry = DRIM_R,
                simulate: bool = False, mesh=None) -> QueuedOffloadReport:
    """DEPRECATED shim: use `compile(graph).lower(partition=True,
    n_queues=...).verdict(n_bits)`.

    The queued side pays the fence-staged critical path plus measured
    command-bus stalls, with host DMA overlapped (double-buffered
    waves); the SIMD fused side pays its shorter wave count but
    serializes the same DMA after compute — both DMA figures now read
    off the one shared DDR clock (`FusedSchedule.dma_s`).
    """
    from repro.pim.compiler import _warn_deprecated, compile as _compile
    _warn_deprecated(
        "offload.plan_queued",
        "compile(graph).lower(partition=True, n_queues=...)"
        ".verdict(n_bits)")
    if n_bits <= 0:
        raise ValueError("n_bits must be positive")
    low = _compile(graph, geom=geom).lower(partition=True,
                                           n_queues=n_queues, mesh=mesh)
    simulated = simulate and n_bits <= SIMULATE_MAX_BITS
    qsched = (_measured_schedule(low, n_bits) if simulated
              else low.cost(n_bits))
    fsched = plan_graph_schedule(graph, n_bits, geom=geom)
    fused_lat = fsched.latency_s + fsched.dma_s

    tpu = _tpu_row(fsched.n_inputs + fsched.n_outputs, fsched.n_nodes,
                   n_bits)
    queued_lat = qsched.overlapped_latency_s
    lats = {"DRIM-queued": queued_lat, "DRIM-fused": fused_lat,
            "TPU": tpu.latency_s}
    return QueuedOffloadReport(
        n_nodes=qsched.n_nodes, n_bits=n_bits, n_queues=qsched.n_queues,
        fence_stages=qsched.fence_stages,
        critical_path_aaps=qsched.critical_path_aaps,
        issued_aaps=qsched.aaps_issued,
        contention_stall_aaps=qsched.contention_stall_aaps,
        queued_latency_s=queued_lat,
        queued_serialized_latency_s=qsched.serialized_latency_s,
        dma_overlap_speedup=qsched.dma_overlap_speedup,
        cross_fence_rows=qsched.cross_rows_per_tile * qsched.tiles,
        fused_latency_s=fused_lat, fused_aaps=fsched.aaps_sequential,
        tpu_latency_s=tpu.latency_s, tpu_energy_j=tpu.energy_j,
        winner=min(lats, key=lats.get),
        speedup_vs_fused=fused_lat / max(queued_lat, 1e-30),
        speedup_vs_tpu=tpu.latency_s / max(queued_lat, 1e-30),
        rows_used=qsched.rows_used, waves=qsched.waves,
        simulated=simulated)


def serving_verdict(m: int, n: int, k_bits: int, *,
                    geom: Optional[DrimGeometry] = None,
                    engine: str = "resident",
                    n_queues: Optional[int] = None,
                    k_tile: Optional[int] = None) -> Verdict:
    """Price one served BitLinear decode GEMM ([m, K] x [K, n]).

    Uses the SAME cached lowerings `pim.bnn.serve_bnn_matmul` executes
    (via `compiler.lower_cached`), priced by `build_verdict` at
    n_bits = m*n lanes per K chunk, with every row field summed across
    the serialized chunks: it prices each chunk as a run of its own,
    which is what a DRIM that spends a wave per chunk would do (the
    serving path packs a GEMM's chunks into the lanes of one run).  The
    TPU roofline row sums the same way, so the Verdict compares like
    with like.
    """
    from repro.pim.bnn import k_chunks, serving_lowering
    chunks = k_chunks(k_bits, k_tile)
    counts: Dict[int, int] = {}
    for kc in chunks:
        counts[kc] = counts.get(kc, 0) + 1
    n_nodes = 0
    acc: Dict[str, VerdictRow] = {}
    order = []
    for kc, count in counts.items():
        low = serving_lowering(kc, engine=engine, geom=geom,
                               n_queues=n_queues)
        v = build_verdict(low, m * n)
        n_nodes += v.n_nodes * count
        for r in v.rows:
            prev = acc.get(r.contender)
            if prev is None:
                order.append(r.contender)
                prev = VerdictRow(contender=r.contender, latency_s=0.0,
                                  compute_s=0.0, dma_s=0.0, energy_j=0.0,
                                  aaps=0, ddr_rows_moved=0)
            acc[r.contender] = VerdictRow(
                contender=r.contender,
                latency_s=prev.latency_s + r.latency_s * count,
                compute_s=prev.compute_s + r.compute_s * count,
                dma_s=prev.dma_s + r.dma_s * count,
                energy_j=prev.energy_j + r.energy_j * count,
                aaps=prev.aaps + r.aaps * count,
                ddr_rows_moved=prev.ddr_rows_moved
                + r.ddr_rows_moved * count)
    return Verdict(workload=f"bitlinear[{m}x{n}x{k_bits}]",
                   n_bits=m * n, n_nodes=n_nodes,
                   rows=tuple(acc[c] for c in order))


def plan_model_payloads(cfg) -> Dict[str, Verdict]:
    """Price the framework's own bulk-bitwise payloads for an arch
    config (1-bit EF gradient all-reduce planes + BitLinear sign
    planes) through the unified pipeline — one Verdict per payload."""
    from repro.pim.compiler import compile as _compile
    n_params = cfg.param_count()
    payloads = (("grad_sign_reduce(add)", "add"),
                ("bitlinear_weight_xnor", "xnor2"),
                ("weight_sign_copy", "copy"))
    return {name: _compile(op).lower().verdict(n_params)
            for name, op in payloads}
