"""Asynchronous per-bank command queues: MIMD execution on the fleet.

Every engine before this one is lock-step SIMD: all (chip, bank,
subarray) slots run the SAME AAP stream behind one shared program
counter, so two effects the in-DRAM processing literature models
explicitly (SIMDRAM's bank-level scheduling, Ambit/RowClone host-DMA
overlap) are invisible.  This module gives each bank block its own
command queue:

  * the bank axis is split into `n_queues` contiguous blocks, each with
    its OWN encoded AAP stream, program counter, and issue-cycle clock;
  * one jitted dispatch executes every queue's stream concurrently
    (`run_waves_queued` — per-queue `isa.run_program_unrolled`
    specializations of the shared `scheduler.wave_fn` body), under
    `shard_map` over a queue-compatible (chips, banks) mesh when the
    caller passes a fleet mesh;
  * `QueueSchedule` extends the fused cost model with what the
    independent clocks expose: per-queue busy cycles, shared command-bus
    contention stalls (`isa.simulate_bus_issue` — one channel issues
    `CMDS_PER_AAP` commands per AAP out of `CMD_SLOTS_PER_AAP` slots in
    its envelope, so ~36 queues saturate a DDR4 channel), and host DMA
    double-buffered behind compute instead of serialized after it.

With every queue running the same program this degrades exactly to the
SIMD engines (the differential suite holds "queued" bit-identical to
"resident"/"baseline").  The point of the independent counters is
`execute_partitioned`: `graph.partition_graph` splits ONE BulkGraph
across queues into per-bank sub-programs separated by cross-bank
dependency fences, so different bank blocks run DIFFERENT programs —
graph-level (MIMD) parallelism whose latency is the fence-staged
critical path (sum over stages of the slowest queue) instead of the
whole node list.  `pim/bnn.py` uses it to run the carry-save
3:2-compressor popcount tree that beats the PR 2 ripple accumulate.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.core import (AAP, CMDS_PER_AAP, DRIM_R, DrimGeometry,
                        FaultModel, simulate_bus_issue)
from repro.core.subarray import WORD_BITS
from repro.core.timing import CMD_SLOTS_PER_AAP, ddr_rows_s
from repro.pim.graph import (DEFAULT_ROW_BUDGET, BulkGraph, FusedSchedule,
                             GraphPartition, partition_graph)
from repro.pim.mesh import STAGED_SPEC, fleet_mesh
from repro.pim.scheduler import (N_DATA_ROWS, OP_ARITY, RESULT_ROWS,
                                 TRACE_COUNTS, _ceil_div, encoded_program,
                                 stage_rows, wave_fn)
from repro.runtime import telemetry

# A queue per bank is the hardware concept, but a 256-bank DRIM-S sweep
# would unroll 256 separate program streams into one XLA computation —
# pure compile-time pain for zero modeling gain, since blocks of banks
# behind one controller clock are indistinguishable from single banks.
# Default: one queue per bank, capped at this many queue blocks.
DEFAULT_MAX_QUEUES = 8


def default_n_queues(geom: DrimGeometry) -> int:
    """Largest divisor of the bank count <= DEFAULT_MAX_QUEUES."""
    return max(d for d in range(1, min(geom.banks, DEFAULT_MAX_QUEUES) + 1)
               if geom.banks % d == 0)


def resolve_n_queues(geom: DrimGeometry, n_queues: Optional[int]) -> int:
    if n_queues is None:
        return default_n_queues(geom)
    if not 1 <= n_queues <= geom.banks or geom.banks % n_queues:
        raise ValueError(
            f"n_queues={n_queues} must divide the bank count {geom.banks}")
    return n_queues


def bank_blocks(banks: int, n_queues: int) -> Tuple[Tuple[int, int], ...]:
    """Contiguous [lo, hi) bank block per queue."""
    if banks % n_queues:
        raise ValueError(f"{n_queues} queues do not divide {banks} banks")
    w = banks // n_queues
    return tuple((q * w, (q + 1) * w) for q in range(n_queues))


def queue_mesh(geom: DrimGeometry, n_queues: int, mesh=None):
    """A fleet mesh compatible with per-queue payloads.

    Queue payloads carry `banks / n_queues` banks, so a caller's fleet
    mesh (built for the FULL bank axis) generally cannot shard them.
    Rebuild over the same devices for the queue-block geometry — the
    largest (chips, banks) split that divides every queue's block.
    `None` stays `None` (no shard_map).

    Known limitation: ONE mesh shards every queue's payload (the MIMD
    runner is a single `shard_map` body), so on an N-device host the
    queue blocks share the first `mc x mb` devices instead of spreading
    across disjoint device blocks — bit-exactness and the cost model
    are unaffected, but device-level queue concurrency is not yet
    exploited (see ROADMAP: queue-level dynamic scheduling).
    """
    if mesh is None:
        return None
    geom_q = dataclasses.replace(geom, banks=geom.banks // n_queues)
    return fleet_mesh(geom_q, devices=list(mesh.devices.flat))


@functools.lru_cache(maxsize=256)
def _queued_stager(n_arrays: int, n_words: int, lead: Tuple[int, ...],
                   n_queues: int, mesh):
    """Compiled queued staging kernel: pad + tile every operand AND
    split the bank axis into queue blocks in one fused dispatch — the
    per-queue payloads are written directly (shard-aligned on `mesh`),
    never materializing the full staged array the SIMD stager builds."""
    pad = lead[0] * lead[1] * lead[2] * lead[3] * lead[4] - n_words
    blocks = bank_blocks(lead[2], n_queues)

    def impl(arrays: Tuple[jax.Array, ...]) -> Tuple[jax.Array, ...]:
        tiled = [jnp.pad(jnp.asarray(a, jnp.uint32), (0, pad))
                 .reshape(lead) for a in arrays]
        # stack per queue block directly — materializing the full SIMD
        # stack and slicing it would copy the payload twice
        return tuple(jnp.stack([t[:, :, lo:hi] for t in tiled], axis=1)
                     for lo, hi in blocks)

    shardings = None
    if mesh is not None:
        shardings = (NamedSharding(mesh, STAGED_SPEC),) * n_queues
    return jax.jit(impl, out_shardings=shardings)


def stage_rows_queued(arrays: Sequence[jax.Array], *, geom: DrimGeometry,
                      n_queues: int, mesh=None,
                      ) -> Tuple[Tuple[jax.Array, ...], int, int]:
    """Tile flat word arrays onto the fleet's bank queues: one fused
    pad/tile/split dispatch producing the per-queue payloads
    [waves, n_arrays, chips, banks_q, subarrays, row_words], each
    device-resident (shard-aligned over the queue mesh when given).
    Same tile -> slot order as `scheduler.stage_rows` by construction.
    Returns (staged_per_queue, tiles, waves)."""
    n_words = arrays[0].shape[0]
    row_w = geom.row_bits // WORD_BITS
    tiles = _ceil_div(n_words, row_w)
    waves = _ceil_div(tiles, geom.n_subarrays)
    lead = (waves, geom.chips, geom.banks, geom.subarrays_per_bank, row_w)
    staged_qs = _queued_stager(len(arrays), n_words, lead, n_queues,
                               mesh)(tuple(arrays))
    return staged_qs, tiles, waves


# ---------------------------------------------------------------------------
# The MIMD wave runner
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _queued_runner(programs, result_rows, n_rows, mesh, donate,
                   body_engine="queued", faults=None, bank_geoms=None):
    """Compiled multi-queue executor for one (programs, readbacks, mesh,
    body engine, faults) signature: every queue's stream is a separate
    specialization of the shared `scheduler.wave_fn` body — trace-time
    unrolled for "queued", the Pallas interpreter for "pallas" — issued
    in ONE jitted computation so XLA schedules the queues concurrently:
    N independent program counters, one dispatch.  `donate=True` hands
    every staged payload to XLA for in-place output reuse (same
    condition as the resident engine's wave runner).

    faults: None, or one `FaultModel` per queue (hardening may protect
    different op ranges per queue); `bank_geoms[q]` = (bank_lo,
    banks_total) anchors queue q's payload at its physical banks so its
    flips match the SIMD engines'."""
    per_q_faults = faults if faults is not None else (None,) * len(programs)
    per_q_geoms = (bank_geoms if bank_geoms is not None
                   else (None,) * len(programs))

    def body(*staged_qs):
        TRACE_COUNTS["wave_body_queued"] += 1
        return tuple(
            jax.lax.map(wave_fn(body_engine, prog, rr, nr, fm, bg), st)
            for prog, rr, nr, fm, bg, st in zip(programs, result_rows,
                                                n_rows, per_q_faults,
                                                per_q_geoms, staged_qs))

    fn = body
    if mesh is not None:
        specs = (STAGED_SPEC,) * len(programs)
        fn = jax.shard_map(body, mesh=mesh, in_specs=specs,
                           out_specs=specs, check_vma=False)
    return jax.jit(fn, donate_argnums=tuple(range(len(programs)))
                   if donate else ())


def run_waves_queued(staged_qs: Sequence[jax.Array],
                     programs: Sequence[Sequence[AAP]],
                     result_rows: Sequence[Tuple[int, ...]],
                     n_rows: Sequence[int], *, mesh=None,
                     body_engine: str = "queued", faults=None,
                     bank_geoms=None, timings=None) -> Tuple[jax.Array, ...]:
    """Execute one wave payload per bank queue, each under its own
    program stream and program counter, in one traced computation.

    staged_qs[q]: [waves_q, n_rows_in_q, chips, banks_q, subarrays,
    row_words] — queue q's tile block; `programs[q]` is its AAP stream,
    resolved against a template with `n_rows[q]` normal rows.  Queues
    need not agree on program length, staged row count, or readback
    rows; they must agree on the (chips, banks_q, subarrays) block
    shape so one queue-compatible `mesh` can shard them all.  Every
    per-queue encoded stream goes through the `encoded_program` memo
    tagged with its queue id, so mixed multi-program streams are
    audited per queue (``ENCODE_CACHE_STATS["q{q}:hits"]``).

    timings: optional dict — when given, the jit compile is split out
    via AOT ``runner.lower(...).compile()`` and its wall-clock is
    ACCUMULATED under ``timings["compile_s"]``, so callers timing a
    dispatch (the chaos recovery path, benchmarks) can report execute
    time without the one-off XLA compile folded in.

    Returns one [waves_q, len(result_rows[q]), ...] readback per queue.
    """
    if not (len(staged_qs) == len(programs) == len(result_rows)
            == len(n_rows)):
        raise ValueError("one staged payload, program, readback row "
                         "tuple and template size per queue required")
    progs = tuple(tuple(p) for p in programs)
    for qid, p in enumerate(progs):
        # memo + per-queue accounting only; the unrolled engine never
        # reads the encoded stream, so don't materialize it
        encoded_program(p, queue=qid, materialize=False)
    if faults is not None:
        if isinstance(faults, FaultModel):
            faults = (faults,) * len(progs)
        faults = tuple(fm.wave_model() if fm is not None else None
                       for fm in faults)
        if not any(faults):
            faults = None
    if faults is None:
        bank_geoms = None
    elif mesh is not None:
        raise ValueError(
            "fault injection is not supported under a shard_map mesh "
            "(see scheduler.run_waves); run faulted queues with "
            "mesh=None")
    else:
        bank_geoms = (tuple(bank_geoms) if bank_geoms is not None
                      else (None,) * len(progs))
    donate = all(len(rr) == st.shape[1]
                 for rr, st in zip(result_rows, staged_qs))
    runner = _queued_runner(progs, tuple(tuple(r) for r in result_rows),
                            tuple(n_rows), mesh, donate, body_engine,
                            faults, bank_geoms)
    if timings is not None:
        t0 = time.perf_counter()
        compiled = runner.lower(*staged_qs).compile()
        timings["compile_s"] = (timings.get("compile_s", 0.0)
                                + time.perf_counter() - t0)
        return compiled(*staged_qs)
    return runner(*staged_qs)


def dispatch_uniform_queued(arrays: Sequence[jax.Array],
                            program: Sequence[AAP],
                            result_rows: Tuple[int, ...], *, n_rows: int,
                            geom: DrimGeometry, mesh=None,
                            n_queues: Optional[int] = None, faults=None,
                            ) -> Tuple[jax.Array, int, int]:
    """`scheduler.dispatch_waves` backend for engine="queued": stage the
    payload once, split the bank axis into queue blocks, run every
    queue's (here identical) stream through the MIMD runner, and merge
    the readbacks bank-wise — bit-identical tile order to the SIMD
    engines by construction.  Under a `FaultModel` every queue anchors
    its flip draws at its physical bank offset, so the merged readback
    stays bit-identical to the faulted SIMD engines too (dead-queue
    entries only apply to partitioned graphs and are ignored here)."""
    nq = resolve_n_queues(geom, n_queues)
    qmesh = queue_mesh(geom, nq, mesh)
    staged_qs, tiles, waves = stage_rows_queued(arrays, geom=geom,
                                                n_queues=nq, mesh=qmesh)
    bank_geoms = None
    if faults is not None and faults.wave_model() is not None:
        bank_geoms = tuple((lo, geom.banks)
                           for lo, hi in bank_blocks(geom.banks, nq))
    outs = run_waves_queued(staged_qs, (tuple(program),) * nq,
                            (result_rows,) * nq, (n_rows,) * nq,
                            mesh=qmesh, faults=faults,
                            bank_geoms=bank_geoms)
    return jnp.concatenate(outs, axis=3), tiles, waves


# ---------------------------------------------------------------------------
# Queue-aware cost model
# ---------------------------------------------------------------------------

def _stall_aaps(queue_lengths: Sequence[int], waves: int) -> int:
    """Shared command-bus contention, in whole AAP cycles over `waves`
    repetitions of the per-queue streams (per channel: every chip has
    its own command bus, and all chips carry the same queue blocks).

    The issue interleave has two components: a ONE-TIME pipeline ramp
    (queue q starts `q * cmds_per_aap` slots late and keeps that offset
    — it does not recur per wave) and a steady-state saturation stall
    that every wave pays once the queues demand more issue slots than
    an AAP envelope provides.  Only the latter scales with `waves`;
    below saturation this returns 0, matching the calibration note in
    `core/timing.py` (DRIM-R's 8 banks never stall).
    """
    lengths = tuple(int(n) for n in queue_lengths if n > 0)
    if not lengths:
        return 0
    makespan, _ = simulate_bus_issue(lengths,
                                     slots_per_aap=CMD_SLOTS_PER_AAP)
    ideal = max(lengths) * CMD_SLOTS_PER_AAP
    ramp = (len(lengths) - 1) * CMDS_PER_AAP
    steady = max(0, makespan - ideal - ramp)
    return (waves * steady + ramp) // CMD_SLOTS_PER_AAP


@dataclasses.dataclass(frozen=True)
class QueueSchedule(FusedSchedule):
    """Cost of a workload issued through per-bank command queues.

    Inherits the fused accounting and re-interprets the serialization
    axis: `aaps_per_tile` is the CRITICAL-PATH stream length (for a
    fence-staged MIMD partition, the sum over stages of the slowest
    queue's segment; for a uniform program, just its length), while
    `issued_aaps_per_tile` keeps the total work for energy.  On top of
    the AAP clock it models the two effects independent queues expose:

      * contention — every queue issues through ONE per-channel command
        bus; `contention_stall_aaps` is measured by interleaving the
        per-queue streams through `isa.simulate_bus_issue` and counting
        the cycles the slowest bank waits for issue slots;
      * DMA overlap — per-bank queues let the controller stream wave
        w+1's tiles (and wave w-1's readback) over the DDR bus while
        wave w computes, so `overlapped_latency_s` pays
        max(compute, DMA) plus a one-wave pipeline fill instead of
        their sum (`serialized_latency_s`, what the SIMD engines pay).

    Cross-bank fence transfers (`cross_rows_per_tile`, MIMD partitions
    only) ride the internal bus at the fences and are NOT overlapped —
    a fence is a synchronization point by definition.
    """

    n_queues: int = 1
    banks_per_queue: int = 0
    fence_stages: int = 1
    queue_aaps_per_tile: Tuple[int, ...] = ()
    issued_aaps_per_tile: int = 0
    contention_stall_aaps: int = 0
    dma_rows_per_tile: int = 0        # host DDR: input loads + readbacks
    cross_rows_per_tile: int = 0      # inter-bank fence transfers

    # -- AAP clock ---------------------------------------------------------
    @property
    def aaps_issued(self) -> int:
        """Total AAPs across all queues (a fence-staged partition runs
        every tile through EVERY queue's segment chain)."""
        return self.tiles * self.issued_aaps_per_tile

    @property
    def critical_path_aaps(self) -> int:
        """Serialized AAP cycles on the slowest queue, stalls included."""
        return self.aaps_sequential + self.contention_stall_aaps

    @property
    def latency_s(self) -> float:
        return self.critical_path_aaps * self.t_aap_s

    @property
    def queue_busy_aaps(self) -> Tuple[int, ...]:
        """Per-queue busy cycles over the whole payload."""
        return tuple(self.waves * a for a in self.queue_aaps_per_tile)

    # -- host DMA (shared clock: `core.timing.ddr_rows_s`) -----------------
    def _rows_s(self, rows: int) -> float:
        return ddr_rows_s(rows, self.row_bits)

    @property
    def dma_s(self) -> float:
        """Host DDR time to move every tile's loads + readbacks."""
        return self._rows_s(self.tiles * self.dma_rows_per_tile)

    @property
    def fence_dma_s(self) -> float:
        return self._rows_s(self.tiles * self.cross_rows_per_tile)

    @property
    def serialized_latency_s(self) -> float:
        """Compute then DMA back-to-back — the SIMD engines' model."""
        return self.latency_s + self.dma_s + self.fence_dma_s

    @property
    def overlapped_latency_s(self) -> float:
        """Double-buffered queues: DMA hides behind compute (or compute
        behind DMA), plus a one-wave pipeline fill of the SHORTER side
        (with one wave there is nothing to overlap and this degrades to
        the serialized sum, never past it) and the non-overlappable
        fence traffic."""
        fill = min(self.latency_s, self.dma_s) / max(self.waves, 1)
        return (max(self.latency_s, self.dma_s) + fill
                + self.fence_dma_s)

    @property
    def dma_overlap_speedup(self) -> float:
        if self.overlapped_latency_s == 0.0:
            return 1.0
        return self.serialized_latency_s / self.overlapped_latency_s


def uniform_queue_schedule(op: str, *, n_bits: int, geom: DrimGeometry,
                           tiles: Optional[int] = None,
                           waves: Optional[int] = None,
                           n_queues: Optional[int] = None) -> QueueSchedule:
    """Queue-aware schedule for one Table-2 bulk op (every queue runs
    the same stream; tiles split bank-wise).  With tiles/waves omitted
    this is the closed form — identical numbers to what
    `execute(engine="queued")` measures."""
    nq = resolve_n_queues(geom, n_queues)
    _, _, n_aaps = encoded_program(op)
    if tiles is None:
        tiles = _ceil_div(n_bits, geom.row_bits)
    if waves is None:
        waves = _ceil_div(tiles, geom.n_subarrays)
    arity, n_res = OP_ARITY[op], len(RESULT_ROWS[op])
    queue_aaps = (n_aaps,) * nq
    return QueueSchedule(
        op=op, n_bits=n_bits, row_bits=geom.row_bits, tiles=tiles,
        slots=geom.n_subarrays, waves=waves, aaps_per_tile=n_aaps,
        chips=geom.chips, banks=geom.banks,
        subarrays_per_bank=geom.subarrays_per_bank, t_aap_s=geom.t_aap_s,
        n_nodes=1, rows_used=N_DATA_ROWS, n_inputs=arity, n_outputs=n_res,
        unfused_aaps_per_tile=n_aaps,
        ddr_rows_per_tile=arity + n_res,
        unfused_ddr_rows_per_tile=arity + n_res,
        n_queues=nq, banks_per_queue=geom.banks // nq, fence_stages=1,
        queue_aaps_per_tile=queue_aaps, issued_aaps_per_tile=n_aaps,
        contention_stall_aaps=_stall_aaps(queue_aaps, waves),
        dma_rows_per_tile=arity + n_res, cross_rows_per_tile=0)


plan_queued_schedule = uniform_queue_schedule


def fused_queue_schedule(sched: FusedSchedule, *, geom: DrimGeometry,
                         n_queues: Optional[int] = None) -> QueueSchedule:
    """Lift a fused (SIMD) schedule into the queue cost model: same
    stream on every queue, contention + DMA overlap added."""
    nq = resolve_n_queues(geom, n_queues)
    queue_aaps = (sched.aaps_per_tile,) * nq
    return QueueSchedule(
        **dataclasses.asdict(sched),
        n_queues=nq, banks_per_queue=geom.banks // nq, fence_stages=1,
        queue_aaps_per_tile=queue_aaps,
        issued_aaps_per_tile=sched.aaps_per_tile,
        contention_stall_aaps=_stall_aaps(queue_aaps, sched.waves),
        dma_rows_per_tile=sched.ddr_rows_per_tile, cross_rows_per_tile=0)


def partitioned_queue_schedule(gp: GraphPartition, *, n_bits: int,
                               geom: DrimGeometry,
                               tiles: Optional[int] = None,
                               waves: Optional[int] = None,
                               ) -> QueueSchedule:
    """Queue-aware schedule of a fence-staged MIMD graph partition.

    Every queue (bank block of `banks / n_parts` banks) executes ALL
    tiles of its assigned sub-programs, so `slots`/`waves` describe ONE
    queue's block; the serialization axis is the fence-staged critical
    path (`gp.critical_path_aaps_per_tile`), contention is measured per
    stage from the concurrent segment streams, and cross-bank fence
    rows ride the bus between stages.
    """
    nq = gp.n_parts
    if geom.banks % nq:
        raise ValueError(
            f"{nq}-part partition does not divide {geom.banks} banks")
    geom_q = dataclasses.replace(geom, banks=geom.banks // nq)
    if tiles is None:
        tiles = _ceil_div(n_bits, geom.row_bits)
    if waves is None:
        waves = _ceil_div(tiles, geom_q.n_subarrays)
    stalls = sum(_stall_aaps(stage, waves) for stage in gp.stage_aaps)
    return QueueSchedule(
        op=f"partitioned[{gp.n_nodes}@{nq}]", n_bits=n_bits,
        row_bits=geom.row_bits, tiles=tiles, slots=geom_q.n_subarrays,
        waves=waves, aaps_per_tile=gp.critical_path_aaps_per_tile,
        chips=geom.chips, banks=geom.banks,
        subarrays_per_bank=geom.subarrays_per_bank, t_aap_s=geom.t_aap_s,
        n_nodes=gp.n_nodes, rows_used=gp.rows_used,
        n_inputs=gp.loaded_input_rows, n_outputs=gp.readback_rows_count,
        unfused_aaps_per_tile=gp.unfused_aaps_per_tile,
        ddr_rows_per_tile=gp.loaded_input_rows + gp.readback_rows_count,
        unfused_ddr_rows_per_tile=gp.unfused_ddr_rows_per_tile,
        n_queues=nq, banks_per_queue=geom.banks // nq,
        fence_stages=gp.n_stages,
        queue_aaps_per_tile=gp.queue_aaps_per_tile,
        issued_aaps_per_tile=gp.issued_aaps_per_tile,
        contention_stall_aaps=stalls,
        dma_rows_per_tile=gp.loaded_input_rows + gp.readback_rows_count,
        cross_rows_per_tile=gp.cross_fence_rows)


def plan_partitioned_schedule(graph: BulkGraph, n_bits: int, *,
                              geom: DrimGeometry = DRIM_R,
                              n_queues: Optional[int] = None,
                              row_budget: Optional[int]
                              = DEFAULT_ROW_BUDGET) -> QueueSchedule:
    """Closed-form MIMD schedule — identical numbers to what
    `execute_partitioned` measures, without touching the simulator."""
    if n_bits <= 0:
        raise ValueError("n_bits must be positive")
    nq = resolve_n_queues(geom, n_queues)
    gp = partition_graph(graph, nq, row_budget=row_budget)
    return partitioned_queue_schedule(gp, n_bits=n_bits, geom=geom)


# ---------------------------------------------------------------------------
# MIMD graph execution
# ---------------------------------------------------------------------------

def execute_partitioned(graph: BulkGraph, feeds: Dict[str, jax.Array], *,
                        geom: DrimGeometry = DRIM_R,
                        n_bits: Optional[int] = None,
                        n_queues: Optional[int] = None,
                        row_budget: Optional[int] = DEFAULT_ROW_BUDGET,
                        mesh=None,
                        ) -> Tuple[Dict[str, jax.Array], QueueSchedule]:
    """DEPRECATED shim over the staged pipeline.

    Use ``drim.compile(graph, geom=geom).lower(partition=True,
    n_queues=..., mesh=...).run(feeds, n_bits=...)`` — partitioning is
    a lowering choice (`compiler.PARTITIONERS`), not a separate entry
    point.  This wrapper lowers per call and returns
    ({output: array}, QueueSchedule) exactly as before.
    """
    from repro.pim.compiler import _warn_deprecated, compile as _compile
    _warn_deprecated(
        "queue.execute_partitioned",
        "compile(graph).lower(partition=True, n_queues=..., mesh=...)"
        ".run(feeds, n_bits=...)")
    low = _compile(graph, geom=geom, row_budget=row_budget).lower(
        partition=True, n_queues=n_queues, mesh=mesh)
    results = low.run(feeds, n_bits=n_bits)
    return results, low.schedule


@dataclasses.dataclass(frozen=True)
class ChaosReport:
    """What one partitioned run survived: which queues died, who
    detected it, what got requeued where, and how long the recovery
    path (detect -> replan -> re-dispatch) took in wall-clock.

    `recovery_s` is the steady-state cost of the path — it EXCLUDES the
    one-off XLA compile of the requeue dispatch, which lands on
    `compile_s` instead (same split PR 7 made for `decode_tok_per_s`);
    a report that folded compile into recovery overstated the latency
    of a warm fleet by orders of magnitude.  Both land as telemetry
    gauges (``chaos.recovery_s`` / ``chaos.compile_s``).
    `death_stages` maps each dead queue to its first dead fence stage
    (the timeline exporter renders the DEAD marker and the requeued
    segments from it)."""

    dead_queues: Tuple[int, ...]
    survivors: Tuple[int, ...]
    detected_stages: Tuple[int, ...]   # fence stages that found a gap
    requeued_segments: int
    recovery_s: float                  # detect -> replan -> dispatch
    data_parallel: int                 # survivor fleet's elastic_plan
    compile_s: float = 0.0             # XLA compile of requeue dispatch
    death_stages: Tuple[Tuple[int, int], ...] = ()  # (queue, stage)

    @property
    def degraded(self) -> bool:
        return bool(self.dead_queues)


class QueueProgressTable:
    """Per-queue fence-stage progress — the in-process analogue of
    `runtime.ft.HeartbeatMonitor`'s host table.  Every queue that
    retires a segment beats (part, stage); the fence barrier then asks
    which expected queues went silent.  A dead queue never beats, so
    detection is structural, not timeout-based: the fence IS the
    deadline."""

    def __init__(self, n_queues: int) -> None:
        self.n_queues = n_queues
        self._beats: Dict[int, set] = {}

    def beat(self, part: int, stage: int) -> None:
        self._beats.setdefault(stage, set()).add(part)

    def missing(self, stage: int, expected) -> Tuple[int, ...]:
        return tuple(sorted(set(expected)
                            - self._beats.get(stage, set())))


def _execute_partitioned(graph: BulkGraph, env: Dict[str, jax.Array], *,
                         gp: GraphPartition, geom: DrimGeometry,
                         n_bits: int, mesh=None,
                         body_engine: str = "queued", faults=None,
                         protected_nodes: FrozenSet[int] = frozenset(),
                         ) -> Tuple[Dict[str, jax.Array], QueueSchedule,
                                    Optional[ChaosReport]]:
    """Run ONE BulkGraph split ACROSS the bank queues (true MIMD) — the
    pipeline backend behind `lower(partition=...)`.

    The partition (`gp`) assigns every node to a queue and a fence
    stage; within a stage all queues execute their compiled segment
    sub-programs concurrently through `run_waves_queued` (different
    programs, independent counters), and fences order cross-bank
    dependencies between stages.  Each queue processes EVERY tile of
    the payload for its own nodes — graph-level parallelism, where the
    SIMD engines replicate the whole node list onto every slot.

    The functional executor stages each segment's live values per stage
    (values are values — results are bit-identical to the fused path
    and the numpy oracle); the COST model charges only what the
    hardware moves: graph inputs once per queue that reads them,
    cross-bank rows at fences, output rows once.  Same-queue values
    stay resident in their bank between stages.  `env` holds one
    pre-validated flat uint32 array per graph input (the compiler's
    feed checks ran already); it is mutated in place as stages retire.
    `body_engine` picks each queue's wave body: "queued" (trace-time
    unrolled lax) or "pallas" (the on-device stream interpreter).

    faults: optional `core.FaultModel`.  Bit-flip injection anchors
    each segment at its queue's physical bank block (hardened voter /
    parity spans mapped per segment through `protected_nodes`, indices
    into `graph.nodes`); `faults.dead_queues` kills those queues
    mid-graph — their segments never execute or beat, the fence's
    progress table detects the gap, the survivor fleet is validated
    through `runtime.ft.elastic_plan` and the orphaned segments are
    requeued round-robin on survivor bank blocks.  Because the executor
    is functional over `env`, a requeued segment is EXACT, not
    approximate — graceful degradation costs latency only.

    Returns ({output_name: array}, QueueSchedule, ChaosReport | None).
    """
    from repro.runtime.ft import elastic_plan

    nq = gp.n_parts
    n_words = next(iter(env.values())).shape[0] if env else 0
    geom_q = dataclasses.replace(geom, banks=geom.banks // nq)
    qmesh = queue_mesh(geom, nq, mesh)
    tiles = _ceil_div(n_bits, geom.row_bits)
    waves = _ceil_div(tiles, geom_q.n_subarrays)
    blocks = bank_blocks(geom.banks, nq)

    flips = faults.wave_model() if faults is not None else None
    # queue -> first fence stage it is dead at ("mid-graph": earlier
    # stages completed normally, this one and everything after are lost)
    death_stage: Dict[int, int] = {}
    if faults is not None:
        for q, s in faults.dead_queues:
            if 0 <= q < nq:
                death_stage[q] = min(s, death_stage.get(q, s))
    dead = tuple(sorted(death_stage))
    survivors = tuple(q for q in range(nq) if q not in death_stage)
    if dead and not survivors:
        raise RuntimeError(f"all {nq} queues are dead; no survivor can "
                           "adopt the orphaned segments")

    def seg_faults(s: QueueSegment, epoch: int):
        fm = flips
        if epoch:
            # A recovery dispatch is a LATER command window on the
            # adopting queue's banks, so its analog draws are
            # independent of the segments that bank ran at the fence.
            # Without this epoch salt a requeued segment would replay
            # the survivor's (op_index, slot) flip stream verbatim —
            # correlated failures that can out-vote TMR replicas, a
            # physically meaningless artifact of the counter hash.
            fm = dataclasses.replace(
                fm, seed=(fm.seed ^ (epoch * 0x9E3779B9)) & 0xFFFFFFFF)
        # Subgraphs contain no copies, so subgraph node k IS original
        # node s.node_ids[k]; protected spans follow that mapping.
        prot = [k for i, lo, hi in s.fp.node_spans
                if s.node_ids[i] in protected_nodes
                for k in range(lo, hi)]
        return fm.with_protected(prot) if prot else fm

    def run_segs(segs: List[QueueSegment], parts: Sequence[int],
                 epoch: int = 0, timings=None) -> None:
        staged_qs: List[jax.Array] = []
        for s in segs:
            st, _, _ = stage_rows([env[n] for n in s.fp.loaded_inputs],
                                  geom=geom_q, mesh=qmesh)
            staged_qs.append(st)
        per_faults = (tuple(seg_faults(s, epoch) for s in segs)
                      if flips is not None else None)
        geoms = (tuple((blocks[p][0], geom.banks) for p in parts)
                 if flips is not None else None)
        outs = run_waves_queued(
            staged_qs, [s.fp.program for s in segs],
            [s.fp.readback_rows for s in segs],
            [s.fp.template_rows for s in segs], mesh=qmesh,
            body_engine=body_engine, faults=per_faults, bank_geoms=geoms,
            timings=timings)
        for s, out in zip(segs, outs):
            col = {row: i for i, row in enumerate(s.fp.readback_rows)}
            for name, row in s.fp.device_outputs:
                env[name] = out[:, col[row]].reshape(-1)[:n_words]
            for name, src in s.fp.alias_outputs:
                env[name] = env[src]

    progress = QueueProgressTable(nq)
    detected: List[int] = []
    requeued = 0
    recovery_s = 0.0
    compile_s = 0.0
    plan_data = len(survivors) if survivors else nq

    for stage in range(gp.n_stages):
        segs = [s for s in gp.segments if s.stage == stage]
        healthy = [s for s in segs
                   if death_stage.get(s.part, gp.n_stages) > stage]
        orphans = [s for s in segs
                   if death_stage.get(s.part, gp.n_stages) <= stage]
        if healthy:
            run_segs(healthy, [s.part for s in healthy])
            for s in healthy:
                progress.beat(s.part, stage)
        missing = progress.missing(stage, {s.part for s in segs})
        if missing:
            # Fence barrier found silent queues: replan on the survivor
            # fleet and adopt their segments.  Orphans are padded up to
            # a survivor multiple so the validated elastic split is
            # exact (ft.elastic_plan rejects ragged assignments).
            t0 = time.perf_counter()
            detected.append(stage)
            padded = -(-len(orphans) // len(survivors)) * len(survivors)
            plan = elastic_plan(len(survivors), 1, padded,
                                model_parallel=1)
            plan_data = plan["data"]
            # Split the one-off XLA compile of the requeue dispatch out
            # of the recovery clock (AOT lower().compile() inside
            # run_waves_queued books it under rec_t["compile_s"]) —
            # recovery_s is the steady-state detect -> replan ->
            # dispatch latency of a warm fleet.
            rec_t: Dict[str, float] = {}
            run_segs(orphans, [survivors[i % len(survivors)]
                               for i in range(len(orphans))],
                     epoch=stage + 1, timings=rec_t)
            requeued += len(orphans)
            compile_s += rec_t.get("compile_s", 0.0)
            recovery_s += (time.perf_counter() - t0
                           - rec_t.get("compile_s", 0.0))
            telemetry.event("chaos:requeue", cat="chaos", tid="chaos",
                            stage=stage, orphans=len(orphans),
                            survivors=list(survivors))

    results = {name: env[src] for name, src in gp.output_sources}
    sched = partitioned_queue_schedule(gp, n_bits=n_bits, geom=geom,
                                       tiles=tiles, waves=waves)
    chaos = None
    if dead:
        chaos = ChaosReport(dead_queues=dead, survivors=survivors,
                            detected_stages=tuple(detected),
                            requeued_segments=requeued,
                            recovery_s=recovery_s,
                            data_parallel=plan_data,
                            compile_s=compile_s,
                            death_stages=tuple(sorted(
                                death_stage.items())))
        telemetry.gauge("chaos.recovery_s", recovery_s)
        telemetry.gauge("chaos.compile_s", compile_s)
        telemetry.REGISTRY.counters("chaos")["requeued_segments"] \
            += requeued
    return results, sched, chaos
