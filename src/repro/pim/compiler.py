"""The staged DRIM pipeline: ONE `compile -> lower -> run` path over
every engine, mesh, queue count, and partition strategy.

PRs 1-4 grew four parallel entry points (`execute` / `execute_oplist` /
`execute_graph` / `execute_partitioned`), three planners and
string-dispatch on engine names scattered through `scheduler.py`,
`queue.py` and `offload.py` — exactly the programmer-visible fan-out
SIMDRAM's end-to-end framework argues a PIM platform must hide.  This
module collapses all of it:

    low = compile(src, geom=...)            # src: op name | BulkGraph |
          .lower(engine=..., mesh=...,      #      TracedProgram | drim.jit
                 n_queues=..., partition=...)
    out = low.run(...)                      # measured low.schedule
    low.cost(n_bits)                        # closed-form schedule
    low.verdict(n_bits)                     # DRIM-vs-TPU placement Verdict

`lower()` runs a REGISTERED pass pipeline — canonicalize -> fuse ->
optional partition -> encode (`PASS_PIPELINE`) — and engines live in one
`EngineRegistry` ("resident", "baseline", "queued", "pallas", plus the
"tpu" roofline comparator), each owning its wave dispatch and its schedule
lifting.  Swapping a partitioner (`PARTITIONERS`) or an engine is a
lowering argument, never a new function: `scheduler.dispatch_waves` and
the legacy `execute*`/`plan*` surface now delegate here.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import AAP, DRIM_R, DrimGeometry, FaultModel
from repro.core.subarray import N_XROWS, WORD_BITS
from repro.pim.frontend import JittedFunction, TracedProgram, jit
from repro.pim.graph import (DEFAULT_ROW_BUDGET, BulkGraph, FusedProgram,
                             GraphPartition, _make_fused_schedule,
                             compile_graph, graph_ref_results,
                             partition_graph)
from repro.pim.scheduler import (N_DATA_ROWS, OP_ARITY, RESULT_ROWS,
                                 Schedule, _ceil_div, encoded_program,
                                 expected_results)
import repro.pim.mesh as mesh_mod
import repro.pim.verify as verify_mod
from repro.runtime import telemetry

# Always-on counters at the run and lowering boundaries (registry
# namespaces, like `LOWER_CACHE_STATS`): "run.calls" per `Lowered.run`,
# "run.h2d_bytes" for the uint32 words of the host (numpy) operand
# planes a run sends to the device and "run.h2d_transfers" for the
# transfers that carry them (one a run at most: the planes go stacked),
# "run.programs" for the compiled programs a run launches (booked where
# each is launched: `scheduler.RUN_STATS` is this namespace),
# "run.xchip_bytes" for the operand and result bytes a run over a mesh
# of several devices delivers to a device other than the one that holds
# or simulates them, and "lower.us" for the integer microseconds spent
# in `Compiled.lower`, every pass included.
RUN_STATS = telemetry.REGISTRY.counters("run")
LOWER_STATS = telemetry.REGISTRY.counters("lower")


def _warn_deprecated(old: str, new: str, *, stacklevel: int = 3) -> None:
    """One shared deprecation channel for the legacy execute*/plan*
    shims; `-W error::DeprecationWarning` turns any lingering caller
    into a hard failure (the CI example gate does exactly this).

    `stacklevel` counts from `warnings.warn` inside this helper: 3 is
    right for the direct shims (caller -> shim -> here) — every current
    shim calls this helper from its own frame, so the warning names the
    CALLER's file and line.  A shim that ever interposes another wrapper
    must pass `stacklevel=4` (tests assert the reported filename is the
    calling module, not this one)."""
    warnings.warn(
        f"{old} is deprecated; use the staged pipeline instead: {new}",
        DeprecationWarning, stacklevel=stacklevel)


# ---------------------------------------------------------------------------
# Engine registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Engine:
    """One execution backend: how waves dispatch and how raw tiling
    numbers lift into this engine's cost model.

    `dispatch(arrays, program, result_rows, n_rows=, geom=, mesh=,
    n_queues=, faults=, blocks=) -> (outs, tiles, waves)` stages the
    flat word arrays and runs one uniform program over the payload
    (`blocks`: the words are in the mesh's block layout,
    `pim.mesh.words_layout`), step by step; `lift_op` / `lift_graph`
    wrap measured (or closed-form) tiling into the engine's Schedule
    flavour.  `fused` engines run a whole `Lowered.run` as one compiled
    program instead (`scheduler.run_program`).  `device` is False for
    comparator engines (TPU roofline) that never touch the simulated
    fleet.
    """

    name: str
    description: str
    device: bool = True
    dispatch: Optional[Callable] = None
    fused: bool = False
    lift_op: Optional[Callable] = None
    lift_graph: Optional[Callable] = None


class EngineRegistry:
    """Single home for every engine the pipeline can lower onto."""

    def __init__(self) -> None:
        self._engines: Dict[str, Engine] = {}

    def register(self, engine: Engine) -> Engine:
        if engine.name in self._engines:
            raise ValueError(f"engine {engine.name!r} already registered")
        self._engines[engine.name] = engine
        return engine

    def get(self, name: str) -> Engine:
        eng = self._engines.get(name)
        if eng is None:
            raise ValueError(f"unknown engine {name!r} (registered: "
                             f"{', '.join(sorted(self._engines))})")
        return eng

    def names(self) -> Tuple[str, ...]:
        return tuple(self._engines)

    def device_names(self) -> Tuple[str, ...]:
        return tuple(n for n, e in self._engines.items() if e.device)


ENGINE_REGISTRY = EngineRegistry()


def get_engine(name: str) -> Engine:
    return ENGINE_REGISTRY.get(name)


def engines() -> Tuple[str, ...]:
    return ENGINE_REGISTRY.names()


def _simd_dispatch(engine_name: str) -> Callable:
    def dispatch(arrays, program, result_rows, *, n_rows, geom,
                 mesh=None, n_queues=None, faults=None, blocks=False):
        from repro.pim.scheduler import run_waves, stage_rows
        staged, tiles, waves = stage_rows(
            arrays, geom=geom,
            mesh=mesh if engine_name == "resident" else None,
            blocks=blocks)
        outs = run_waves(staged, program, result_rows, n_rows=n_rows,
                         mesh=mesh, engine=engine_name, faults=faults)
        return outs, tiles, waves
    return dispatch


def _queued_dispatch(arrays, program, result_rows, *, n_rows, geom,
                     mesh=None, n_queues=None, faults=None, blocks=False):
    from repro.pim.queue import dispatch_uniform_queued
    return dispatch_uniform_queued(arrays, program, result_rows,
                                   n_rows=n_rows, geom=geom, mesh=mesh,
                                   n_queues=n_queues, faults=faults)


def _pallas_dispatch(arrays, program, result_rows, *, n_rows, geom,
                     mesh=None, n_queues=None, faults=None, blocks=False):
    if mesh is not None:
        raise ValueError("engine 'pallas' runs unsharded — use "
                         "engine='resident' for shard_map fleet meshes")
    return _simd_dispatch("pallas")(arrays, program, result_rows,
                                    n_rows=n_rows, geom=geom,
                                    faults=faults)


def _lift_op_plain(low: "Lowered", n_bits: int,
                   tiles: Optional[int] = None,
                   waves: Optional[int] = None) -> Schedule:
    geom = low.geom
    if tiles is None:
        tiles = _ceil_div(n_bits, geom.row_bits)
    if waves is None:
        waves = _ceil_div(tiles, geom.n_subarrays)
    return Schedule(
        op=low.op, n_bits=n_bits, row_bits=geom.row_bits, tiles=tiles,
        slots=geom.n_subarrays, waves=waves, aaps_per_tile=low.aaps,
        chips=geom.chips, banks=geom.banks,
        subarrays_per_bank=geom.subarrays_per_bank, t_aap_s=geom.t_aap_s)


def _lift_op_queued(low: "Lowered", n_bits: int,
                    tiles: Optional[int] = None,
                    waves: Optional[int] = None):
    from repro.pim.queue import uniform_queue_schedule
    return uniform_queue_schedule(low.op, n_bits=n_bits, geom=low.geom,
                                  tiles=tiles, waves=waves,
                                  n_queues=low.n_queues)


def _lift_graph_plain(low: "Lowered", sched):
    return sched


def _lift_graph_queued(low: "Lowered", sched):
    from repro.pim.queue import fused_queue_schedule
    return fused_queue_schedule(sched, geom=low.geom,
                                n_queues=low.n_queues)


ENGINE_REGISTRY.register(Engine(
    "resident", "trace-time-unrolled program over device-resident "
    "tiles, donated buffers, optional shard_map over a fleet mesh",
    dispatch=_simd_dispatch("resident"), lift_op=_lift_op_plain,
    lift_graph=_lift_graph_plain, fused=True))
ENGINE_REGISTRY.register(Engine(
    "baseline", "PR 2 reference: full device state through the vmapped "
    "lax.scan interpreter, fresh state per wave",
    dispatch=_simd_dispatch("baseline"), lift_op=_lift_op_plain,
    lift_graph=_lift_graph_plain, fused=True))
ENGINE_REGISTRY.register(Engine(
    "queued", "per-bank command queues with independent program "
    "counters, contention + DMA-overlap cost model",
    dispatch=_queued_dispatch, lift_op=_lift_op_queued,
    lift_graph=_lift_graph_queued))
ENGINE_REGISTRY.register(Engine(
    "pallas", "Pallas AAP bit-plane interpreter: the encoded stream as "
    "data, replayed by an on-device program counter over VMEM-resident "
    "row planes (interpret mode off-TPU)",
    dispatch=_pallas_dispatch, lift_op=_lift_op_plain,
    lift_graph=_lift_graph_plain, fused=True))
ENGINE_REGISTRY.register(Engine(
    "tpu", "roofline comparator: numpy oracle semantics, TPU v5e "
    "HBM/VPU cost model — the offload verdict's contender",
    device=False))

# Partition strategies `lower(partition=...)` can pick.  Greedy
# follow-your-producer list scheduling is the only entry today; a
# critical-path-aware clusterer registers here, not as a new API.
PARTITIONERS: Dict[str, Callable[..., GraphPartition]] = {
    "greedy": partition_graph,
}


# ---------------------------------------------------------------------------
# compile(): source normalization
# ---------------------------------------------------------------------------

class Compiled:
    """A compilation unit: normalized source (Table-2 op name, BulkGraph,
    or traced program) bound to a geometry and row budget, ready to
    lower onto any registered engine."""

    def __init__(self, *, kind: str, geom: DrimGeometry,
                 row_budget: Optional[int], op: Optional[str] = None,
                 graph: Optional[BulkGraph] = None,
                 traced: Optional[TracedProgram] = None) -> None:
        self.kind = kind                  # "op" | "graph"
        self.geom = geom
        self.row_budget = row_budget
        self.op = op
        self.graph = graph
        self.traced = traced

    def lower(self, engine: Optional[str] = None, *, mesh=None,
              n_queues: Optional[int] = None, partition=None,
              harden: Optional[str] = None,
              faults: Optional[FaultModel] = None,
              verify: Optional[bool] = None) -> "Lowered":
        """Run the registered pass pipeline and bind an engine.

        engine: any `EngineRegistry` name; defaults to "resident"
        ("queued" when `partition` is set).  partition: None, True
        (default "greedy" strategy), a `PARTITIONERS` key, or an int
        (queue count, greedy strategy) — splits the graph ACROSS queues
        into fence-staged per-bank sub-programs (MIMD).

        harden: None | "tmr" | "ecc" | "tmr+ecc" — rewrite the graph
        for fault tolerance BEFORE fusing (`pim.harden.harden_graph`):
        "tmr" triples every node and votes each result through a
        protected maj3; "ecc" duplicates the compute and folds the
        replica outputs into a parity row read back as detection
        evidence (`Lowered.last_ecc` after each run).  The extra AAPs
        are real program text, so `cost()`/`verdict()` price them.

        faults: default `core.FaultModel` for every `run()` of this
        lowering (a per-call `run(..., faults=...)` overrides it).

        verify: run the static verifier (`pim.verify`) over the lowered
        program — AAP-stream hazards, MIMD fence races, harden
        invariants.  Defaults ON (``DRIM_VERIFY=0`` opts the process
        out; ``DRIM_VERIFY=1`` forces it back on even over an explicit
        ``verify=False``).  The report lands on `Lowered.verify_report`;
        a diagnostic raises `verify.VerifyError` at lower time.
        """
        st = _LoweringState(compiled=self, engine_name=engine, mesh=mesh,
                            n_queues=n_queues, partition=partition,
                            harden=harden, faults=faults,
                            verify=verify_mod.resolve_enabled(verify))
        t0 = time.perf_counter()
        with telemetry.span("lower", cat="compiler", tid="compiler",
                            kind=self.kind, engine=engine or ""):
            for p in PASS_PIPELINE:
                with telemetry.span(f"pass:{p.name}", cat="compiler",
                                    tid="compiler") as sp:
                    p.fn(st)
                    sp.set(nodes=(len(st.graph.nodes)
                                  if st.graph is not None else 1),
                           aaps=st.aaps)
        LOWER_STATS["us"] += int((time.perf_counter() - t0) * 1e6)
        return Lowered(
            kind=st.kind, engine=st.engine, geom=self.geom,
            mesh=st.mesh, n_queues=st.n_queues, partition=st.partition,
            row_budget=self.row_budget, op=self.op, graph=st.graph,
            traced=self.traced, fp=st.fp, gp=st.gp, program=st.program,
            result_rows=st.result_rows, n_rows=st.n_rows, aaps=st.aaps,
            harden=st.harden, default_faults=st.faults,
            protected_nodes=st.protected_nodes,
            verify_report=st.verify_report)


def compile(src, *, geom: Optional[DrimGeometry] = None,
            row_budget: Optional[int] = DEFAULT_ROW_BUDGET) -> Compiled:
    """ONE front door for every program source.

    src may be a Table-2 op name ("xnor2", ...), a hand-built
    `BulkGraph`, a `TracedProgram`/`JittedFunction` from `drim.jit`, or
    a plain Python function (traced on the spot).
    """
    geom = geom if geom is not None else DRIM_R
    if isinstance(src, str):
        return Compiled(kind="op", geom=geom, row_budget=row_budget,
                        op=src)
    if isinstance(src, BulkGraph):
        return Compiled(kind="graph", geom=geom, row_budget=row_budget,
                        graph=src)
    if callable(src) and not isinstance(src, (JittedFunction,
                                              TracedProgram)):
        src = jit(src)
    if isinstance(src, JittedFunction):
        src = src.trace()
    if isinstance(src, TracedProgram):
        return Compiled(kind="graph", geom=geom, row_budget=row_budget,
                        graph=src.graph, traced=src)
    raise TypeError(
        f"cannot compile {type(src).__name__}: expected an op name, "
        "BulkGraph, TracedProgram, drim.jit function, or callable")


# ---------------------------------------------------------------------------
# The pass pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _LoweringState:
    """Mutable scratch the passes fill in order."""

    compiled: Compiled
    engine_name: Optional[str]
    mesh: Any
    n_queues: Optional[int]
    partition: Any
    harden: Optional[str] = None
    faults: Optional[FaultModel] = None
    kind: str = ""
    engine: Optional[Engine] = None
    graph: Optional[BulkGraph] = None     # working graph (post-harden)
    protected_nodes: frozenset = frozenset()
    fp: Optional[FusedProgram] = None
    gp: Optional[GraphPartition] = None
    program: Tuple[AAP, ...] = ()
    result_rows: Tuple[int, ...] = ()
    n_rows: int = 0
    aaps: int = 0
    verify: bool = True
    verify_report: Optional["verify_mod.VerifyReport"] = None


def _pass_canonicalize(st: _LoweringState) -> None:
    """Validate the source, resolve engine/partition/queue defaults."""
    c = st.compiled
    if c.kind == "op" and c.op not in OP_ARITY:
        raise ValueError(f"unknown bulk op {c.op!r}")
    if st.partition is not None and st.partition is not False:
        if c.kind != "graph":
            raise ValueError("partition= needs a graph source; a single "
                             "Table-2 op has nothing to split")
        if isinstance(st.partition, bool):
            st.partition = "greedy"
        elif isinstance(st.partition, int):
            if st.n_queues not in (None, st.partition):
                raise ValueError("partition=<int> conflicts with n_queues")
            st.n_queues = st.partition
            st.partition = "greedy"
        if st.partition not in PARTITIONERS:
            raise ValueError(
                f"unknown partitioner {st.partition!r} (registered: "
                f"{', '.join(sorted(PARTITIONERS))})")
        if st.engine_name is None:
            st.engine_name = "queued"
        elif st.engine_name not in ("queued", "pallas"):
            raise ValueError("a partitioned graph runs on the queued "
                             f"(or pallas) engine, not {st.engine_name!r}")
    else:
        st.partition = None
    st.engine = ENGINE_REGISTRY.get(st.engine_name or "resident")
    if st.engine.name == "pallas" and st.mesh is not None:
        raise ValueError("engine 'pallas' runs unsharded — use "
                         "engine='resident' for shard_map fleet meshes")
    if not st.engine.device:
        if st.mesh is not None or st.n_queues is not None:
            raise ValueError(f"engine {st.engine.name!r} is a comparator"
                             " — mesh/n_queues do not apply")
    elif st.engine.name == "queued" or st.partition is not None:
        from repro.pim.queue import resolve_n_queues
        st.n_queues = resolve_n_queues(c.geom, st.n_queues)
    elif st.n_queues is not None:
        raise ValueError(
            f"n_queues only applies to the queued engine, not "
            f"{st.engine.name!r}")
    if st.harden is not None and c.kind != "graph":
        raise ValueError("harden= needs a graph source; a single "
                         "Table-2 op has no redundancy to compile in")
    if st.faults is not None:
        if not isinstance(st.faults, FaultModel):
            raise TypeError("faults= expects a core.FaultModel")
        if st.faults.active and st.mesh is not None:
            raise verify_mod.faults_on_mesh_error()
    st.graph = c.graph
    st.kind = c.kind


def _pass_harden(st: _LoweringState) -> None:
    """Optionally rewrite the graph for fault tolerance (TMR voting
    and/or parity ECC) before fusion, so the redundancy is ordinary
    program text every engine executes and every cost model prices."""
    if st.harden is None:
        return
    from repro.pim.harden import harden_graph
    st.graph, st.protected_nodes = harden_graph(st.graph, st.harden)


def _pass_fuse(st: _LoweringState) -> None:
    """Op sources pull their memoized Table-2 microprogram; graph
    sources compile to one fused AAP stream (row allocation, copy and
    destructive-read elision) — `graph.compile_graph`."""
    c = st.compiled
    if c.kind == "op":
        _, prog, n_aaps = encoded_program(c.op)
        st.program, st.aaps = prog, n_aaps
        st.result_rows = tuple(RESULT_ROWS[c.op])
        st.n_rows = N_DATA_ROWS + N_XROWS
    else:
        st.fp = compile_graph(st.graph, row_budget=c.row_budget)
        st.program = st.fp.program
        st.result_rows = st.fp.readback_rows
        st.n_rows = st.fp.template_rows
        st.aaps = st.fp.aaps_per_tile


def _pass_partition(st: _LoweringState) -> None:
    """Optionally split the graph across bank queues (MIMD)."""
    if st.partition is None:
        return
    st.gp = PARTITIONERS[st.partition](
        st.graph, st.n_queues,
        row_budget=st.compiled.row_budget)
    st.kind = "partition"
    st.aaps = st.gp.critical_path_aaps_per_tile


def _pass_encode(st: _LoweringState) -> None:
    """Freeze program streams to hashable AAP tuples — the form the
    encoded-program memo, the unrolled wave engines, and the jitted
    runner caches all key on.  (Device encoding itself is memoized at
    first dispatch through `scheduler.encoded_program`, so lowering
    twice never re-encodes.)"""
    st.program = tuple(st.program)
    st.result_rows = tuple(st.result_rows)


def _pass_verify(st: _LoweringState) -> None:
    """Static verification of the lowered program (`pim.verify`):
    AAP-stream hazard analysis over the fused stream, fence
    happens-before over MIMD partitions, harden structural invariants.
    On by default; `lower(verify=False)` skips it (unless DRIM_VERIFY=1
    pins it on).  Raises `verify.VerifyError` on the first diagnostic;
    the clean report lands on `Lowered.verify_report`."""
    if not st.verify:
        return
    st.verify_report = verify_mod.verify_state(st)


@dataclasses.dataclass(frozen=True)
class Pass:
    name: str
    fn: Callable[[_LoweringState], None]


PASS_PIPELINE: Tuple[Pass, ...] = (
    Pass("canonicalize", _pass_canonicalize),
    Pass("harden", _pass_harden),
    Pass("fuse", _pass_fuse),
    Pass("partition", _pass_partition),
    Pass("encode", _pass_encode),
    Pass("verify", _pass_verify),
)


# ---------------------------------------------------------------------------
# Lowered: run / cost / verdict
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EccReport:
    """Host-side parity verdict of one `harden="ecc"` run: the primary
    outputs xor-reduced against the device parity row."""

    mismatch_bits: int                 # popcount of the parity diff
    words: int                         # parity row width compared

    @property
    def corrupted(self) -> bool:
        return self.mismatch_bits > 0


def _plane_words(planes, what: str) -> int:
    """The common word count of a run's operand planes."""
    sizes = {int(np.size(x)) for x in planes}
    if len(sizes) > 1:
        raise ValueError(f"{what} must have equal length")
    return sizes.pop() if sizes else 1


def _mesh_words(planes, mesh, layout: str) -> list:
    """Device planes of a resident run over a mesh of several devices,
    as flat uint32 words in `layout` ("blocks" or "whole",
    `pim.mesh.words_layout`).  Planes held otherwise are placed once
    (span "run.place"); the bytes that reach a device other than the
    one holding them go to "run.xchip_bytes"."""
    target = (mesh_mod.words_sharding(mesh) if layout == "blocks"
              else NamedSharding(mesh, P()))

    def in_layout(x) -> bool:
        return (x.ndim == 1 and x.dtype == jnp.uint32
                and x.sharding.is_equivalent_to(target, 1))

    ops, moved = list(planes), 0
    foreign = [i for i, x in enumerate(ops) if not in_layout(x)]
    if foreign:
        with telemetry.span("run.place", cat="run", tid="run",
                            planes=len(foreign)):
            for i in foreign:
                w = jnp.asarray(ops[i], jnp.uint32).reshape(-1)
                moved += mesh_mod.missing_bytes(w, target)
                ops[i] = jax.device_put(w, target)
    RUN_STATS["xchip_bytes"] += moved
    return ops


def _send(host, n_words: int, mesh, layout: str) -> jax.Array:
    """A run's host planes stacked into one [n_host, n_words] uint32
    block and sent in one transfer, in the run's `layout`: on the
    default device ("one"), in the mesh's block layout ("blocks", span
    "run.place") or whole on every device ("whole", whose copies beyond
    the first count in "run.xchip_bytes")."""
    block = np.empty((len(host), n_words), np.uint32)
    for j, x in enumerate(host):
        block[j] = np.asarray(x).reshape(-1)
    RUN_STATS["h2d_bytes"] += block.nbytes
    if layout == "one":
        return jax.device_put(block)
    if layout == "whole":
        RUN_STATS["xchip_bytes"] += ((mesh_mod.n_devices(mesh) - 1)
                                     * block.nbytes)
        return jax.device_put(block, NamedSharding(mesh, P()))
    with telemetry.span("run.place", cat="run", tid="run",
                        planes=len(host)):
        return jax.device_put(block, NamedSharding(
            mesh, P(None, *mesh_mod.WORDS_SPEC)))


@dataclasses.dataclass(frozen=True)
class _Feeds:
    """A run's operand planes after its one feed step: `srcs[k]` names
    where plane k is, as `scheduler.run_program` reads it: ("d", i)
    device plane `dev[i]`, ("h", j) row j of the stacked host block
    `host[0]`, ("z", 0) a constant zero plane, made on the device."""

    n_words: int
    layout: str
    srcs: Tuple[Tuple[str, int], ...]
    dev: Tuple[jax.Array, ...]
    host: Tuple[jax.Array, ...]

    def plane(self, src) -> jax.Array:
        """One plane as flat device words, for the step-by-step
        engines."""
        kind, i = src
        if kind == "d":
            x = self.dev[i]
            return x if x.ndim == 1 else x.reshape(-1)
        if kind == "h":
            return self.host[0][i]
        return jnp.zeros(self.n_words, jnp.uint32)


class Lowered:
    """A program bound to (engine, geometry, mesh, queues, partition).

    `run(...)` executes on the simulated fleet (or the comparator's
    oracle) and records the measured schedule on `self.schedule`;
    `cost(n_bits)` prices a payload in closed form without touching the
    simulator; `verdict(n_bits)` returns the unified DRIM-vs-TPU
    placement `Verdict`.
    """

    def __init__(self, *, kind, engine, geom, mesh, n_queues, partition,
                 row_budget, op, graph, traced, fp, gp, program,
                 result_rows, n_rows, aaps, harden=None,
                 default_faults=None,
                 protected_nodes=frozenset(),
                 verify_report=None) -> None:
        self.kind = kind
        self.engine = engine
        self.geom = geom
        self.mesh = mesh
        # The mesh a run's operand and result words are laid over
        # (`pim.mesh.words_layout`): the resident engine's; the queued
        # engine stages its words row-major from wherever they are.
        self.words_mesh = mesh if engine.name == "resident" else None
        self.n_queues = n_queues
        self.partition = partition
        self.row_budget = row_budget
        self.op = op
        self.graph = graph
        self.traced = traced
        self.fp = fp
        self.gp = gp
        self.program = program
        self.result_rows = result_rows
        self.n_rows = n_rows
        self.aaps = aaps
        self.harden = harden
        self.default_faults = default_faults
        self.protected_nodes = frozenset(protected_nodes)
        self.verify_report = verify_report   # pim.verify, when enabled
        self.schedule = None          # measured by the last run()
        self.last_ecc = None          # EccReport of the last ecc run()
        self.chaos_report = None      # ChaosReport of the last run()

    # -- execution ---------------------------------------------------------
    def _resolve_faults(self, faults):
        """Per-call faults override the lowering default; hardened
        lowerings add their protected op spans (voter/parity AAPs run
        on guard-banded sense amplifiers and never flip); comparator
        engines ignore faults entirely (the clean oracle IS the
        graceful-degradation fallback)."""
        if faults is None:
            faults = self.default_faults
        if faults is None or not self.engine.device:
            return None
        if not isinstance(faults, FaultModel):
            raise TypeError("faults= expects a core.FaultModel")
        if not faults.active:
            return None
        if self.mesh is not None:
            raise verify_mod.faults_on_mesh_error()
        if self.protected_nodes and self.fp is not None:
            spans = {i: (lo, hi) for i, lo, hi in self.fp.node_spans}
            ops = [k for i in self.protected_nodes
                   for k in range(*spans[i])]
            faults = faults.with_protected(ops)
        return faults

    def _check_ecc(self, results):
        """Host side of the parity scheme: xor-reduce the primary
        outputs and diff against the device parity row."""
        parity = np.asarray(results.pop("__ecc__"), dtype=np.uint32)
        expect = np.zeros_like(parity)
        for arr in results.values():
            expect = expect ^ np.asarray(arr, dtype=np.uint32)
        diff = (parity ^ expect).view(np.uint8)
        bits = int(np.unpackbits(diff).sum())
        self.last_ecc = EccReport(mismatch_bits=bits,
                                  words=int(parity.size))
        return results

    def run(self, *args, n_bits: Optional[int] = None,
            faults: Optional[FaultModel] = None):
        """Execute.  Op sources take positional word arrays (one per
        operand) and return a result tuple; graph sources take either a
        {input_name: array} dict or — for traced programs — positional
        arrays in the traced argument order, and return outputs shaped
        like the traced function's own return value (a plain dict for
        hand-built graphs).

        faults: a `core.FaultModel` for THIS run only (overrides the
        lowering-time default).  With `harden="ecc"` lowerings the
        detection evidence of each run lands on `self.last_ecc`.

        Device planes (`jax.Array`) pass through as they are; host
        planes are stacked and sent in one transfer; a traced program's
        constant planes are made on the device.  On the resident,
        baseline and pallas engines the run is then one compiled
        program that stages, runs the waves and cuts the result planes
        out (`scheduler.run_program`).

        Over a fleet mesh of several devices (resident engine), operand
        and result words take the mesh's block layout
        (`pim.mesh.words_sharding`): each device holds, simulates and
        returns one contiguous block.  Operands already in it move
        nothing between devices; others are placed there first.  A word
        count that does not divide by the device count keeps the
        replicated path.  "run.xchip_bytes" counts what crosses devices.
        """
        RUN_STATS["calls"] += 1
        with telemetry.span("run", cat="run", tid="run", kind=self.kind,
                            engine=self.engine.name, op=self.op or "",
                            aaps=self.aaps,
                            devices=mesh_mod.n_devices(self.mesh)):
            out = self._run(args, n_bits, faults)
        if self.kind == "partition" and telemetry.enabled():
            # MIMD runs also drop their simulated-clock queue timeline
            # (per-queue tracks, fences, contention stalls, chaos).
            telemetry.record_queue_timeline(self)
        return out

    def _run(self, args, n_bits, faults):
        faults = self._resolve_faults(faults)
        if self.kind == "op":
            return self._run_op(args, n_bits, faults)
        names = self.graph.input_names
        with telemetry.span("run.feeds", cat="run", tid="run"):
            planes = self._graph_planes(args)
            if not self.engine.device:
                n_words = _plane_words(
                    [x for x in planes if x is not None], "graph inputs")
                self.schedule = self.cost(
                    self._resolve_n_bits(n_bits, n_words))
                return self._finish(graph_ref_results(self.graph, {
                    n: (np.zeros(n_words, np.uint32) if x is None else
                        np.asarray(x, np.uint32).reshape(-1))
                    for n, x in zip(names, planes)}))
            feeds = self._feed(planes, "graph inputs")
            n_bits = self._resolve_n_bits(n_bits, feeds.n_words)
        if self.kind == "partition":
            return self._finish(self._run_partitioned(
                {n: feeds.plane(src) for n, src in zip(names, feeds.srcs)},
                n_bits, faults))
        return self._finish(self._run_graph(feeds, n_bits, faults))

    def _finish(self, outs):
        if self.harden is not None and "ecc" in self.harden:
            outs = self._check_ecc(dict(outs))
        if self.traced is not None:
            return self.traced.restructure(outs)
        return outs

    def _graph_planes(self, args) -> list:
        """A graph run's arguments as its input planes in
        `graph.input_names` order, None for a traced program's constant
        plane that the caller did not give."""
        if len(args) == 1 and isinstance(args[0], dict):
            feeds = dict(args[0])
        elif self.traced is not None:
            feeds = self.traced.arg_feeds(args)
        else:
            raise ValueError("graph lowering expects a feeds dict (or "
                             "positional planes for traced programs)")
        names = self.graph.input_names
        consts = self.traced.const_names if self.traced is not None else ()
        missing = set(names) - set(feeds) - set(consts)
        extra = set(feeds) - set(names)
        if missing or extra:
            raise ValueError(f"feed mismatch: missing {sorted(missing)}, "
                             f"unexpected {sorted(extra)}")
        return [feeds.get(n) for n in names]

    def _feed(self, planes, what: str) -> _Feeds:
        """The one feed step of a run over `planes` (None: a constant
        zero plane, made on the device).  Device planes pass through
        (placed once where a mesh run needs another layout); host planes
        are stacked and sent in one transfer (`_send`)."""
        n_words = _plane_words([x for x in planes if x is not None], what)
        layout = mesh_mod.words_layout(self.words_mesh, n_words)
        srcs, dev, host = [], [], []
        for x in planes:
            if x is None:
                srcs.append(("z", 0))
            elif isinstance(x, jax.Array):
                srcs.append(("d", len(dev)))
                dev.append(x)
            else:
                srcs.append(("h", len(host)))
                host.append(x)
        if layout != "one":
            dev = _mesh_words(dev, self.words_mesh, layout)
        block = ()
        if host:
            block = (_send(host, n_words, self.words_mesh, layout),)
        RUN_STATS["h2d_transfers"] += len(block)
        return _Feeds(n_words, layout, tuple(srcs), tuple(dev), block)

    def _execute(self, feeds: _Feeds, staged, outputs, program,
                 result_rows, n_rows, faults):
        """Run `program` over the planes `staged` (sources of `feeds`)
        and return the planes `outputs` (("col", c): readback column c;
        a source: that input plane as it is) with the run's tiles and
        waves.  A fused engine launches one compiled program
        (`scheduler.run_program`); the queued engine stages, dispatches
        and reads back step by step."""
        from repro.pim.scheduler import read_planes, run_program, tiling
        n_words = feeds.n_words
        tiles, waves, lead = tiling(n_words, self.geom)
        cols = [i for kind, i in outputs if kind == "col"]
        if feeds.layout == "whole":
            d = mesh_mod.n_devices(self.words_mesh)
            RUN_STATS["xchip_bytes"] += (d - 1) * n_words * 4 * len(cols)
        attrs = dict(cat="run", tid="run", engine=self.engine.name,
                     waves=waves, tiles=tiles, aaps=len(program))
        if self.engine.fused:
            fn = run_program(
                self.engine.name, program, result_rows, n_rows, staged,
                outputs, n_words, lead,
                self.words_mesh if feeds.layout != "one" else None,
                feeds.layout == "blocks",
                faults.wave_model() if faults is not None else None)
            with telemetry.span("run.dispatch", **attrs):
                out = fn(feeds.dev, feeds.host)
            RUN_STATS["programs"] += 1
            return out, tiles, waves
        read = {}
        if cols:
            with telemetry.span("run.dispatch", **attrs):
                outs, tiles, waves = self.engine.dispatch(
                    [feeds.plane(src) for src in staged], program,
                    result_rows, n_rows=n_rows, geom=self.geom,
                    mesh=self.mesh, n_queues=self.n_queues, faults=faults)
            with telemetry.span("run.readback", cat="run", tid="run",
                                outputs=len(cols)):
                read = dict(zip(cols, read_planes(outs, cols, n_words)))
        return tuple(read[i] if kind == "col" else feeds.plane((kind, i))
                     for kind, i in outputs), tiles, waves

    def _run_op(self, operands, n_bits, faults=None):
        arity = OP_ARITY[self.op]
        if len(operands) != arity:
            raise ValueError(f"{self.op} takes {arity} operands, got "
                             f"{len(operands)}")
        if not self.engine.device:
            args = [np.asarray(o, dtype=np.uint32).reshape(-1)
                    for o in operands]
            if any(a.shape != args[0].shape for a in args):
                raise ValueError("operands must have equal length")
            if n_bits is None:
                n_bits = args[0].size * WORD_BITS
            if not 0 < n_bits <= args[0].size * WORD_BITS:
                raise ValueError(
                    "n_bits out of range for the given operands")
            self.schedule = self.cost(n_bits)
            return expected_results(self.op, args)
        with telemetry.span("run.feeds", cat="run", tid="run"):
            feeds = self._feed(list(operands), "operands")
            n_words = feeds.n_words
            if n_bits is None:
                n_bits = n_words * WORD_BITS
            if not 0 < n_bits <= n_words * WORD_BITS:
                raise ValueError(
                    "n_bits out of range for the given operands")
        results, tiles, waves = self._execute(
            feeds, feeds.srcs,
            tuple(("col", i) for i in range(len(self.result_rows))),
            self.program, self.result_rows, self.n_rows, faults)
        with telemetry.span("run.schedule", cat="run", tid="run"):
            self.schedule = self.engine.lift_op(self, n_bits, tiles, waves)
        return results

    def _resolve_n_bits(self, n_bits, n_words):
        if n_bits is None:
            return n_words * WORD_BITS
        # n_bits marks a ragged tail INSIDE the last word only; oversized
        # feeds would make the executed wave count silently disagree
        # with the closed-form cost, so reject them.
        if not (n_words - 1) * WORD_BITS < n_bits <= n_words * WORD_BITS:
            raise ValueError(
                f"n_bits={n_bits} does not match feeds of {n_words} "
                f"words; expected a value in "
                f"({(n_words - 1) * WORD_BITS}, {n_words * WORD_BITS}]")
        return n_bits

    def _run_graph(self, feeds: _Feeds, n_bits, faults=None):
        fp = self.fp
        src = dict(zip(self.graph.input_names, feeds.srcs))
        col = {row: i for i, row in enumerate(fp.readback_rows)}
        names = ([name for name, _ in fp.alias_outputs]
                 + [name for name, _ in fp.device_outputs])
        outputs = (tuple(src[a] for _, a in fp.alias_outputs)
                   + tuple(("col", col[row]) for _, row in fp.device_outputs))
        planes, tiles, waves = self._execute(
            feeds, tuple(src[n] for n in fp.loaded_inputs), outputs,
            fp.program, fp.readback_rows, fp.template_rows, faults)
        with telemetry.span("run.schedule", cat="run", tid="run"):
            sched = _make_fused_schedule(fp, n_bits, tiles, waves,
                                         self.geom)
            self.schedule = self.engine.lift_graph(self, sched)
        return dict(zip(names, planes))

    def _run_partitioned(self, arrays, n_bits, faults=None):
        from repro.pim.queue import _execute_partitioned
        results, sched, chaos = _execute_partitioned(
            self.graph, arrays, gp=self.gp, geom=self.geom,
            n_bits=n_bits, mesh=self.mesh,
            body_engine=("pallas" if self.engine.name == "pallas"
                         else "queued"),
            faults=faults, protected_nodes=self.protected_nodes)
        self.schedule = sched
        self.chaos_report = chaos
        return results

    # -- pricing -----------------------------------------------------------
    def cost(self, n_bits: int):
        """Closed-form schedule for an `n_bits` payload — identical
        numbers to what `run()` measures on the same payload."""
        if n_bits <= 0:
            raise ValueError("n_bits must be positive")
        if not self.engine.device:
            from repro.pim.offload import tpu_cost
            return tpu_cost(self, n_bits)
        if self.kind == "op":
            return self.engine.lift_op(self, n_bits)
        if self.kind == "partition":
            from repro.pim.queue import partitioned_queue_schedule
            return partitioned_queue_schedule(self.gp, n_bits=n_bits,
                                              geom=self.geom)
        geom = self.geom
        tiles = _ceil_div(n_bits, geom.row_bits)
        waves = _ceil_div(tiles, geom.n_subarrays)
        sched = _make_fused_schedule(self.fp, n_bits, tiles, waves, geom)
        return self.engine.lift_graph(self, sched)

    def verdict(self, n_bits: int, *, simulate: bool = False):
        """Unified DRIM-vs-TPU placement verdict (`offload.Verdict`):
        the same row fields for the fused, queued, unfused and TPU
        contenders, DDR traffic accounted once for all of them."""
        from repro.pim.offload import build_verdict
        return build_verdict(self, n_bits, simulate=simulate)

    # -- misc --------------------------------------------------------------
    def __repr__(self) -> str:
        src = self.op if self.kind == "op" else (
            self.traced.name if self.traced is not None
            else f"graph[{len(self.graph.nodes)}]")
        extra = f", partition={self.partition!r}" if self.partition else ""
        return (f"Lowered({src}, engine={self.engine.name!r}, "
                f"geom={self.geom.chips}x{self.geom.banks}x"
                f"{self.geom.subarrays_per_bank}{extra})")


def lower(src, *, geom: Optional[DrimGeometry] = None,
          engine: Optional[str] = None, mesh=None,
          n_queues: Optional[int] = None, partition=None,
          harden: Optional[str] = None,
          faults: Optional[FaultModel] = None,
          row_budget: Optional[int] = DEFAULT_ROW_BUDGET,
          verify: Optional[bool] = None) -> Lowered:
    """Convenience: `compile(src).lower(...)` in one call."""
    return compile(src, geom=geom, row_budget=row_budget).lower(
        engine=engine, mesh=mesh, n_queues=n_queues, partition=partition,
        harden=harden, faults=faults, verify=verify)


# ---------------------------------------------------------------------------
# Process-wide lowering memo: the serving hot path
# ---------------------------------------------------------------------------

_LOWER_CACHE: Dict[Tuple, Lowered] = {}

# Observable from tests/telemetry: a decode loop must pay trace +
# compile + lower once per kernel shape, never once per token.  Backed
# by the registry's "lower_cache" namespace (same Counter object), so
# `telemetry.snapshot()` and `telemetry.fresh()` see it.
LOWER_CACHE_STATS = telemetry.REGISTRY.counters("lower_cache")


def clear_lower_cache() -> None:
    _LOWER_CACHE.clear()
    # Counter.update(hits=0) ADDS zero — clear() is the reset.
    LOWER_CACHE_STATS.clear()


def lower_cached(src, *, key: Optional[Tuple] = None,
                 geom: Optional[DrimGeometry] = None,
                 engine: Optional[str] = None, mesh=None,
                 n_queues: Optional[int] = None, partition=None,
                 harden: Optional[str] = None,
                 faults: Optional[FaultModel] = None,
                 row_budget: Optional[int] = DEFAULT_ROW_BUDGET,
                 verify: Optional[bool] = None) -> Lowered:
    """`compile(src).lower(...)` memoized for the LIFE OF THE PROCESS.

    This is the serving hot path: `models.layers` routes every BitLinear
    decode matmul here, so one `Lowered` (and the jitted wave runners
    underneath it) is shared across every request that hits the same
    (program, geometry, engine, mesh, queues, partition) signature —
    and with `offload.serving_verdict`, so pricing and execution read
    the SAME lowering.

    `src` itself keys the memo when hashable (op names, frozen traced
    programs); pass an explicit `key` identifying the program for
    unhashable sources or when the source object is rebuilt per call
    (object-identity hashes would defeat the cache).
    """
    ident: Any = key if key is not None else src
    try:
        hash(ident)
    except TypeError:
        raise TypeError(
            "lower_cached needs a hashable src or an explicit key= "
            "identifying the program") from None
    # The resolved verify flag keys the memo (not the raw argument):
    # DRIM_VERIFY may differ between calls, and a verified lowering must
    # not be handed to a caller who pinned verification on.
    verify_on = verify_mod.resolve_enabled(verify)
    full_key = (ident, geom, engine, mesh, n_queues, partition,
                harden, faults, row_budget, verify_on)
    low = _LOWER_CACHE.get(full_key)
    if low is None:
        LOWER_CACHE_STATS["misses"] += 1
        low = compile(src, geom=geom, row_budget=row_budget).lower(
            engine=engine, mesh=mesh, n_queues=n_queues,
            partition=partition, harden=harden, faults=faults,
            verify=verify_on)
        _LOWER_CACHE[full_key] = low
    else:
        LOWER_CACHE_STATS["hits"] += 1
    return low
