"""Bulk-op scheduler: map tensor-sized bit-wise ops onto a DrimDevice.

Takes a tensor-level op (xnor2 / xor2 / not / maj3 / add / copy over
bit-packed uint32 operands of arbitrary size), tiles the operands into
`row_bits`-wide rows, assigns tiles to (chip, bank, subarray) slots, and
executes the batched AAP command stream wave by wave on the functional
`DrimDevice` simulator, every active sub-array running the same Table-2
microprogram in lock-step.

Two wave engines share the staging/tiling/cost model:

  * "resident" (default): operand tiles are staged device-resident in
    one fused dispatch, the AAP stream runs trace-time-UNROLLED
    (`isa.run_program_unrolled` — each wave touches only the rows the
    program names, readback gathers only the result rows), the staged
    buffer is donated to XLA for in-place reuse, and the whole loop can
    be `shard_map`-sharded over a (chips, banks) `pim.mesh.fleet_mesh`.
  * "baseline": the PR 2 loop — every wave rebuilds the full device
    state and runs the encoded stream through the vmapped `lax.scan`
    interpreter.  Kept as the reference the differential suite and
    `benchmarks/fig_fleet.py` measure the resident/sharded paths
    against (bit-exact, ~an order of magnitude slower at DRIM-S).

Cost accounting is *measured from the executed stream*, not a separate
closed form: `aaps_per_tile` is the length of the encoded program each
slot runs, latency is `waves x aaps_per_tile x t_AAP` (waves are the only
serialization; slots within a wave are concurrent, paper §3.4), and
energy charges `E_AAP` per KB of activated row per AAP for the assigned
tiles (idle slots are not activated by the Modified Row Decoder, so
padding slots draw nothing).  `pim/offload.py` prices placements from
these schedules; `benchmarks/fig8_throughput.py --simulate` sweeps
parallelism through `execute()` and checks the analytic model against it.

Semantics per op (results read back from the Table-2 destination rows):
    copy  (a)       -> a
    not   (a)       -> ~a
    xnor2 (a, b)    -> ~(a ^ b)
    xor2  (a, b)    -> a ^ b
    maj3  (a, b, c) -> majority
    add   (a, b, c) -> (a ^ b ^ c, majority)   # full-adder bit-slice
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core import (AAP, DRIM_R, DrimGeometry, encode,
                        make_subarray, microprogram_add, microprogram_copy,
                        microprogram_maj3, microprogram_not,
                        microprogram_xnor2, microprogram_xor2,
                        run_program_unrolled)
from repro.core.device import (_device_run_program, device_load_rows,
                               device_read_rows, device_run_program,
                               make_device)
from repro.core.energy import E_AAP_NJ_PER_KB
from repro.core.faults import mix32, slot_ids_grid
from repro.core.subarray import N_XROWS, WORD_BITS
from repro.runtime import telemetry

# Per-slot row layout: operands at word-lines [0, arity), results at the
# word-lines listed here.  8 data rows are plenty for every Table-2 op.
N_DATA_ROWS = 8

OP_ARITY: Dict[str, int] = {
    "copy": 1, "not": 1, "xnor2": 2, "xor2": 2, "maj3": 3, "add": 3,
}
RESULT_ROWS: Dict[str, Tuple[int, ...]] = {
    "copy": (1,), "not": (1,), "xnor2": (2,), "xor2": (2,),
    "maj3": (3,), "add": (3, 4),
}
# `kernels/ref.py` oracle name per bulk op (None -> identity); single
# source of truth for benchmarks/tests that cross-check results.
REF_OP: Dict[str, str | None] = {
    "copy": None, "not": "not", "xnor2": "xnor", "xor2": "xor",
    "maj3": "maj3", "add": "fa",
}


def random_operands(op: str, n_words: int, seed: int = 0) -> List:
    """Seeded uint32 word arrays with the right arity for `op` — shared
    by benchmarks/tests/offload so cross-check recipes cannot drift."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1 << 32, n_words, dtype=np.uint32)
            for _ in range(OP_ARITY[op])]


def expected_results(op: str, args: Sequence) -> Tuple:
    """Oracle results for `op` via `kernels/ref.py`, normalized to a
    tuple aligned with RESULT_ROWS[op]."""
    from repro.kernels.ref import bitwise_ref
    if REF_OP[op] is None:
        return (args[0],)
    padded = tuple(args) + (None,) * (3 - len(args))
    out = bitwise_ref(REF_OP[op], *padded)
    return out if isinstance(out, tuple) else (out,)

_PROGRAM_CACHE: Dict[str, List[AAP]] = {}


def build_program(op: str) -> List[AAP]:
    """Table-2 microprogram for `op` over the scheduler's row layout
    (operands at rows 0..arity-1, results at RESULT_ROWS[op])."""
    if op not in OP_ARITY:
        raise ValueError(f"unknown bulk op {op!r}")
    if op not in _PROGRAM_CACHE:
        t = make_subarray(n_data=N_DATA_ROWS, row_bits=WORD_BITS)
        _PROGRAM_CACHE[op] = {
            "copy": lambda: microprogram_copy(t, 0, 1),
            "not": lambda: microprogram_not(t, 0, 1),
            "xnor2": lambda: microprogram_xnor2(t, 0, 1, 2),
            "xor2": lambda: microprogram_xor2(t, 0, 1, 2),
            "maj3": lambda: microprogram_maj3(t, 0, 1, 2, 3),
            "add": lambda: microprogram_add(t, 0, 1, 2, 3, 4),
        }[op]()
    return _PROGRAM_CACHE[op]


# Encoded-program memo: `execute`/`plan_schedule` used to re-encode the
# AAP stream (and re-measure its cost) on every call — pure waste, since
# the program depends only on the op: Table-2 addresses are per-slot row
# indices, identical for every geometry (the template is built from
# N_DATA_ROWS and WORD_BITS, never from banks/chips/row_bits).  The key
# is either an op name or a program tuple itself (the queued engine
# streams per-bank programs through the same memo); `queue=` tags the
# hit/miss on that queue's own counters so mixed multi-program streams
# can be audited per bank queue.  The stats counter exists so tests can
# assert the hit path is taken.  It IS the telemetry registry's
# "encode_cache" namespace (same Counter object), so one
# `telemetry.snapshot()` sees it and `telemetry.fresh()` scopes it.
ENCODE_CACHE_STATS: collections.Counter = \
    telemetry.REGISTRY.counters("encode_cache")
# Op-name keys are bounded by the Table-2 op count; program-tuple keys
# (fused graphs, partition segments) are open-ended, so that side is a
# bounded LRU — the nightly random-DAG sweeps stream a fresh program
# per graph and must not grow process memory without bound.
_ENCODED_CACHE: Dict = {}
_ENCODED_TUPLE_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_ENCODED_TUPLE_CACHE_MAX = 512


def encoded_program(op, *, queue: int | None = None,
                    materialize: bool = True,
                    ) -> Tuple[jax.Array | None, Tuple[AAP, ...], int]:
    """Cached (encoded [n, 5] stream, program tuple, n_aaps).

    `op` is an op name ("xnor2", ...) or a sequence of `AAP`s — fused
    graph streams and per-bank queue programs memoize through the same
    stats.  `queue` additionally books the hit/miss under
    ``q{queue}:hits`` / ``q{queue}:misses``.  `materialize=False` skips
    building the encoded device array (the unrolled engines never read
    it — they memoize for the dedup + accounting); a later
    materializing call fills it in place.
    """
    key = op if isinstance(op, str) else tuple(op)
    cache = _ENCODED_CACHE if isinstance(key, str) else _ENCODED_TUPLE_CACHE
    hit = cache.get(key)
    kind = "hits" if hit is not None else "misses"
    ENCODE_CACHE_STATS[kind] += 1
    if queue is not None:
        ENCODE_CACHE_STATS[f"q{queue}:{kind}"] += 1
    if hit is not None:
        if cache is _ENCODED_TUPLE_CACHE:
            _ENCODED_TUPLE_CACHE.move_to_end(key)
        if hit[0] is None and materialize:
            hit = (encode(hit[1]), hit[1], hit[2])
            cache[key] = hit
        return hit
    prog = key if isinstance(key, tuple) else tuple(build_program(key))
    out = (encode(prog) if materialize else None, prog, len(prog))
    cache[key] = out
    if cache is _ENCODED_TUPLE_CACHE:
        while len(_ENCODED_TUPLE_CACHE) > _ENCODED_TUPLE_CACHE_MAX:
            _ENCODED_TUPLE_CACHE.popitem(last=False)
    return out


@contextlib.contextmanager
def fresh_encode_cache():
    """Run a block against an EMPTY encode memo + stats counter, then
    restore the process-wide state untouched.

    Cache-accounting tests used to diff `ENCODE_CACHE_STATS` around
    their calls and tolerate slack for streams other tests had already
    warmed — order-dependent by construction.  Inside this context the
    first issue of any program is deterministically a miss, repeats are
    hits, and exact assertions hold in any test order (the
    `encode_cache` pytest fixture wraps this).  Yields the (cleared)
    stats counter.

    The stats side delegates to the telemetry registry (the counter IS
    the registry's "encode_cache" namespace, restored in place), so
    this context composes with an enclosing `telemetry.fresh()` instead
    of maintaining a second save/restore mechanism."""
    saved_ops = dict(_ENCODED_CACHE)
    saved_tuples = collections.OrderedDict(_ENCODED_TUPLE_CACHE)
    _ENCODED_CACHE.clear()
    _ENCODED_TUPLE_CACHE.clear()
    try:
        with telemetry.REGISTRY.fresh_namespace("encode_cache") as stats:
            yield stats
    finally:
        _ENCODED_CACHE.clear()
        _ENCODED_CACHE.update(saved_ops)
        _ENCODED_TUPLE_CACHE.clear()
        _ENCODED_TUPLE_CACHE.update(saved_tuples)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Tiling + wave plan for one bulk op, with measured cost model.

    `tiles` counts only assigned tiles (the ragged tail is padded to a
    full row but idle slots in the last wave are never activated).
    """

    op: str
    n_bits: int
    row_bits: int
    tiles: int
    slots: int             # concurrent (chip, bank, subarray) lanes
    waves: int
    aaps_per_tile: int     # length of the executed AAP stream per slot
    chips: int
    banks: int
    subarrays_per_bank: int
    t_aap_s: float

    @property
    def aaps_sequential(self) -> int:
        """Serialized AAP cycles on the command bus (waves back-to-back)."""
        return self.waves * self.aaps_per_tile

    @property
    def aaps_issued(self) -> int:
        """Total AAPs executed across all active sub-arrays."""
        return self.tiles * self.aaps_per_tile

    @property
    def latency_s(self) -> float:
        return self.aaps_sequential * self.t_aap_s

    @property
    def energy_j(self) -> float:
        row_kb = self.row_bits / 8.0 / 1024.0
        return self.aaps_issued * row_kb * E_AAP_NJ_PER_KB * 1e-9

    @property
    def active_subarrays(self) -> int:
        """Slots busy in the fullest wave."""
        return min(self.tiles, self.slots)

    @property
    def occupancy(self) -> float:
        """Fraction of wave x slot capacity holding real tiles."""
        return self.tiles / float(self.waves * self.slots)

    @property
    def throughput_bits_s(self) -> float:
        return self.n_bits / self.latency_s

    def parallelism_breakdown(self) -> Dict[str, float]:
        return {
            "chips": self.chips,
            "banks": self.banks,
            "subarrays_per_bank": self.subarrays_per_bank,
            "slots": self.slots,
            "tiles": self.tiles,
            "waves": self.waves,
            "active_subarrays": self.active_subarrays,
            "occupancy": self.occupancy,
        }


def plan_schedule(op: str, n_bits: int, *,
                  geom: DrimGeometry = DRIM_R) -> Schedule:
    """Closed-form schedule for an `n_bits` bulk op — identical numbers to
    what `execute()` measures (same tiling, same program length)."""
    if n_bits <= 0:
        raise ValueError("n_bits must be positive")
    _, _, n_aaps = encoded_program(op)
    tiles = _ceil_div(n_bits, geom.row_bits)
    slots = geom.n_subarrays
    return Schedule(
        op=op, n_bits=n_bits, row_bits=geom.row_bits, tiles=tiles,
        slots=slots, waves=_ceil_div(tiles, slots),
        aaps_per_tile=n_aaps, chips=geom.chips, banks=geom.banks,
        subarrays_per_bank=geom.subarrays_per_bank, t_aap_s=geom.t_aap_s,
    )


# Trace-count telemetry: the wave body below must be traced ONCE per
# (geometry, program) signature no matter how many waves execute — the
# whole wave axis runs under a single `lax.map`, so a 1-wave and a
# 64-wave payload dispatch the same compiled function.  Tests assert the
# counter is wave-count independent.  Backed by the telemetry
# registry's "wave_trace" namespace (same Counter object).
TRACE_COUNTS: collections.Counter = \
    telemetry.REGISTRY.counters("wave_trace")

ENGINES = ("resident", "baseline", "queued", "pallas")


def wave_fn(engine: str, program: Tuple[AAP, ...],
            result_rows: Tuple[int, ...], n_rows: int,
            faults=None, bank_geom=None):
    """The per-wave function every engine shares — ONE code path.

    Returns `one_wave(tiles)` mapping one wave's staged tile block
    [n_rows_in, chips, banks, subarrays, row_words] to the readback
    block [len(result_rows), ...]:

      * "resident" / "queued": `run_program_unrolled` specializes every
        AAP to its word-lines at trace time, so each wave touches ONLY
        the rows the stream names — operand tiles arrive device-
        resident, intermediates live as per-row values, and readback
        gathers just the result rows.  The queued engine maps this over
        per-bank payloads, one program (and program counter) per queue.
      * "baseline": the PR 2 reference — a fresh full device state per
        wave, the encoded stream through the vmapped `lax.scan`
        interpreter, `device_read_rows` readback.
      * "pallas": the stream stays DATA — `encode_kernel_stream` lowers
        it host-side and a `pl.pallas_call` program counter replays it
        over VMEM-resident row planes (`kernels.aap_interpreter`;
        interpret mode off-TPU).

    All tile shapes are static under trace, so the engine split costs
    nothing at runtime; the differential suites hold the engines
    bit-identical.

    faults: optional `core.faults.FaultModel` — every engine draws its
    seed-deterministic DRA/TRA flips from the same (seed, op-index,
    global-slot) counters, so the differential suites keep holding even
    with injection ON.  `bank_geom` = (bank_lo, banks_total) anchors a
    per-bank queue's payload at its physical bank offset so the queued
    engine reproduces the SIMD engines' flips exactly.
    """
    if faults is not None:
        faults = faults.wave_model()
    bank_lo, banks_total = bank_geom if bank_geom is not None else (0, None)
    if engine == "pallas":
        # Lazy import: the scheduler must not pull Pallas in at import
        # time for the lax-only engines.
        from repro.kernels.aap_interpreter import (default_interpret,
                                                   pallas_wave_fn)
        interpret = default_interpret()
        # Which kernel mode each traced pallas body was built in.
        TRACE_COUNTS["pallas_interpret" if interpret
                     else "pallas_compiled"] += 1
        return pallas_wave_fn(program, result_rows, n_rows,
                              interpret=interpret, faults=faults,
                              bank_geom=bank_geom)
    if engine == "baseline":
        # encode directly: the enclosing runner is already memoized per
        # program, and the op-name `encoded_program` cache would only
        # gain a duplicate entry under the tuple key
        encoded = encode(program)

        def one_wave(tiles: jax.Array) -> jax.Array:
            _, c, b, s, w = tiles.shape
            dev0 = make_device(chips=c, banks=b, subarrays=s,
                               n_data=n_rows - N_XROWS, row_bits=w * 32)
            dev = device_load_rows(dev0, 0, jnp.moveaxis(tiles, 0, 3))
            out = _device_run_program(dev, encoded, faults,
                                      bank_lo=bank_lo,
                                      banks_total=banks_total)
            return device_read_rows(out, result_rows)
    else:
        def one_wave(tiles: jax.Array) -> jax.Array:
            zeros = jnp.zeros(tiles.shape[1:], jnp.uint32)
            rows = {wl: tiles[wl] for wl in range(tiles.shape[0])}
            slot_hash = None
            if faults is not None:
                c, b, s, _ = tiles.shape[1:]
                grid = slot_ids_grid(c, b, s, bank_lo=bank_lo,
                                     banks_total=banks_total)
                slot_hash = mix32(grid ^ jnp.uint32(faults.seed))[..., None]
            rows, dcc = run_program_unrolled(program, rows, {},
                                             n_rows=n_rows, zeros=zeros,
                                             faults=faults,
                                             slot_hash=slot_hash)
            return jnp.stack([rows.get(r, zeros) for r in result_rows])
    return one_wave


@functools.lru_cache(maxsize=512)
def _wave_runner(engine: str, program: Tuple[AAP, ...],
                 result_rows: Tuple[int, ...], n_rows: int, mesh,
                 donate: bool, faults=None, bank_geom=None):
    """Compiled wave executor for one (engine, program, readback, mesh,
    faults) signature: a single `lax.map` of the shared `wave_fn` body
    over the wave axis.  With a mesh, the body runs under `shard_map`
    over (chips, banks) with no collectives; `donate=True` hands the
    staged buffer to XLA for output reuse.  A `FaultModel` is frozen/
    hashable, so faulted builds cache alongside the clean ones."""
    if faults is not None:
        # Armed fault-site census, booked once per build (lru_cached
        # like the trace counts): how many DRA/TRA instances of this
        # program can draw flips on this engine.
        for kind, n in faults.count_faultable(program).items():
            if n:
                telemetry.REGISTRY.counters("faults")[
                    f"{engine}:armed_{kind}"] += n

    def body(staged: jax.Array) -> jax.Array:
        TRACE_COUNTS["wave_body" if engine != "baseline"
                     else "wave_body_baseline"] += 1
        return jax.lax.map(wave_fn(engine, program, result_rows, n_rows,
                                   faults, bank_geom),
                           staged)

    fn = body
    if mesh is not None:
        from repro.pim.mesh import STAGED_SPEC
        fn = jax.shard_map(body, mesh=mesh, in_specs=(STAGED_SPEC,),
                           out_specs=STAGED_SPEC, check_vma=False)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def run_waves(staged: jax.Array, program: Sequence[AAP],
              result_rows: Tuple[int, ...], *, n_rows: int,
              mesh=None, engine: str = "resident",
              faults=None, bank_geom=None) -> jax.Array:
    """Execute every wave of a staged payload in ONE traced computation.

    staged: [waves, n_rows_in, chips, banks, subarrays, row_words] —
    wave w holds its [n_rows_in, ...] tile block in word-lines
    [0, n_rows_in) (operands for the plain scheduler, graph inputs for
    the fused path).  `program` is the host-side AAP stream whose
    addresses were resolved against a template with `n_rows` total
    normal rows (addresses >= n_rows are DCC word-lines); the engine-
    specific per-wave body comes from `wave_fn`.  Waves are independent
    (each starts from a fresh sub-array; every live row is written
    before it is read), so the wave axis is one `lax.map`: one trace,
    one dispatch, regardless of wave count.

    The staged buffer is DONATED to XLA whenever the output tile block
    has the same shape (len(result_rows) == n_rows_in), letting the
    readback reuse the operand memory in place of a fresh allocation
    (resident engine only).  `mesh` (from `pim.mesh.fleet_mesh`) runs
    the whole loop under `shard_map` over (chips, banks).

    Returns [waves, len(result_rows), chips, banks, subarrays, row_words].
    """
    if faults is not None:
        faults = faults.wave_model()
    if faults is None:
        bank_geom = None
    elif mesh is not None:
        # V020_FAULTS_UNSUPPORTED_ON_MESH — the named diagnostic the
        # verify pass also raises at lower time (lazy import: verify
        # sits above the scheduler in the module graph).
        from repro.pim.verify import faults_on_mesh_error
        raise faults_on_mesh_error()
    donate = engine != "baseline" and len(result_rows) == staged.shape[1]
    if engine == "baseline":
        mesh = None
    runner = _wave_runner(engine, tuple(program), tuple(result_rows),
                          n_rows, mesh, donate, faults, bank_geom)
    return runner(staged)


def run_waves_baseline(staged: jax.Array, program: Sequence[AAP],
                       result_rows: Tuple[int, ...], *,
                       n_rows: int) -> jax.Array:
    """The PR 2 wave loop (full device state through the vmapped
    `lax.scan` interpreter, fresh state per wave), kept as the
    differential/benchmark reference — now a thin dispatch through the
    same `wave_fn`/`_wave_runner` path the resident and queued engines
    use."""
    return run_waves(staged, program, result_rows, n_rows=n_rows,
                     engine="baseline")


@functools.lru_cache(maxsize=512)
def _stager(n_arrays: int, n_words: int, lead: Tuple[int, ...], mesh):
    """Compiled staging kernel: pad + tile every operand in one fused
    dispatch, leaving the result device-resident (and shard-aligned on
    `mesh`) instead of round-tripping per-array pads through separate
    eager kernels."""
    pad = lead[0] * lead[1] * lead[2] * lead[3] * lead[4] - n_words

    def impl(arrays: Tuple[jax.Array, ...]) -> jax.Array:
        return jnp.stack([jnp.pad(jnp.asarray(a, jnp.uint32), (0, pad))
                          .reshape(lead) for a in arrays], axis=1)

    shardings = None
    if mesh is not None:
        from repro.pim.mesh import STAGED_SPEC
        shardings = NamedSharding(mesh, STAGED_SPEC)
    return jax.jit(impl, out_shardings=shardings)


def stage_rows(arrays: Sequence[jax.Array], *, geom: DrimGeometry,
               mesh=None) -> Tuple[jax.Array, int, int]:
    """Tile flat word arrays onto the fleet: pad to a whole number of
    waves and reshape to [waves, n_arrays, chips, banks, subarrays,
    row_words], device-resident (shard-aligned over `mesh` when given).
    Returns (staged, tiles, waves)."""
    n_words = arrays[0].shape[0]
    row_w = geom.row_bits // WORD_BITS
    tiles = _ceil_div(n_words, row_w)
    waves = _ceil_div(tiles, geom.n_subarrays)
    lead = (waves, geom.chips, geom.banks, geom.subarrays_per_bank, row_w)
    staged = _stager(len(arrays), n_words, lead, mesh)(tuple(arrays))
    return staged, tiles, waves


def dispatch_waves(engine: str, arrays: Sequence[jax.Array],
                   program: Sequence[AAP], result_rows: Tuple[int, ...],
                   *, n_rows: int, geom: DrimGeometry, mesh=None,
                   n_queues: int | None = None, faults=None,
                   ) -> Tuple[jax.Array, int, int]:
    """ONE dispatch point for all the wave engines: engine-specific
    staging, shared wave body (`wave_fn`).

      * "resident": device-resident shard-aligned staging, donated
        buffers, optional `shard_map` over `mesh`.
      * "baseline": eager staging, full-state scan interpreter.
      * "queued":  the payload is split into per-bank command queues
        (`pim.queue`), each with its own program stream and program
        counter, issued as one MIMD dispatch.
      * "pallas":  resident staging, the encoded stream replayed by the
        on-device Pallas interpreter (`kernels.aap_interpreter`).

    Every lowering routes here, so an engine added once is available to
    plain ops and fused DAGs alike.  The engine-specific staging and
    schedule lifting live in `pim.compiler.ENGINE_REGISTRY` — this
    function is the low-level delegate the pipeline (and the legacy
    shims) share.  Returns (outs, tiles, waves) with outs
    [waves, len(result_rows), chips, banks, subarrays, row_words].
    """
    from repro.pim.compiler import get_engine
    eng = get_engine(engine)
    if not eng.device:
        raise ValueError(f"engine {engine!r} is a comparator, not a "
                         "device wave engine")
    return eng.dispatch(arrays, program, result_rows, n_rows=n_rows,
                        geom=geom, mesh=mesh, n_queues=n_queues,
                        faults=faults)


def execute(op: str, *operands: jax.Array, geom: DrimGeometry = DRIM_R,
            n_bits: int | None = None, mesh=None, engine: str = "resident",
            n_queues: int | None = None,
            ) -> Tuple[Tuple[jax.Array, ...], Schedule]:
    """DEPRECATED shim over the staged pipeline.

    Use ``drim.compile(op, geom=geom).lower(engine=..., mesh=...,
    n_queues=...).run(*operands, n_bits=...)`` — the lowering is
    reusable across payloads and its measured schedule lands on
    ``lowered.schedule``.  This wrapper lowers per call and returns
    (results, schedule) exactly as before.
    """
    from repro.pim.compiler import _warn_deprecated, compile as _compile
    _warn_deprecated(
        "scheduler.execute",
        "compile(op).lower(engine=..., mesh=..., n_queues=...).run(...)")
    low = _compile(op, geom=geom).lower(engine=engine, mesh=mesh,
                                        n_queues=n_queues)
    results = low.run(*operands, n_bits=n_bits)
    return results, low.schedule


def execute_oplist(ops: Sequence[Tuple[str, Tuple[jax.Array, ...]]], *,
                   geom: DrimGeometry = DRIM_R, mesh=None,
                   engine: str = "resident", n_queues: int | None = None,
                   ) -> List[Tuple[Tuple[jax.Array, ...], Schedule]]:
    """DEPRECATED shim over the staged pipeline.

    This was the UNFUSED baseline: every op reloads its operands over
    the DDR bus and reads its results back to the host.  Lower each op
    (or better, trace the whole chain with `drim.jit` so it fuses);
    this wrapper keeps the [(results, schedule), ...] contract for the
    differential suites.
    """
    from repro.pim.compiler import _warn_deprecated, compile as _compile
    _warn_deprecated("scheduler.execute_oplist",
                     "compile(op).lower(...).run(...) per op, or "
                     "drim.jit over the whole chain")
    out = []
    for op, args in ops:
        low = _compile(op, geom=geom).lower(engine=engine, mesh=mesh,
                                            n_queues=n_queues)
        res = low.run(*args)
        out.append((res, low.schedule))
    return out
