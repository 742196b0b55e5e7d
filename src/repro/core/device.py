"""Bank/chip-level DRIM device model: a [chips, banks, subarrays] stack.

The paper's headline throughput (Fig. 8) comes from *inter-subarray
parallelism*: every computational sub-array of every bank (and every chip
of a rank) executes the same AAP sequence in lock-step over different
rows — a SIMD machine whose lanes are 256-bit DRAM rows.  `DrimDevice`
models exactly that: the full `[chips, banks, subarrays]` stack of
`SubArray` states held as ONE batched pytree, with program execution a
single `jax.vmap` of the `lax.scan` AAP interpreter (`isa.run_program`)
over the flattened slot axis — same encoded program, different data.

Addressing follows `subarray.py`: word-lines `[0, n_rows)` are data rows
plus x1..x8, `[n_rows, n_rows + 4)` are the four DCC word-lines.  All
helpers are pure and jit-friendly; `pim/scheduler.py` builds on this
layer to tile tensor-sized operands onto slots and account cycles.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from .faults import slot_ids_grid
from .isa import run_program
from .subarray import N_XROWS, SubArray, make_subarray, row_words
from .timing import DrimGeometry

# Mesh axis names the fleet is laid out over (`pim/mesh.py` builds
# meshes with these axes; `device_run_program_sharded` and the
# scheduler's sharded wave runner shard the leading device dims on them).
MESH_AXES = ("chips", "banks")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DrimDevice:
    """Batched state of every computational sub-array in the device.

    data: [chips, banks, subarrays, n_rows, words] uint32
    dcc:  [chips, banks, subarrays, 2, words]      uint32
    """

    data: jax.Array
    dcc: jax.Array

    # -- geometry ----------------------------------------------------------
    @property
    def chips(self) -> int:
        return self.data.shape[0]

    @property
    def banks(self) -> int:
        return self.data.shape[1]

    @property
    def subarrays(self) -> int:
        return self.data.shape[2]

    @property
    def n_slots(self) -> int:
        """Total (chip, bank, subarray) slots = SIMD width in rows."""
        return self.chips * self.banks * self.subarrays

    @property
    def n_rows(self) -> int:
        return self.data.shape[3]

    @property
    def words(self) -> int:
        return self.data.shape[4]

    @property
    def row_bits(self) -> int:
        return self.words * 32

    # -- word-line aliases (same layout in every slot) ---------------------
    def wl_x(self, k: int) -> int:
        return self.n_rows - N_XROWS + (k - 1)

    def wl_dcc(self, k: int) -> int:
        return self.n_rows + (k - 1)

    def slot(self, chip: int, bank: int, sub: int) -> SubArray:
        """View one slot as a plain SubArray (for single-lane debugging)."""
        return SubArray(data=self.data[chip, bank, sub],
                        dcc=self.dcc[chip, bank, sub])


def make_device(geom: Optional[DrimGeometry] = None, *,
                chips: int = 2, banks: int = 4, subarrays: int = 8,
                n_data: int = 16, row_bits: int = 256) -> DrimDevice:
    """Fresh all-zero device.  `geom` overrides chips/banks/subarrays and
    row_bits; `n_data` stays a knob so tests/schedulers can keep the
    per-slot row count (and simulation memory) small."""
    if geom is not None:
        chips, banks, subarrays = geom.chips, geom.banks, geom.subarrays_per_bank
        row_bits = geom.row_bits
    w = row_words(row_bits)
    lead = (chips, banks, subarrays)
    return DrimDevice(
        data=jnp.zeros(lead + (n_data + N_XROWS, w), jnp.uint32),
        dcc=jnp.zeros(lead + (2, w), jnp.uint32),
    )


def device_template(dev: DrimDevice) -> SubArray:
    """Zero SubArray with this device's per-slot shape — used to resolve
    x/dcc word-line aliases when building microprograms."""
    return make_subarray(n_data=dev.n_rows - N_XROWS, row_bits=dev.row_bits)


def device_load_rows(dev: DrimDevice, start: int, rows: jax.Array) -> DrimDevice:
    """Load per-slot row blocks: rows [chips, banks, subarrays, k, words]
    are written to word-lines [start, start+k) of every slot (the DDR
    write path, not an AAP)."""
    rows = jnp.asarray(rows, jnp.uint32)
    data = jax.lax.dynamic_update_slice(dev.data, rows, (0, 0, 0, start, 0))
    return dataclasses.replace(dev, data=data)


def device_broadcast_rows(dev: DrimDevice, start: int,
                          rows: jax.Array) -> DrimDevice:
    """Write the same [k, words] block into every slot at `start`."""
    rows = jnp.asarray(rows, jnp.uint32)
    tiled = jnp.broadcast_to(rows, dev.data.shape[:3] + rows.shape)
    return device_load_rows(dev, start, tiled)


def device_read_row(dev: DrimDevice, wl: int) -> jax.Array:
    """Read word-line `wl` of every slot -> [chips, banks, subarrays, words]."""
    return dev.data[:, :, :, wl, :]


def device_read_rows(dev: DrimDevice, wls) -> jax.Array:
    """Gather a window of word-lines from every slot.

    wls: sequence of word-line numbers (need not be contiguous — the fused
    graph executor reads back output rows wherever the row allocator left
    them).  Returns [len(wls), chips, banks, subarrays, words] so the row
    axis leads, matching the order results are handed back to the host.
    """
    idx = jnp.asarray(wls, jnp.int32)
    return jnp.moveaxis(dev.data[:, :, :, idx, :], 3, 0)


def device_read_row_window(dev: DrimDevice, start: int, k: int) -> jax.Array:
    """Read the contiguous word-lines [start, start+k) of every slot ->
    [k, chips, banks, subarrays, words] (the DDR read path, mirror of
    `device_load_rows`)."""
    return device_read_rows(dev, range(start, start + k))


def _device_run_program(dev: DrimDevice, encoded: jax.Array,
                        faults=None, bank_lo: int = 0,
                        banks_total: Optional[int] = None) -> DrimDevice:
    lead = dev.data.shape[:3]
    flat = SubArray(
        data=dev.data.reshape((-1,) + dev.data.shape[3:]),
        dcc=dev.dcc.reshape((-1,) + dev.dcc.shape[3:]),
    )
    if faults is not None:
        faults = faults.wave_model()
    if faults is None:
        out = jax.vmap(run_program, in_axes=(0, None))(flat, encoded)
    else:
        # Global slot ids so a bank slice (queue block) draws the same
        # flips as the full-fleet dispatch of the identical program.
        sids = slot_ids_grid(*lead, bank_lo=bank_lo,
                             banks_total=banks_total).reshape(-1)
        out = jax.vmap(
            lambda sa, sid: run_program(sa, encoded, faults=faults,
                                        slot_id=sid),
            in_axes=(0, 0))(flat, sids)
    return DrimDevice(
        data=out.data.reshape(lead + out.data.shape[1:]),
        dcc=out.dcc.reshape(lead + out.dcc.shape[1:]),
    )


_device_run_program_donating = jax.jit(_device_run_program,
                                       donate_argnums=(0,))


def device_run_program(dev: DrimDevice, encoded: jax.Array, *,
                       donate: bool = False, faults=None) -> DrimDevice:
    """Execute one encoded [n, 5] AAP stream on EVERY slot at once.

    One `jax.vmap` over the flattened slot axis of the `lax.scan`
    interpreter — the SIMD lock-step of paper §3.4.  jit-friendly; the
    scheduler jits this together with its operand loads.

    donate=True hands `dev`'s buffers to XLA for in-place reuse (the
    input becomes invalid — the output state occupies the same memory).
    The default keeps the input alive, since tests and debugging
    sessions routinely compare pre/post states.

    faults: optional `core.faults.FaultModel` — seed-deterministic bit
    flips on DRA/TRA results (fault injection skips buffer donation; the
    fault-free path is byte-identical to a build without this kwarg).
    """
    if faults is not None and faults.wave_model() is not None:
        return _device_run_program(dev, encoded, faults)
    if donate:
        return _device_run_program_donating(dev, encoded)
    return _device_run_program(dev, encoded)


def device_run_program_banked(dev: DrimDevice, encoded_by_block,
                              bank_blocks, *, faults=None) -> DrimDevice:
    """MIMD over the bank axis: a DIFFERENT encoded stream per bank block.

    bank_blocks: sequence of (lo, hi) pairs partitioning [0, banks) into
    contiguous blocks; block i runs `encoded_by_block[i]` on its
    [chips, hi-lo, subarrays] slice through the same vmapped scan
    interpreter as `device_run_program`.  This is the full-state
    reference the per-bank queue engine (`pim/queue.py`) is held
    bit-identical to in the differential suite — each block has its own
    program counter, blocks advance independently.
    """
    if len(encoded_by_block) != len(bank_blocks):
        raise ValueError("one encoded stream per bank block required")
    cover = [b for lo, hi in bank_blocks for b in range(lo, hi)]
    if cover != list(range(dev.banks)):
        raise ValueError(f"bank blocks {list(bank_blocks)} do not "
                         f"partition [0, {dev.banks})")
    datas, dccs = [], []
    for (lo, hi), enc in zip(bank_blocks, encoded_by_block):
        block = DrimDevice(data=dev.data[:, lo:hi], dcc=dev.dcc[:, lo:hi])
        out = _device_run_program(block, enc, faults,
                                  bank_lo=lo, banks_total=dev.banks)
        datas.append(out.data)
        dccs.append(out.dcc)
    return DrimDevice(data=jnp.concatenate(datas, axis=1),
                      dcc=jnp.concatenate(dccs, axis=1))


@functools.lru_cache(maxsize=None)
def _sharded_program_runner(mesh):
    spec = P(*MESH_AXES)

    def body(data: jax.Array, dcc: jax.Array, encoded: jax.Array):
        out = _device_run_program(DrimDevice(data=data, dcc=dcc), encoded)
        return out.data, out.dcc

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(spec, spec, P()), out_specs=(spec, spec),
                       check_vma=False)
    return jax.jit(fn)


def device_run_program_sharded(dev: DrimDevice, encoded: jax.Array,
                               mesh, *, faults=None) -> DrimDevice:
    """`device_run_program` over a (chips, banks) device mesh.

    The slot axis is embarrassingly parallel (every sub-array runs the
    same stream over its own rows), so `shard_map` splits the leading
    [chips, banks] dims across `mesh` with NO collectives: each mesh
    device runs the vmapped scan interpreter on its local block.  The
    mesh must use `MESH_AXES` names with shapes dividing (chips, banks)
    — `pim.mesh.fleet_mesh` constructs exactly that, falling back to a
    1x1 mesh on a single device (bit-identical to the vmap path either
    way).
    """
    if faults is not None and faults.active:
        raise ValueError(
            "fault injection is not supported on the shard_map path: "
            "global slot ids are not visible inside a mesh shard, so "
            "flips could not stay identical to the vmap engines; run "
            "faulted programs with mesh=None")
    data, dcc = _sharded_program_runner(mesh)(dev.data, dev.dcc, encoded)
    return DrimDevice(data=data, dcc=dcc)
