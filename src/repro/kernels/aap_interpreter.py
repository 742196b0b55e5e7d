"""Pallas AAP bit-plane interpreter: the encoded stream as DATA.

The lax engines ("resident"/"queued") specialize the AAP stream at trace
time — `isa.run_program_unrolled` unrolls every instruction into the XLA
graph with static word-lines.  This kernel is the opposite design point,
and the closest software analogue of the DRIM sub-array itself: the
program is lowered host-side to the int32 micro-op table of
`isa.encode_kernel_stream` and executed on-device by a real program
counter (`lax.fori_loop` over the stream, the three sense-amp outcomes
— pass-through, DRA-XNOR, TRA-MAJ3 — selected by all-ones masks).  The
row-plane state stays resident in a VMEM scratch across the whole
program — rows never round-trip through HBM between AAPs, exactly as
DRAM rows never leave the sub-array between ACTIVATEs.

Memory layout (what the TPU's Mosaic compiler accepts):

  * the micro-op table (and, with faults, the per-op thresholds) rides
    in SMEM through scalar prefetch, so every word-line index is a
    scalar and a row is addressed as `state[pl.ds(row, 1)]` on the
    untiled leading axis of the state scratch;
  * word columns are independent, so one wave's [n_rows_in, chips,
    banks, subarrays, row_words] tile block flattens to
    [n_rows_in, total_words] and folds to [n_rows_in, total/128, 128]:
    each grid cell owns `block_cols` words as a full (sublane, lane)
    tile slab per row;
  * planes are bit-cast to int32 at the kernel boundary — the ops are
    pure bit-wise, so signedness never shows, and a scalar BL̄ flag `f`
    becomes the XOR mask `-f` (0 or all-ones).

Each cell starts from a fresh zeroed state of `dcc_state_rows(n_rows)`
rows (normal rows plus the two DCC cells), loads its operand planes
into rows [0, n_in) and replays the stream over it.

The kernel runs compiled on a TPU and in interpret mode on every other
backend; `interpret=` overrides that choice per call.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.faults import fault_mask, mix32, slot_ids_grid
from repro.core.isa import (AAP, KSTREAM_COLS, OP_DRA, OP_TRA,
                            dcc_state_rows, encode_kernel_stream,
                            kstream_slot)

# Largest word-column block per grid cell, and the VMEM the cell's
# buffers may take (state scratch + double-buffered in/out blocks).
# Blocks shrink in steps of one (8, 128) tile slab until they fit.
BLOCK_COLS = 4096
_LANES = 128
_SLAB = 8 * _LANES
_VMEM_BUDGET = 12 * 1024 * 1024


def default_interpret() -> bool:
    """Compiled on a TPU, interpret mode on every other backend."""
    return jax.default_backend() != "tpu"


def block_cols_for(n_in: int, n_state: int, n_out: int, n_meta: int,
                   total: int, block_cols: int = BLOCK_COLS) -> int:
    """Words per grid cell: a multiple of one (8, 128) slab, no larger
    than the padded payload, shrunk until the cell's VMEM fits."""
    bc = min(block_cols, -(-total // _SLAB) * _SLAB)
    bc = max(_SLAB, bc // _SLAB * _SLAB)
    per_word = 4 * (n_state + 2 * (n_in + n_out + n_meta))
    while bc > _SLAB and bc * per_word > _VMEM_BUDGET:
        bc -= _SLAB
    return bc


def _interp_kernel(n_in: int, n_ins: int,
                   out_slots: Tuple[Tuple[int, int], ...],
                   fault_cfg: Optional[Tuple[int, Tuple]], *refs):
    """One grid cell: replay the whole micro-op stream over its columns.

    refs: stream (SMEM, flat [n_ins * KSTREAM_COLS]), then with faults
    the per-op thresholds (SMEM [n_ins]) and the [2, S, 128] column
    metadata block, then the [n_in, S, 128] operand block, the
    [n_out, S, 128] output block and the [n_state, S, 128] state
    scratch.  Reads resolve before writes within one AAP, and the
    up-to-four write slots replay in instruction-arg order — bit-exact
    with `run_program_unrolled`.

    fault_cfg = (n_positions, stuck) arms Table-3 injection: each
    DRA/TRA XORs the counter-based flip mask the lax engines draw for
    its (op-index, slot) onto the BL value before write-back (the
    threshold is zero for copies and protected ops), and `stuck`
    pins stuck-at state rows after every instruction.
    """
    if fault_cfg is None:
        stream_ref, in_ref, out_ref, st_ref = refs
    else:
        stream_ref, thresh_ref, meta_ref, in_ref, out_ref, st_ref = refs
        n_positions, stuck = fault_cfg
        slot_h = jax.lax.bitcast_convert_type(meta_ref[0], jnp.uint32)
        word_ids = jax.lax.bitcast_convert_type(meta_ref[1], jnp.uint32)

    tile = st_ref.shape[1:]

    def force():
        for row, v in stuck:
            st_ref[pl.ds(row, 1)] = jnp.full((1,) + tile, -v, jnp.int32)

    st_ref[...] = jnp.zeros(st_ref.shape, jnp.int32)
    st_ref[pl.ds(0, n_in)] = in_ref[...]
    if fault_cfg is not None:
        force()

    def step(i, carry):
        base = i * KSTREAM_COLS

        def rd(k):
            row = st_ref[pl.ds(stream_ref[base + 1 + 2 * k], 1)]
            return row ^ -stream_ref[base + 2 + 2 * k]

        a, b, c = rd(0), rd(1), rd(2)
        kind = stream_ref[base]
        is_dra = -(kind == 1).astype(jnp.int32)
        is_tra = -(kind == 2).astype(jnp.int32)
        bl = ((a & ~(is_dra | is_tra))                   # COPY/COPY2
              | (~(a ^ b) & is_dra)                      # DRA: XNOR
              | (((a & b) | (a & c) | (b & c)) & is_tra))  # TRA: MAJ3
        if fault_cfg is not None:
            # same-width int convert keeps the bits (XLA semantics)
            t = thresh_ref[i].astype(jnp.uint32)
            flip = fault_mask(t, i, slot_h, word_ids, n_positions)
            bl = bl ^ jax.lax.bitcast_convert_type(flip, jnp.int32)[None]
        for k in range(4):                     # write slots, in arg order
            col = base + 7 + 3 * k
            row, neg = stream_ref[col], stream_ref[col + 1]

            @pl.when(stream_ref[col + 2] != 0)
            def _write():
                st_ref[pl.ds(row, 1)] = bl ^ -neg
        if fault_cfg is not None:
            force()
        return carry

    jax.lax.fori_loop(0, n_ins, step, 0)
    for j, (row, neg) in enumerate(out_slots):
        v = st_ref[pl.ds(row, 1)]
        out_ref[pl.ds(j, 1)] = ~v if neg else v


def _op_thresholds(program: Tuple[AAP, ...], faults) -> np.ndarray:
    """[n_ins] per-instruction failure thresholds, uint32 bits held as
    int32 (SMEM is a 32-bit scalar memory)."""
    tvec = np.zeros(len(program), np.uint32)
    prot = set(faults.protected_ops)
    for i, ins in enumerate(program):
        if i in prot:
            continue
        if ins.op == OP_DRA:
            tvec[i] = faults.dra_thresh
        elif ins.op == OP_TRA:
            tvec[i] = faults.tra_thresh
    return tvec.view(np.int32)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _fold(x: jax.Array, padded: int) -> jax.Array:
    """[rows, total] planes -> [rows, padded/128, 128] int32 slabs."""
    x = jax.lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)
    x = jnp.pad(x, ((0, 0), (0, padded - x.shape[1])))
    return x.reshape(x.shape[0], padded // _LANES, _LANES)


def pallas_wave_fn(program: Tuple[AAP, ...], result_rows: Tuple[int, ...],
                   n_rows: int, *, interpret: bool | None = None,
                   block_cols: int = BLOCK_COLS,
                   faults=None, bank_geom=None):
    """Build the `one_wave(tiles)` body behind `engine="pallas"`.

    Same contract as `scheduler.wave_fn`: maps one wave's staged tile
    block [n_rows_in, chips, banks, subarrays, row_words] to the
    readback block [len(result_rows), ...].  The stream is encoded
    host-side once per (program, n_rows) signature; the enclosing
    `_wave_runner` memoizes the compiled executor.

    With a `FaultModel`, per-column slot hashes and per-op thresholds
    ride into the kernel as extra inputs and the kernel draws the exact
    flips the lax engines draw for the same (seed, op-index, global
    slot).  `bank_geom` = (bank_lo, banks_total) anchors per-queue
    payloads at their physical bank offset.  Padded grid columns may
    draw (discarded) flips; they are sliced away with the padding.
    """
    if interpret is None:
        interpret = default_interpret()
    if faults is not None:
        faults = faults.wave_model()
    out_slots = tuple(kstream_slot(r, n_rows) for r in result_rows)
    bank_lo, banks_total = bank_geom if bank_geom is not None else (0, None)
    stuck = ()
    if faults is not None:
        stuck = tuple((wl, v) for wl, v in faults.stuck_rows
                      if wl < n_rows)

    if not len(program):
        # Degenerate stream: readback of an untouched sub-array.
        def one_wave(tiles: jax.Array) -> jax.Array:
            zeros = jnp.zeros(tiles.shape[1:], jnp.uint32)

            def pick(row, neg):
                v = tiles[row] if row < tiles.shape[0] else zeros
                for srow, sval in stuck:
                    if srow == row:
                        v = ~zeros if sval else zeros
                return ~v if neg else v
            return jnp.stack([pick(row, neg) for row, neg in out_slots])
        return one_wave

    stream = jnp.asarray(
        encode_kernel_stream(program, n_rows=n_rows).reshape(-1))
    n_ins = len(program)
    n_state = dcc_state_rows(n_rows)
    n_out = len(result_rows)
    prefetch = (stream,)
    if faults is not None:
        prefetch += (jnp.asarray(_op_thresholds(program, faults)),)

    def one_wave(tiles: jax.Array) -> jax.Array:
        n_in = tiles.shape[0]
        flat = tiles.reshape(n_in, -1)
        total = flat.shape[1]
        n_meta = 2 if faults is not None else 0
        bc = block_cols_for(n_in, n_state, n_out, n_meta, total,
                            block_cols)
        padded = _round_up(total, bc)
        sub = bc // _LANES

        def spec(rows):
            return pl.BlockSpec((rows, sub, _LANES),
                                lambda j, *_: (0, j, 0))

        inputs = (_fold(flat, padded),)
        in_specs = [spec(n_in)]
        cfg = None
        if faults is not None:
            c, b, s, w = tiles.shape[1:]
            grid = slot_ids_grid(c, b, s, bank_lo=bank_lo,
                                 banks_total=banks_total)
            slot_h = mix32(grid ^ jnp.uint32(faults.seed)).reshape(-1)
            meta = jnp.stack([jnp.repeat(slot_h, w),
                              jnp.tile(jnp.arange(w, dtype=jnp.uint32),
                                       grid.size)])
            inputs = (_fold(meta, padded),) + inputs
            in_specs = [spec(2)] + in_specs
            cfg = (w * 32, stuck)
        out = pl.pallas_call(
            functools.partial(_interp_kernel, n_in, n_ins, out_slots, cfg),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(padded // bc,),
                in_specs=in_specs,
                out_specs=spec(n_out),
                scratch_shapes=[pltpu.VMEM((n_state, sub, _LANES),
                                           jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((n_out, padded // _LANES,
                                            _LANES), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(*prefetch, *inputs)
        out = jax.lax.bitcast_convert_type(out, jnp.uint32)
        return out.reshape(n_out, padded)[:, :total].reshape(
            (n_out,) + tiles.shape[1:])
    return one_wave
