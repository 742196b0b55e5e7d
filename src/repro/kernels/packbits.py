"""Pallas TPU kernels: sign-bit pack / unpack between dense and packed.

pack_signs  : [R, K] float  -> [R, K/32] uint32   (bit=1 where x >= 0)
unpack_signs: [R, W] uint32 -> [R, W*32] ±1 dtype

These are bandwidth-bound layout ops (the DRIM "RowClone" analogue: data
enters the compute-capable layout once, then all bulk ops run on packed
rows).  Each grid step handles up to 128 packed words (4096 dense
columns); both kernels are validated against ref.py oracles in interpret
mode and exposed through ops.py with a fused jnp fallback for non-TPU
backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

WORD_BITS = 32
BR = 256          # rows per block (fewer when the array has fewer)
BWORDS = 128      # packed words per block -> 4096 unpacked columns


def _blocks(r: int, w: int):
    """(rows, words) per block and the padded (rows, words): the whole
    packed width up to BWORDS words, else BWORDS-word (lane-dense)
    blocks — the two last-dim shapes the TPU tiling accepts."""
    br = min(BR, pl.cdiv(r, 8) * 8)
    bw = w if w <= BWORDS else BWORDS
    return br, bw, pl.cdiv(r, br) * br, pl.cdiv(w, bw) * bw


def _pack_kernel(x_ref, lo_ref, hi_ref, o_ref):
    """Pack by matmul: bit k of the block lands in word k // 32 through
    a 0/1 x power-of-two product, 16 bits per half so every f32 sum is
    exact.  (Mosaic neither splits the lane axis into (words, 32) nor
    reduces unsigned ints, so the VPU shift-and-sum form does not
    compile.)"""
    bits = (x_ref[...] >= 0).astype(jnp.bfloat16)          # [br, Kb]
    lo = jnp.dot(bits, lo_ref[...], preferred_element_type=jnp.float32)
    hi = jnp.dot(bits, hi_ref[...], preferred_element_type=jnp.float32)
    word = lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)
    o_ref[...] = jax.lax.bitcast_convert_type(word, jnp.uint32)


def _pack_weights(k_block: int):
    """[Kb, Kb/32] bf16 place values of the low and high 16 bits."""
    k = jnp.arange(k_block)
    bit = k % WORD_BITS
    onto = (k // WORD_BITS)[:, None] == jnp.arange(k_block // WORD_BITS)
    place = jnp.exp2((bit % 16).astype(jnp.float32))[:, None]
    lo = jnp.where(onto & (bit < 16)[:, None], place, 0.0)
    hi = jnp.where(onto & (bit >= 16)[:, None], place, 0.0)
    return lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)


def _unpack_kernel(p_ref, o_ref, *, dtype):
    p = p_ref[...]                       # [br, bw]
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (p[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    pm1 = (bits.astype(jnp.int32) * 2 - 1).astype(dtype)
    o_ref[...] = pm1.reshape(p.shape[0], p.shape[1] * WORD_BITS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_signs(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """[R, K] -> [R, ceil(K/32)] uint32 sign-bit words (pad bits = 0)."""
    r, k = x.shape
    w = pl.cdiv(k, WORD_BITS)
    br, bw, rp, wp = _blocks(r, w)
    # pad with -1 so pad bits pack to 0
    x2 = jnp.pad(x.astype(jnp.float32),
                 ((0, rp - r), (0, wp * WORD_BITS - k)),
                 constant_values=-1.0)
    lo, hi = _pack_weights(bw * WORD_BITS)
    wspec = pl.BlockSpec((bw * WORD_BITS, bw), lambda i, j: (0, 0))
    out = pl.pallas_call(
        _pack_kernel, grid=(rp // br, wp // bw),
        in_specs=[pl.BlockSpec((br, bw * WORD_BITS), lambda i, j: (i, j)),
                  wspec, wspec],
        out_specs=pl.BlockSpec((br, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, wp), jnp.uint32),
        interpret=interpret,
    )(x2, lo, hi)
    return out[:r, :w]


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def unpack_signs(p: jax.Array, dtype=jnp.bfloat16, *,
                 interpret: bool = False) -> jax.Array:
    """[R, W] uint32 -> [R, W*32] ±1 values of `dtype`."""
    r, w = p.shape
    br, bw, rp, wp = _blocks(r, w)
    p2 = jnp.pad(p.astype(jnp.uint32), ((0, rp - r), (0, wp - w)))
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, dtype=dtype),
        grid=(rp // br, wp // bw),
        in_specs=[pl.BlockSpec((br, bw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bw * WORD_BITS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, wp * WORD_BITS), dtype),
        interpret=interpret,
    )(p2)
    return out[:r, :w * WORD_BITS]
