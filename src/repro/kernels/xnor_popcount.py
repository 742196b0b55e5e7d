"""Pallas TPU kernel: XNOR-popcount binary GEMM (the DRIM flagship op).

DRIM computes bulk X(N)OR in the memory array; the dominant consumer of
bulk X(N)OR in modern workloads is the binarized matmul
(XNOR-Net / BNN):  C[m,n] = dot(sign(A[m,:]), sign(B[n,:]))
                           = 2*popcount(XNOR(pack(A), pack(B))) - K.

TPU-native adaptation (DESIGN.md §2): instead of a VPU popcount reduction
(which cannot feed the MXU), each K-chunk of packed sign words is decoded
in VMEM to ±1 int8 tiles and pushed through the 128x128 MXU with int32
accumulation — recovering the exact XNOR-popcount result while running at
matmul roofline.  Weights stay bit-packed in HBM (32x compression), which
is the paper's "the memory array holds X(N)OR operands" insight mapped to
the HBM->VMEM hierarchy.

Grid: (M/BM, N/BN, W/BW) with the packed-K dimension innermost
(arbitrary) so the f32/int32 accumulator lives in VMEM across the
reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD_BITS = 32
BM, BN, BW = 128, 128, 128  # <= 128 words = 4096 K-bits per block


def _unpack_pm1(words: jax.Array, dtype) -> jax.Array:
    """[R, W] uint32 -> [R, W*32] ±1 (bit=1 -> +1, bit=0 -> -1)."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    pm1 = (bits.astype(jnp.int32) * 2 - 1).astype(dtype)
    return pm1.reshape(words.shape[0], words.shape[1] * WORD_BITS)


def _xnor_gemm_kernel(a_ref, b_ref, o_ref, *, acc_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = _unpack_pm1(a_ref[...], acc_dtype)   # [BM, BW*32]
    b = _unpack_pm1(b_ref[...], acc_dtype)   # [BN, BW*32]
    o_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k_bits", "interpret"))
def xnor_gemm_packed(a_packed: jax.Array, b_packed: jax.Array,
                     k_bits: int, *, interpret: bool = False) -> jax.Array:
    """C[M,N] = 2*popcount(XNOR(a,b)) - k_bits, exactly, as int32.

    a_packed [M, W], b_packed [N, W] uint32 sign-bit words.  Tail words
    must be zero-padded on BOTH operands; the pad bits then each
    contribute +1 ((-1)·(-1)) to the ±1 dot, corrected by subtracting
    (W*32 - k_bits).
    """
    m, w = a_packed.shape
    n, w2 = b_packed.shape
    assert w == w2, (w, w2)

    mp = pl.cdiv(m, BM) * BM
    np_ = pl.cdiv(n, BN) * BN
    # A K block is either the whole packed width or a multiple of 128
    # lane-words: the two shapes the TPU tiling accepts for a last dim.
    bw = w if w <= BW else BW
    wp = pl.cdiv(w, bw) * bw
    a2 = jnp.pad(a_packed.astype(jnp.uint32), ((0, mp - m), (0, wp - w)))
    b2 = jnp.pad(b_packed.astype(jnp.uint32), ((0, np_ - n), (0, wp - w)))

    grid = (mp // BM, np_ // BN, wp // bw)
    out = pl.pallas_call(
        functools.partial(_xnor_gemm_kernel, acc_dtype=jnp.int8),
        grid=grid,
        in_specs=[pl.BlockSpec((BM, bw), lambda i, j, k: (i, k)),
                  pl.BlockSpec((BN, bw), lambda i, j, k: (j, k))],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a2, b2)

    pad_bits = wp * WORD_BITS - k_bits
    return out[:m, :n] - pad_bits
