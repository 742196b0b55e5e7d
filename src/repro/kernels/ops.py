"""Public jit'd wrappers for the DRIM-X kernels with backend dispatch.

The backend alone decides what runs:

    backend == tpu   -> pallas_call, compiled by Mosaic
    otherwise        -> ref.py jnp oracle (XLA-fused)

The two are numerically identical: the kernel test suite runs every
Pallas kernel in interpret mode against the same oracles, and
`tests/test_tpu_compile.py` compiles the main-path kernels for a v5e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .bitwise import bitwise as _bitwise_pallas
from .bitserial_add import bitplane_add as _bitplane_add_pallas
from .packbits import pack_signs as _pack_pallas, unpack_signs as _unpack_pallas
from .xnor_popcount import xnor_gemm_packed as _xnor_gemm_pallas

WORD_BITS = 32


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --- bulk bit-wise ops -------------------------------------------------------

def bitwise(op: str, a, b=None, c=None):
    if not _on_tpu():
        return ref.bitwise_ref(op, a, b, c)
    return _bitwise_pallas(op, a, b, c)


def xnor(a, b):
    return bitwise("xnor", a, b)


def maj3(a, b, c):
    return bitwise("maj3", a, b, c)


def full_adder(a, b, c):
    return bitwise("fa", a, b, c)


# --- pack / unpack -----------------------------------------------------------

def pack_signs(x):
    """[..., K] -> [..., ceil(K/32)] uint32 sign words (flattens leading)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if _on_tpu():
        out = _pack_pallas(x2)
    else:
        pad = (-x2.shape[-1]) % WORD_BITS
        x2 = jnp.pad(x2, ((0, 0), (0, pad)), constant_values=-1.0)
        out = ref.pack_signs_ref(x2)
    return out.reshape(*lead, out.shape[-1])


def unpack_signs(p, dtype=jnp.bfloat16):
    lead = p.shape[:-1]
    p2 = p.reshape(-1, p.shape[-1])
    if _on_tpu():
        out = _unpack_pallas(p2, dtype)
    else:
        out = ref.unpack_signs_ref(p2, dtype)
    return out.reshape(*lead, out.shape[-1])


def sign_bits(x):
    """[..., K] values -> {0, 1} sign bits (1 where x >= 0), uint8.

    The sign convention every packed/DRIM path shares: bit 1 encodes
    +1, matching `pack_signs` / `ref.pack_signs_ref` little-endian
    words and `pim.bnn.stage_bnn_planes` lane planes.
    """
    return (jnp.asarray(x) >= 0).astype(jnp.uint8)


def unpack_sign_bits_np(packed, k_bits: int):
    """Host-side inverse of `pack_signs` word layout: [..., W] uint32
    little-endian sign words -> [..., k_bits] {0, 1} uint8 bits (the
    pad bits beyond k_bits are dropped).  Numpy in, numpy out — the
    DRIM serving route unpacks weights once per layer on the host."""
    words = np.ascontiguousarray(np.asarray(packed, np.uint32))
    bits = np.unpackbits(words.view(np.uint8).reshape(*words.shape[:-1], -1),
                         axis=-1, bitorder="little")
    return bits[..., :k_bits]


# --- binary GEMM -------------------------------------------------------------

def xnor_gemm_packed(a_packed, b_packed, k_bits: int):
    """C[M,N] int32 = ±1-dot of packed sign rows (XNOR-popcount identity)."""
    if not _on_tpu():
        return ref.xnor_gemm_ref(a_packed, b_packed, k_bits)
    return _xnor_gemm_pallas(a_packed, b_packed, k_bits)


def binary_matmul(x, w_packed, k_bits: int, dtype=jnp.bfloat16):
    """Dense activations x [..., K] vs bit-packed weights [N, W].

    Binarizes x on the fly (sign), runs the XNOR-popcount GEMM, returns
    [..., N] in `dtype` (unscaled ±1 dot; layers apply XNOR-Net scaling).
    """
    lead = x.shape[:-1]
    xp = pack_signs(x.reshape(-1, x.shape[-1]))
    out = xnor_gemm_packed(xp, w_packed, k_bits)
    return out.astype(dtype).reshape(*lead, w_packed.shape[0])


# --- bit-plane adder ---------------------------------------------------------

def bitplane_add(a_planes, b_planes):
    if not _on_tpu():
        return ref.bitplane_add_ref(a_planes, b_planes)
    return _bitplane_add_pallas(a_planes, b_planes)


# --- popcount (VPU path, used by hamming-distance style apps) ---------------

def popcount(x):
    return ref.popcount_u32_ref(x)
