"""Plain numpy answers of the DRIM fleet programs, independent of `src/`.

Bit-planes are little-endian uint32 words: lane l is bit l % 32 of word
l // 32.  A binary dot of A [M, K] and B [N, K] sign bits puts output
(m, n) on lane m * N + n; its popcount of XNOR(A[m], B[n]) comes back as
counter planes, plane i holding bit i of every lane's count.
"""
from __future__ import annotations

from typing import List

import numpy as np


def xnor2(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return ~(np.asarray(a, np.uint32) ^ np.asarray(b, np.uint32))


def pack_lanes(bits: np.ndarray) -> np.ndarray:
    """[..., L] {0, 1} -> [..., ceil(L / 32)] little-endian words."""
    bits = np.asarray(bits, np.uint8)
    pad = (-bits.shape[-1]) % 32
    if pad:
        bits = np.concatenate(
            [bits, np.zeros(bits.shape[:-1] + (pad,), np.uint8)], -1)
    return np.packbits(bits, axis=-1, bitorder="little").view(np.uint32)


def xnor_popcounts(a_bits: np.ndarray, b_bits: np.ndarray) -> np.ndarray:
    """[M, N] count of equal bits between rows of A [M, K] and B [N, K]."""
    k = a_bits.shape[1]
    a = pack_lanes(a_bits).view(np.uint32)
    b = pack_lanes(b_bits).view(np.uint32)
    diff = np.zeros((a.shape[0], b.shape[0]), np.int64)
    for w in range(a.shape[1]):
        diff += np.bitwise_count(a[:, None, w] ^ b[None, :, w])
    return (k - diff).astype(np.int32)


def counter_planes(counts: np.ndarray, n_planes: int) -> List[np.ndarray]:
    """Per-lane counts (row-major lanes) -> the popcount's bit-planes."""
    flat = counts.reshape(-1)
    return [pack_lanes(((flat >> i) & 1).astype(np.uint8))
            for i in range(n_planes)]


def pm1_dot(a_bits: np.ndarray, b_bits: np.ndarray) -> np.ndarray:
    """[M, N] int32 dot of the +-1 vectors that sign bits A, B encode."""
    a = np.asarray(a_bits, np.int64) * 2 - 1
    b = np.asarray(b_bits, np.int64) * 2 - 1
    return (a @ b.T).astype(np.int32)
