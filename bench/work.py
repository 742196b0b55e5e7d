"""Work counted from the traffic, never from the program: operand bits
consumed, and the least bytes a wave program must move."""
from __future__ import annotations

import math

WORD_BITS = 32


# -- DRIM fleet -------------------------------------------------------------

def xnor2_bitops(n_bits: int) -> int:
    """A bulk XNOR of two n-bit operands: one XNOR per bit position."""
    return n_bits


def bnn_dot_bitops(m: int, n: int, k: int) -> int:
    """An [M, K] x [N, K] binary dot: M*N*K operand bit positions, one
    XNOR each."""
    return m * n * k


def counter_planes(k: int) -> int:
    """Bit-planes of a popcount of K ones: ceil(log2(K + 1))."""
    return max(1, math.ceil(math.log2(k + 1)))


def plane_bytes(lanes: int) -> int:
    """One bit-plane over `lanes` lanes, in whole 32-bit words."""
    return -(-lanes // WORD_BITS) * WORD_BITS // 8


def xnor2_min_bytes(n_bits: int) -> int:
    """Two operand planes read once, the result plane written once."""
    return 3 * plane_bytes(n_bits)


def bnn_dot_min_bytes(m: int, n: int, k: int) -> int:
    """2K operand planes read once and the popcount's counter planes
    written once, over the call's M*N real lanes (not the padded wave)."""
    return (2 * k + counter_planes(k)) * plane_bytes(m * n)


def occupied_tiles(lanes: int, row_bits: int) -> int:
    """Sub-array rows (tiles) that hold at least one lane."""
    return -(-lanes // row_bits)
