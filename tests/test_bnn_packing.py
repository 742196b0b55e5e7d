"""K-chunk packing in `pim.bnn.serve_bnn_matmul`.

The full-width K chunks of one GEMM run side by side in the lanes of
one `Lowered.run`, as many as one wave holds.  On `small_geom` a wave
is 64 sub-arrays x 64 bits = 4096 lanes.  Every case is bit-exact
against a plain numpy ±1 dot, books its chunks and runs in the
"offload" counters, and feeds each run the planes of the layout the
docstring of `_stage_chunk_planes` states, built here plane by plane.
"""
import numpy as np
import pytest

from repro.core import DRIM_R
from repro.core.subarray import WORD_BITS
from repro.pim import bnn
from repro.pim.bnn import (OFFLOAD_STATS, _chunks_per_run,
                           _stage_chunk_planes, serve_bnn_matmul,
                           serving_lowering, stage_bnn_planes)
from repro.runtime import telemetry


def _pm1_dot(a, b):
    return (2 * a.astype(np.int64) - 1) @ (2 * b.astype(np.int64) - 1).T


def _plane_by_plane(a, b):
    """One chunk's planes, one numpy pass per plane: lane m*N + n holds
    A[m, k] in plane a_k and B[n, k] in plane b_k."""
    m, k_bits = a.shape
    n = b.shape[0]
    lanes = m * n
    n_words = -(-lanes // WORD_BITS)
    planes = []
    for lane_bits in ([np.repeat(a[:, k], n) for k in range(k_bits)]
                      + [np.tile(b[:, k], m) for k in range(k_bits)]):
        padded = np.zeros(n_words * WORD_BITS, np.uint8)
        padded[:lanes] = lane_bits
        planes.append(np.packbits(padded, bitorder="little")
                      .view(np.uint32))
    return planes


def _lane_bits(plane, lanes):
    return np.unpackbits(np.asarray(plane).view(np.uint8),
                         bitorder="little")[:lanes]


def _operands(seed, m, n, k_bits):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 2, (m, k_bits), dtype=np.uint8),
            rng.integers(0, 2, (n, k_bits), dtype=np.uint8))


@pytest.mark.parametrize("m,n,k_bits,k_tile,runs", [
    (2, 8, 64, 16, [4]),              # four full-width chunks, one run
    (3, 5, 300, 128, [2, 1]),         # ragged K: the 44-wide tail alone
    (3, 7, 40, 8, [5]),               # M*N = 21 lanes, not a word multiple
    (64, 64, 24, 8, [1, 1, 1]),       # M*N fills the wave: one run a chunk
    (30, 40, 40, 8, [3, 2]),          # g = 3 over five chunks
], ids=["packed", "ragged", "odd-lanes", "wave-sized", "partial-group"])
def test_packed_runs_are_bit_exact(monkeypatch, small_geom, m, n, k_bits,
                                   k_tile, runs):
    """Bit-exact, one run per group of chunks, and each run's planes
    are the chunks' own planes laid side by side in the lanes."""
    a, b = _operands(k_bits, m, n, k_bits)
    fed = []
    for kc in set(bnn.k_chunks(k_bits, k_tile)):
        low = serving_lowering(kc, geom=small_geom)
        monkeypatch.setattr(low, "run", lambda *planes, _run=low.run, **kw:
                            fed.append((planes, kw["n_bits"]))
                            or _run(*planes, **kw))
    with telemetry.fresh():
        got = serve_bnn_matmul(a, b, geom=small_geom, k_tile=k_tile)
        assert dict(OFFLOAD_STATS) == {"runs": len(runs),
                                       "chunks": sum(runs)}
    np.testing.assert_array_equal(got, _pm1_dot(a, b))
    assert [n_bits for _, n_bits in fed] == [g * m * n for g in runs]
    offset = 0
    for (planes, n_bits), group in zip(fed, runs):
        kc = min(k_tile, k_bits - offset)
        per_chunk = [_plane_by_plane(a[:, s:s + kc], b[:, s:s + kc])
                     for s in range(offset, offset + group * kc, kc)]
        if group == 1:
            for got_plane, want in zip(planes, per_chunk[0], strict=True):
                np.testing.assert_array_equal(got_plane, want)
        for p, plane in enumerate(planes):
            np.testing.assert_array_equal(
                _lane_bits(plane, n_bits),
                np.concatenate([_lane_bits(c[p], m * n) for c in per_chunk]))
        offset += group * kc
    assert offset == k_bits


@pytest.mark.parametrize("m,n", [(30, 40), (32, 64), (41, 50), (64, 64)])
def test_a_group_never_adds_a_wave(small_geom, m, n):
    """The schedule after a run of a whole group has the waves of a run
    of one chunk, whatever the group size (3, 2, 1, 1 here)."""
    per_run = _chunks_per_run(m * n, small_geom)
    a, b = _operands(m * n, m, n, 8 * per_run)
    low = serving_lowering(8, geom=small_geom)
    serve_bnn_matmul(a[:, :8], b[:, :8], geom=small_geom, k_tile=8)
    one_chunk = low.schedule
    with telemetry.fresh():
        got = serve_bnn_matmul(a, b, geom=small_geom, k_tile=8)
        assert dict(OFFLOAD_STATS) == {"runs": 1, "chunks": per_run}
    np.testing.assert_array_equal(got, _pm1_dot(a, b))
    assert low.schedule.waves == one_chunk.waves == 1
    assert low.schedule.tiles == -(-per_run * m * n // small_geom.row_bits)


@pytest.mark.parametrize("lanes,per_run", [
    (12_288, 170), (3_072, 682), (98_304, 21),     # DRIM-R decode shapes
    (2_097_152, 1), (4_000_000, 1), (1, 2_097_152),
])
def test_chunks_per_run_fill_one_wave(lanes, per_run):
    assert _chunks_per_run(lanes, DRIM_R) == per_run
    assert per_run * lanes <= max(lanes, DRIM_R.parallel_bits)


def test_stage_bnn_planes_keeps_its_layout():
    """`stage_bnn_planes` feeds the plane-by-plane layout and a zero
    plane of the same width."""
    a, b = _operands(9, 5, 7, 12)
    feeds, lanes = stage_bnn_planes(a, b)
    want = _plane_by_plane(a, b)
    assert lanes == 35
    assert list(feeds) == ([f"a{k}" for k in range(12)]
                           + [f"b{k}" for k in range(12)] + ["zero"])
    for name, plane in zip(list(feeds)[:-1], want, strict=True):
        np.testing.assert_array_equal(feeds[name], plane)
    np.testing.assert_array_equal(feeds["zero"], np.zeros(2, np.uint32))


def test_staging_slabs_match_one_pass(monkeypatch):
    """Planes staged a few at a time equal those of one pass."""
    a, b = _operands(11, 6, 9, 3 * 16)
    whole, lanes = _stage_chunk_planes(a, b, 3)
    monkeypatch.setattr(bnn, "_STAGE_SLAB_BYTES", 5 * lanes)
    slabbed, _ = _stage_chunk_planes(a, b, 3)
    assert len(slabbed) == len(whole) == 32
    for got, want in zip(slabbed, whole):
        np.testing.assert_array_equal(got, want)
