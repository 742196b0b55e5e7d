"""The `offload_chunks_per_run` reader against the program's counters."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _tiny  # noqa: E402


def _read():
    from bench import spec
    return spec.Benchmark().reader("offload_chunks_per_run").read


def test_reads_nothing_without_the_counters():
    from repro.runtime import telemetry
    with telemetry.fresh():
        assert _read()(None) is None


def test_reads_chunks_over_runs_by_hand():
    from repro.pim.bnn import OFFLOAD_STATS
    from repro.runtime import telemetry
    read = _read()
    with telemetry.fresh():
        # one layer's decode FFN on DRIM-R: 6 + 6 + 24 chunks in 3 runs
        OFFLOAD_STATS["runs"] += 3
        OFFLOAD_STATS["chunks"] += 36
        assert read(None) == 12.0
        # a ragged tail runs alone: one more run of one chunk
        OFFLOAD_STATS["runs"] += 1
        OFFLOAD_STATS["chunks"] += 1
        assert read(None) == 37 / 4


def test_reads_what_serve_bnn_matmul_counts():
    """Five 8-wide chunks of 16 lanes share one run of a 1024-lane
    wave."""
    import numpy as np
    from repro.core import DrimGeometry
    from repro.pim.bnn import serve_bnn_matmul
    from repro.runtime import telemetry
    rng = np.random.default_rng(6)
    a = rng.integers(0, 2, (2, 40), dtype=np.uint8)
    b = rng.integers(0, 2, (8, 40), dtype=np.uint8)
    with telemetry.fresh():
        serve_bnn_matmul(a, b, geom=DrimGeometry(**_tiny.TINY_GEOM),
                         k_tile=8)
        assert _read()(None) == 5.0
