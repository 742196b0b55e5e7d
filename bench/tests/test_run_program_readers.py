"""The `programs_per_run` and `h2d_transfers_per_run` readers against
the program's counters."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _tiny  # noqa: E402


def _read(metric):
    from bench import spec
    return spec.Benchmark().reader(metric).read


@pytest.mark.parametrize("metric", ["programs_per_run",
                                    "h2d_transfers_per_run"])
def test_reads_nothing_without_the_counter(metric):
    """No run, or runs of a program that does not count: nothing."""
    from repro.pim.compiler import RUN_STATS
    from repro.runtime import telemetry
    read = _read(metric)
    with telemetry.fresh():
        assert read(None) is None
        RUN_STATS["calls"] += 3
        assert read(None) is None


def test_reads_counters_over_calls_by_hand():
    from repro.pim.compiler import RUN_STATS
    from repro.runtime import telemetry
    programs, transfers = (_read("programs_per_run"),
                           _read("h2d_transfers_per_run"))
    with telemetry.fresh():
        # four fused runs, one of them with host planes
        RUN_STATS["calls"] += 4
        RUN_STATS["programs"] += 4
        RUN_STATS["h2d_transfers"] += 1
        assert programs(None) == 1.0 and transfers(None) == 0.25
        # a queued run: its stager and its MIMD runner
        RUN_STATS["calls"] += 1
        RUN_STATS["programs"] += 2
        RUN_STATS["h2d_transfers"] += 0
        assert programs(None) == 6 / 5 and transfers(None) == 1 / 5


@pytest.mark.parametrize("where,transfers", [("host", 1.0),
                                             ("device", 0.0)])
def test_reads_what_a_run_counts(where, transfers):
    """A traced K=8 kernel on the tiny geometry: one program a run, one
    transfer for host planes and none for device planes."""
    import jax.numpy as jnp
    import numpy as np
    import drim
    from repro.core import DrimGeometry
    from repro.pim.bnn import bitlinear_kernel
    from repro.runtime import telemetry
    rng = np.random.default_rng(8)
    planes = [rng.integers(0, 1 << 32, 24, dtype=np.uint32)
              for _ in range(16)]
    if where == "device":
        planes = [jnp.asarray(p) for p in planes]
    low = drim.compile(bitlinear_kernel(8).trace(),
                       geom=DrimGeometry(**_tiny.TINY_GEOM)).lower()
    with telemetry.fresh():
        low.run(*planes)
        low.run(*planes)
        assert _read("programs_per_run")(None) == 1.0
        assert _read("h2d_transfers_per_run")(None) == transfers
