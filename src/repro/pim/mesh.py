"""Fleet mesh: lay the simulated DRIM slot axis over JAX devices.

`DrimDevice` batches every (chip, bank, subarray) slot into one pytree
and `device_run_program` vmaps over the flattened slot axis — pure data
parallelism with no cross-slot communication.  That makes the leading
[chips, banks] dims the natural `shard_map` cut for multi-device (and
eventually multi-host) simulation of DRIM-S-scale fleets: each mesh
device simulates its own block of banks, bit-identical to the
single-device path.

Mesh layout (axes named by `core.device.MESH_AXES`):

            banks ->
    chips   +--------+--------+
      |     | dev 0  | dev 1  |     each device holds
      v     +--------+--------+     [chips/mc, banks/mb, subarrays,
            | dev 2  | dev 3  |      rows, words] of the fleet state
            +--------+--------+

`fleet_mesh` picks the largest (mc, mb) with mc | chips, mb | banks and
mc*mb <= available devices, preferring to split banks (the axis DRIM-S
scales: 256 banks x 152 sub-arrays).  On a single device that is a 1x1
mesh, so the sharded code path always works — tier-1 stays green on a
bare CPU runner, and `XLA_FLAGS=--xla_force_host_platform_device_count=8`
exercises real multi-device partitioning in CI.

Construction reuses `launch.mesh.make_named_mesh`, and every placement
is validated with `runtime.sharding.sanitize_spec` (the same exact-
divisibility rule jit in/out shardings enforce).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.device import DrimDevice, MESH_AXES
from repro.core.timing import DrimGeometry
from repro.launch.mesh import make_named_mesh
from repro.runtime.sharding import sanitize_spec

AXIS_CHIPS, AXIS_BANKS = MESH_AXES

# Device state [chips, banks, subarrays, rows, words]: shard the two
# leading dims.  Staged wave payloads [waves, n_rows, chips, banks,
# subarrays, row_words] carry the same split two axes later.
DEVICE_SPEC = P(AXIS_CHIPS, AXIS_BANKS)
STAGED_SPEC = P(None, None, AXIS_CHIPS, AXIS_BANKS)


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def fleet_shape(geom: DrimGeometry, n_devices: int) -> Tuple[int, int]:
    """Largest (mc, mb) with mc | chips, mb | banks, mc*mb <= n_devices.

    Ties prefer the banks axis (mb), matching how DRIM-S scales out.
    """
    best = (1, 1)
    for mc in _divisors(geom.chips):
        for mb in _divisors(geom.banks):
            if mc * mb > n_devices:
                continue
            if (mc * mb, mb) > (best[0] * best[1], best[1]):
                best = (mc, mb)
    return best


def fleet_mesh(geom: DrimGeometry, *,
               devices: Optional[Sequence] = None) -> Mesh:
    """A (chips, banks) mesh for this geometry over available devices.

    It takes as many devices as `fleet_shape` can use — on one device a
    1x1 mesh, over which `shard_map` runs the plain path bit-for-bit.
    A caller that needs N devices checks `mesh.devices.size`.
    """
    if devices is None:
        devices = jax.devices()
    mc, mb = fleet_shape(geom, len(devices))
    return make_named_mesh((mc, mb), MESH_AXES, list(devices))


def _check_spec(spec: P, shape, mesh: Mesh) -> P:
    # sanitize_spec drops every named axis that does not exactly divide
    # its dim — a changed spec therefore means the mesh cannot hold this
    # array without padding, which we refuse (same rule as jit in/out
    # shardings).
    if sanitize_spec(spec, shape, mesh) != spec:
        raise ValueError(
            f"mesh {dict(mesh.shape)} does not divide array shape "
            f"{tuple(shape)} under spec {spec}")
    return spec


def shard_staged(staged: jax.Array, mesh: Mesh) -> jax.Array:
    """Place a staged wave payload shard-aligned on the fleet mesh."""
    _check_spec(STAGED_SPEC, staged.shape, mesh)
    return jax.device_put(staged, NamedSharding(mesh, STAGED_SPEC))


def shard_device(dev: DrimDevice, mesh: Mesh) -> DrimDevice:
    """Place a DrimDevice's state shard-aligned on the fleet mesh."""
    _check_spec(DEVICE_SPEC, dev.data.shape, mesh)
    return DrimDevice(
        data=jax.device_put(dev.data, NamedSharding(mesh, DEVICE_SPEC)),
        dcc=jax.device_put(dev.dcc, NamedSharding(mesh, DEVICE_SPEC)),
    )
