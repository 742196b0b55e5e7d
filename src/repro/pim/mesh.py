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

Operand and result words of a mesh run (`WORDS_SPEC`): a flat uint32
word array split into equal contiguous blocks over the mesh's devices
in (chips, banks) order, device (ic, ib) holding block ic * mb + ib.
Each device simulates the lanes of its own block: on a mesh the
lane-to-slot mapping is block-major, each block laid over that
device's [waves, chips/mc, banks/mb, subarrays, row_words] slots, so
staging, the wave program and readback move no word between devices.
The wave count is the unsharded one, ceil(n_words / wave_words):
ceil(ceil(n / D) / (L / D)) == ceil(n / L).  Make operands in this
layout where they are produced, e.g. a jitted generator with
`out_shardings=words_sharding(mesh)`; `Lowered.run` places operands
in any other layout (host numpy, one device, replicated) once per call
and counts the bytes that reach a device other than their own in
"run.xchip_bytes".  JAX shards only evenly, so a word count that does
not divide by the device count cannot take this layout: such a run
keeps the replicated path (operands and results whole on every
device), and counts what that path moves.  `words_layout` makes that
choice once per run, for placement, staging and readback together.

Construction reuses `launch.mesh.make_named_mesh`, and every placement
is validated with `runtime.sharding.sanitize_spec` (the same exact-
divisibility rule jit in/out shardings enforce).
"""
from __future__ import annotations

import functools
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
# Flat operand and result words of a mesh run: contiguous blocks in
# (chips, banks) device order.
WORDS_SPEC = P((AXIS_CHIPS, AXIS_BANKS))


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
    A caller that needs N devices checks `mesh.devices.size`.  A run
    over a mesh of several devices takes and returns its words in the
    `words_sharding(mesh)` layout.
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


def words_sharding(mesh: Mesh) -> NamedSharding:
    """The layout of a mesh run's flat operand and result words."""
    return NamedSharding(mesh, WORDS_SPEC)


def n_devices(mesh: Optional[Mesh]) -> int:
    return 1 if mesh is None else mesh.devices.size


def words_layout(mesh: Optional[Mesh], n_words: int) -> str:
    """The one layout decision of a run of `n_words` words per plane
    over `mesh`, made once per run and handed to placement, staging and
    readback alike: "one" with no mesh or a mesh of one device (the
    single-device path); "blocks" over several devices when the words
    split into equal blocks (`words_sharding`, lanes block-major);
    "whole" otherwise (every operand and result on every device)."""
    d = n_devices(mesh)
    if d == 1:
        return "one"
    return "blocks" if n_words % d == 0 else "whole"


def missing_bytes(x, target: NamedSharding) -> int:
    """Bytes of the flat words `x` that `target` puts on a device that
    does not hold them yet.  Host arrays hold none on any device: their
    words are host-to-device bytes, not cross-device ones."""
    if not isinstance(x, jax.Array):
        return 0
    n = x.shape[0]
    held = x.sharding.devices_indices_map((n,))
    total = 0
    for dev, idx in target.devices_indices_map((n,)).items():
        lo, hi, _ = idx[0].indices(n)
        have = 0
        if dev in held:
            a, b, _ = held[dev][0].indices(n)
            have = max(0, min(hi, b) - max(lo, a))
        total += (hi - lo - have) * x.dtype.itemsize
    return total


def shard_staged(staged: jax.Array, mesh: Mesh) -> jax.Array:
    """Place a staged wave payload shard-aligned on the fleet mesh (the
    slots of every device: what the mesh stager builds from operand
    words in the `words_sharding` layout)."""
    _check_spec(STAGED_SPEC, staged.shape, mesh)
    return jax.device_put(staged, NamedSharding(mesh, STAGED_SPEC))


def shard_device(dev: DrimDevice, mesh: Mesh) -> DrimDevice:
    """Place a DrimDevice's state shard-aligned on the fleet mesh."""
    _check_spec(DEVICE_SPEC, dev.data.shape, mesh)
    return DrimDevice(
        data=jax.device_put(dev.data, NamedSharding(mesh, DEVICE_SPEC)),
        dcc=jax.device_put(dev.dcc, NamedSharding(mesh, DEVICE_SPEC)),
    )


@functools.lru_cache(maxsize=256)
def _unstager(cols: Tuple[int, ...], per: int, mesh: Mesh):
    from repro.pim.scheduler import read_planes

    def unstage(outs: jax.Array) -> Tuple[jax.Array, ...]:
        return read_planes(outs, cols, per)

    return jax.jit(jax.shard_map(
        unstage, mesh=mesh, in_specs=(STAGED_SPEC,),
        out_specs=tuple(WORDS_SPEC for _ in cols), check_vma=False))


def read_blocks(outs: jax.Array, cols: Sequence[int], n_words: int,
                mesh: Mesh) -> Tuple[jax.Array, ...]:
    """Result planes `cols` of a blocked mesh run's readback
    [waves, rows, chips, banks, subarrays, row_words]: each device's
    own slab flattened and trimmed to its block, returned in the
    `words_sharding` layout with no collective."""
    from repro.pim.scheduler import RUN_STATS
    RUN_STATS["programs"] += 1
    return _unstager(tuple(cols), n_words // n_devices(mesh), mesh)(outs)
