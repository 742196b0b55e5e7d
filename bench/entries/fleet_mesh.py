"""Entry: a DRIM program over the fleet mesh, the device's banks split
over the cell's chips, as a user runs it,
`drim.compile(src, geom=...).lower(mesh=drim.fleet_mesh(geom, devices=...))
.run(*planes)`, with operand planes made on the mesh from the seed, each
chip making its own contiguous block of words.

Traffic parameters as for `fleet` (`bench/traffic/<name>.json`), with
`program` "xnor2" only.  The layout is built here from `jax.sharding`
and the program's mesh axis names, so a program that predates its own
export of the layout runs the cell too.
"""
from __future__ import annotations

from bench import seeds, work
from bench.entries import fleet
from bench.tracing import span


def layout(mesh):
    """Flat words in contiguous blocks over the mesh's devices in
    (chips, banks) order."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core.device import MESH_AXES
    return NamedSharding(mesh, PartitionSpec(tuple(MESH_AXES)))


class Entry(fleet.Entry):
    def __init__(self, ctx):
        import jax
        import drim
        from repro.core import DrimGeometry
        self.ctx = ctx
        t = ctx.traffic
        self.geom = DrimGeometry(**ctx.config["geometry"])
        self.program = t["program"]
        if self.program != "xnor2":
            raise ValueError(f"fleet_mesh runs xnor2, not {self.program!r}")
        self.ref = ctx.bench.reference(ctx.config["reference"])
        self.lanes = int(t["n_bits"])
        self.bitops = work.xnor2_bitops(self.lanes)
        self.min_bytes = work.xnor2_min_bytes(self.lanes)
        self.mesh = drim.fleet_mesh(
            self.geom, devices=jax.devices()[:ctx.cell.chips])
        if self.mesh.devices.size != ctx.cell.chips:
            raise ValueError(f"the fleet mesh spans {self.mesh.devices.size}"
                             f" devices, the cell {ctx.cell.chips} chips")
        self.sharding = layout(self.mesh)
        self._make = jax.jit(fleet._xnor2_operands, static_argnames="words",
                             out_shardings=(self.sharding, self.sharding))
        with span("lower"):
            self.low = drim.compile("xnor2", geom=self.geom).lower(
                engine=t.get("engine"), mesh=self.mesh)
        self.pool = [self._operands(i) for i in range(int(t["pool"]))]
        jax.block_until_ready([p["planes"] for p in self.pool])
        self.kept = seeds.Reservoir(int(t["check"]), ctx.seed, stream=1)
        self.n_calls = 0
        for i in range(len(self.pool)):       # warm up: compile, stage
            jax.block_until_ready(self.low.run(*self.pool[i]["planes"]))

    def _operands(self, i: int):
        a, b = self._make(seeds.jax_key(self.ctx.seed, i),
                          -(-self.lanes // 32))
        return {"planes": (a, b)}
