"""Sharded fleet execution: shard_map path bit-exact vs the vmap path.

The (chips, banks) fleet mesh is pure data parallelism — no collectives
— so the sharded executor must agree with the single-device vmap path
bit for bit on everything: single programs (`device_run_program_sharded`
vs `device_run_program`), bulk ops (`execute(mesh=...)` vs the PR 2
"baseline" engine), and whole fused DAGs (the random-DAG differential
suite from `tests/test_graph.py` re-run through the sharded executor).

On a bare CPU runner the fleet mesh degrades to 1x1 (the fallback that
keeps tier-1 green); a subprocess test re-runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so real 1xN /
2x4 partitioning is exercised even locally, and the CI job sets the
same flag to run it in-process.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from test_graph import GEOMS, random_graph

from repro.core import DrimGeometry, encode
from repro.core.device import (device_load_rows, device_run_program,
                               device_run_program_sharded, make_device)
from repro.pim import (OP_ARITY, build_program, execute, execute_graph,
                       expected_results, fleet_mesh, fleet_shape,
                       graph_ref_results, random_operands, shard_device,
                       shard_staged, stage_rows)

MULTI_DEVICE = len(jax.devices()) >= 8


def test_fleet_shape_divides_geometry():
    """The mesh shape always divides (chips, banks) exactly and never
    exceeds the device count; one device means the 1x1 fallback."""
    geom = DrimGeometry(chips=2, banks=4, subarrays_per_bank=8)
    for n_dev in (1, 2, 3, 4, 6, 8, 16):
        mc, mb = fleet_shape(geom, n_dev)
        assert geom.chips % mc == 0 and geom.banks % mb == 0
        assert mc * mb <= n_dev
    assert fleet_shape(geom, 1) == (1, 1)
    assert fleet_shape(geom, 8) == (2, 4)
    # ties prefer the banks axis (how DRIM-S scales out)
    assert fleet_shape(geom, 4) == (1, 4)
    # no dividing shape fits 2 devices for 1 chip x 3 banks -> fallback
    assert fleet_shape(DrimGeometry(chips=1, banks=3), 2) == (1, 1)
    assert fleet_shape(DrimGeometry(chips=1, banks=3), 3) == (1, 3)


def test_fleet_mesh_axes_and_fallback(small_geom):
    mesh = fleet_mesh(small_geom)
    assert mesh.axis_names == ("chips", "banks")
    assert small_geom.chips % mesh.shape["chips"] == 0
    assert small_geom.banks % mesh.shape["banks"] == 0
    if len(jax.devices()) == 1:
        assert dict(mesh.shape) == {"chips": 1, "banks": 1}


@pytest.mark.skipif(not MULTI_DEVICE, reason="needs >= 8 devices")
def test_fleet_mesh_uses_all_forced_devices(small_geom):
    mesh = fleet_mesh(small_geom)
    assert dict(mesh.shape) == {"chips": 2, "banks": 4}


def test_device_run_program_sharded_matches_vmap(small_geom):
    """Same encoded stream, full post-state equality (data AND dcc)."""
    rng = np.random.default_rng(0xD1)
    dev = make_device(small_geom, n_data=8)
    rows = rng.integers(0, 1 << 32,
                        (dev.chips, dev.banks, dev.subarrays, 3, dev.words),
                        dtype=np.uint32)
    dev = device_load_rows(dev, 0, rows)
    mesh = fleet_mesh(small_geom)
    dev = shard_device(dev, mesh)
    for op in ("xnor2", "add"):
        enc = encode(build_program(op))
        ref = device_run_program(dev, enc)
        out = device_run_program_sharded(dev, enc, mesh)
        np.testing.assert_array_equal(np.asarray(out.data),
                                      np.asarray(ref.data))
        np.testing.assert_array_equal(np.asarray(out.dcc),
                                      np.asarray(ref.dcc))


def test_execute_sharded_bit_exact_all_ops(small_geom):
    """Every bulk op through the sharded path == oracle == baseline
    engine, including a ragged multi-wave payload."""
    mesh = fleet_mesh(small_geom)
    row_w = small_geom.row_bits // 32
    n_words = 2 * small_geom.n_subarrays * row_w + 3
    for op in sorted(OP_ARITY):
        args = random_operands(op, n_words, seed=sum(map(ord, op)))
        res_m, sched_m = execute(op, *args, geom=small_geom, mesh=mesh)
        res_b, sched_b = execute(op, *args, geom=small_geom,
                                 engine="baseline")
        assert sched_m == sched_b
        for got, base, want in zip(res_m, res_b, expected_results(op, args)):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
            np.testing.assert_array_equal(np.asarray(base),
                                          np.asarray(want))


def test_shard_staged_alignment(small_geom):
    """stage_rows(mesh=...) places tiles shard-aligned — same layout the
    wave runner's in_specs declare, so no resharding on dispatch."""
    from jax.sharding import NamedSharding

    from repro.pim import STAGED_SPEC
    mesh = fleet_mesh(small_geom)
    a, b = random_operands("xnor2", 64, seed=5)
    staged, _, _ = stage_rows([a, b], geom=small_geom, mesh=mesh)
    assert staged.sharding == NamedSharding(mesh, STAGED_SPEC)
    again = shard_staged(staged, mesh)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(staged))


def test_shard_device_rejects_indivisible():
    geom = DrimGeometry(chips=1, banks=3, subarrays_per_bank=2, row_bits=32)
    dev = make_device(geom, n_data=4)
    mesh = fleet_mesh(DrimGeometry(chips=2, banks=4, subarrays_per_bank=2))
    if dict(mesh.shape) == {"chips": 1, "banks": 1}:
        pytest.skip("single device: every shape divides a 1x1 mesh")
    with pytest.raises(ValueError):
        shard_device(dev, mesh)


def test_random_dag_sharded_differential(n_examples):
    """ISSUE acceptance: the random-DAG suite from tests/test_graph.py
    through the sharded executor, bit-exact vs the vmap path AND the
    numpy oracle, with identical measured schedules."""
    for seed in range(n_examples):
        rng = np.random.default_rng(0x5EED + seed)
        graph = random_graph(rng)
        geom = GEOMS[int(rng.integers(0, len(GEOMS)))]
        mesh = fleet_mesh(geom)
        row_w = geom.row_bits // 32
        max_words = 2 * geom.n_subarrays * row_w + 3
        n_words = int(rng.integers(1, max_words + 1))
        feeds = {name: rng.integers(0, 1 << 32, n_words, dtype=np.uint32)
                 for name in graph.input_names}

        sharded, sched_s = execute_graph(graph, feeds, geom=geom, mesh=mesh)
        vmap_path, sched_v = execute_graph(graph, feeds, geom=geom,
                                           engine="baseline")
        ref = graph_ref_results(graph, feeds)
        assert set(sharded) == set(vmap_path) == set(ref)
        for name in ref:
            np.testing.assert_array_equal(np.asarray(sharded[name]),
                                          ref[name])
            np.testing.assert_array_equal(np.asarray(vmap_path[name]),
                                          ref[name])
        assert sched_s == sched_v


def test_forced_8device_cpu_mesh_subprocess(fast_mode):
    """Run this module's differential tests on a REAL 1xN partitioning:
    a fresh interpreter with XLA_FLAGS forcing 8 CPU devices (the flag
    must be set before jax initializes, hence the subprocess).  The CI
    job runs the same configuration in-process."""
    if MULTI_DEVICE:
        pytest.skip("already running with forced multi-device platform")
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        JAX_PLATFORMS="cpu",
        REPRO_FAST_TESTS="1",
    )
    cmd = [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
           os.path.abspath(__file__), "-k", "not subprocess"]
    proc = subprocess.run(
        cmd, env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, (
        f"forced-8-device suite failed:\n{proc.stdout}\n{proc.stderr}")
    assert "passed" in proc.stdout


# ---------------------------------------------------------------------------
# The block layout of a mesh run's operand and result words
# ---------------------------------------------------------------------------

COLLECTIVES = ("all-gather", "all-to-all", "collective-permute",
               "all-reduce", "reduce-scatter")


def _layout_words(geom, mesh):
    """Word counts that divide by the device count: two whole waves, a
    ragged third wave, and less than a wave; then one that does not."""
    d = mesh.devices.size
    wave = geom.n_subarrays * geom.row_bits // 32
    return [2 * wave, 2 * wave + 2 * d, 5 * d, 2 * wave + 3]


@pytest.mark.parametrize("devices,n_words,want", [
    (None, 8, "one"), (1, 8, "one"), (4, 8, "blocks"), (4, 10, "whole"),
    (2, 10, "blocks")])
def test_layout_decided_once_per_run(devices, n_words, want):
    """The one layout decision (`pim.mesh.words_layout`), which reads
    only the mesh's device count: one device runs the single-device
    path, several take blocks where the words split evenly and whole
    planes where they do not."""
    from types import SimpleNamespace

    from repro.pim.mesh import words_layout
    mesh = (None if devices is None
            else SimpleNamespace(devices=np.empty((1, devices))))
    assert words_layout(mesh, n_words) == want


def test_layout_words_mesh_is_the_resident_engines(small_geom):
    """Only the resident engine lays a run's words over its mesh; the
    queued engine stages them row-major from wherever they are."""
    import drim
    mesh = fleet_mesh(small_geom)
    compiled = drim.compile("xnor2", geom=small_geom)
    assert compiled.lower(mesh=mesh).words_mesh is mesh
    assert compiled.lower(engine="queued", mesh=mesh).words_mesh is None
    assert compiled.lower().words_mesh is None


def _placed(planes, mesh):
    from repro.pim.mesh import words_layout, words_sharding
    if words_layout(mesh, planes[0].size) != "blocks":
        return list(planes)
    return [jax.device_put(p, words_sharding(mesh)) for p in planes]


def _check_layout(out, mesh, n_words):
    """Blocked results carry the layout, and each device's shard holds
    its own block and no more."""
    from repro.pim.mesh import words_layout, words_sharding
    if words_layout(mesh, n_words) != "blocks":
        return
    per = n_words // mesh.devices.size
    host = np.asarray(out)
    assert out.sharding.is_equivalent_to(words_sharding(mesh), 1)
    for shard in out.addressable_shards:
        assert shard.data.shape == (per,)
        lo = shard.index[0].start or 0
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      host[lo:lo + per])


@pytest.mark.parametrize("case", range(4))
def test_layout_xnor2_block_in_block_out(small_geom, case):
    """xnor2 over the mesh: block-sharded in, block-sharded out,
    bit-identical to the single-device run and to numpy, with the
    unsharded schedule."""
    import drim
    mesh = fleet_mesh(small_geom)
    n_words = _layout_words(small_geom, mesh)[case]
    a, b = random_operands("xnor2", n_words, seed=case)
    low = drim.compile("xnor2", geom=small_geom).lower(mesh=mesh)
    one = drim.compile("xnor2", geom=small_geom).lower()
    (got,) = low.run(*_placed([a, b], mesh))
    (want,) = one.run(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), ~(a ^ b))
    assert low.schedule == one.schedule
    _check_layout(got, mesh, n_words)


@pytest.mark.parametrize("case", range(4))
def test_layout_bitlinear_graph_block_in_block_out(small_geom, case):
    """The graph path (`bitlinear_kernel(32)`, 64 operand planes and a
    constant zero plane) keeps the layout and the unsharded schedule."""
    import drim
    from repro.pim.bnn import bitlinear_kernel
    traced = bitlinear_kernel(32).trace()
    mesh = fleet_mesh(small_geom)
    n_words = _layout_words(small_geom, mesh)[case]
    rng = np.random.default_rng(0xB17 + case)
    planes = [rng.integers(0, 1 << 32, n_words, dtype=np.uint32)
              for _ in range(64)]
    low = drim.compile(traced, geom=small_geom).lower(mesh=mesh)
    one = drim.compile(traced, geom=small_geom).lower()
    got = jax.tree.leaves(low.run(*_placed(planes, mesh)))
    want = jax.tree.leaves(one.run(*planes))
    ref = graph_ref_results(traced.graph, traced.feeds_for(planes))
    assert len(got) == len(want) == len(ref) == 6
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        _check_layout(g, mesh, n_words)
    assert sorted(np.asarray(g).tobytes() for g in got) == sorted(
        np.asarray(r).tobytes() for r in ref.values())
    assert low.schedule == one.schedule


def test_layout_stager_and_readback_have_no_collectives(small_geom):
    """The compiled stager, wave program and readback of a blocked mesh
    run move no word between devices."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.pim.mesh import STAGED_SPEC, _unstager, words_sharding
    from repro.pim.scheduler import _stager, _wave_runner
    mesh = fleet_mesh(small_geom)
    d = mesh.devices.size
    n_words = _layout_words(small_geom, mesh)[1]
    row_w = small_geom.row_bits // 32
    waves = -(-n_words // (small_geom.n_subarrays * row_w))
    lead = (waves, small_geom.chips, small_geom.banks,
            small_geom.subarrays_per_bank, row_w)
    words = jax.ShapeDtypeStruct((n_words,), jnp.uint32,
                                 sharding=words_sharding(mesh))
    staged = jax.ShapeDtypeStruct((waves, 2) + lead[1:], jnp.uint32,
                                  sharding=NamedSharding(mesh, STAGED_SPEC))
    outs = jax.ShapeDtypeStruct((waves, 1) + lead[1:], jnp.uint32,
                                sharding=NamedSharding(mesh, STAGED_SPEC))
    texts = [
        _stager(2, n_words, lead, mesh, True).lower((words, words)).compile(),
        _wave_runner("resident", tuple(build_program("xnor2")), (2,), 16,
                     mesh, False).lower(staged).compile(),
        _unstager((0,), n_words // d, mesh).lower(outs).compile(),
    ]
    for compiled in texts:
        hlo = compiled.as_text()
        assert not [c for c in COLLECTIVES if c in hlo]


@pytest.mark.parametrize("source", ["layout", "host", "one_device",
                                    "replicated", "uneven"])
def test_layout_cross_device_bytes(small_geom, source):
    """run.xchip_bytes: nothing for operands in the layout, for host
    operands (host-to-device bytes instead) and for replicated ones; the
    other devices' blocks of a one-device operand; every operand and
    result whole on every other device where the words do not split
    evenly."""
    import drim
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.pim.compiler import RUN_STATS
    mesh = fleet_mesh(small_geom)
    d = mesh.devices.size
    words = _layout_words(small_geom, mesh)
    n_words = words[3] if source == "uneven" else words[1]
    a, b = random_operands("xnor2", n_words, seed=7)
    nbytes = 4 * n_words
    if source == "layout":
        args, moved = _placed([a, b], mesh), 0
    elif source == "one_device":
        args = [jax.device_put(x, jax.devices()[0]) for x in (a, b)]
        moved = 2 * nbytes * (d - 1) // d
    elif source == "replicated":
        whole = NamedSharding(mesh, PartitionSpec())
        args, moved = [jax.device_put(x, whole) for x in (a, b)], 0
    else:
        args = [a, b]
        moved = 3 * nbytes * (d - 1) if source == "uneven" else 0
    h2d = 4 * sum(x.size for x in args if isinstance(x, np.ndarray))
    low = drim.compile("xnor2", geom=small_geom).lower(mesh=mesh)
    x0, h0 = RUN_STATS["xchip_bytes"], RUN_STATS["h2d_bytes"]
    (got,) = low.run(*args)
    np.testing.assert_array_equal(np.asarray(got), ~(a ^ b))
    if d == 1:
        moved = 0
    assert RUN_STATS["xchip_bytes"] - x0 == moved
    assert RUN_STATS["h2d_bytes"] - h0 == h2d


def test_layout_one_by_one_mesh_is_the_plain_run(small_geom):
    """A 1x1 mesh takes the plain path: same words, same schedule, the
    same single-device result layout, nothing counted as crossing."""
    import drim
    from repro.pim.compiler import RUN_STATS
    mesh = fleet_mesh(small_geom, devices=jax.devices()[:1])
    assert mesh.devices.size == 1
    n_words = 2 * small_geom.n_subarrays * small_geom.row_bits // 32 + 3
    a, b = random_operands("xnor2", n_words, seed=11)
    low = drim.compile("xnor2", geom=small_geom).lower(mesh=mesh)
    plain = drim.compile("xnor2", geom=small_geom).lower()
    x0 = RUN_STATS["xchip_bytes"]
    (got,) = low.run(a, b)
    (want,) = plain.run(a, b)
    assert RUN_STATS["xchip_bytes"] == x0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert low.schedule == plain.schedule
    assert got.sharding.device_set == want.sharding.device_set


def test_layout_run_span_names_its_devices(small_geom):
    """The `run` span says over how many devices the run went; placing
    host operands on a blocked mesh is its own `run.place` span."""
    import drim
    from repro.pim.mesh import words_layout
    from repro.runtime import telemetry
    mesh = fleet_mesh(small_geom)
    n_words = _layout_words(small_geom, mesh)[0]
    a, b = random_operands("xnor2", n_words, seed=3)
    low = drim.compile("xnor2", geom=small_geom).lower(mesh=mesh)
    n0 = len(telemetry.trace_events())
    with telemetry.armed():
        low.run(a, b)
    events = telemetry.trace_events()[n0:]
    runs = [e for e in events if e.get("name") == "run"]
    assert runs and runs[0]["args"]["devices"] == mesh.devices.size
    placed = [e for e in events if e.get("name") == "run.place"]
    assert len(placed) == int(words_layout(mesh, n_words) == "blocks")


def test_forced_4device_layout_subprocess():
    """The layout tests on a (1, 4) mesh, the `drim-s4` cell's: a fresh
    interpreter whose CPU backend has four devices."""
    if len(jax.devices()) >= 4:
        pytest.skip("already running with forced multi-device platform")
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_FAST_TESTS="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
           "-p", "no:xdist", os.path.abspath(__file__), "-k",
           "layout and not subprocess"]
    proc = subprocess.run(
        cmd, env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (
        f"forced-4-device layout suite failed:\n{proc.stdout}\n"
        f"{proc.stderr}")
    assert "passed" in proc.stdout and "skipped" not in proc.stdout


@pytest.mark.parametrize("i", range(4))
def test_layout_fused_run_matches_step_by_step(small_geom, i):
    """A mesh run is one program: words in the layout in, each device
    staging, running and trimming its own block, results in the layout
    out.  Bit-exact with the step-by-step path (`stage_rows` ->
    `run_waves` -> `read_blocks`, or `read_planes` off the blocks
    layout) with the same schedule; one program, nothing across
    devices, nothing sent from the host."""
    import drim
    import jax.numpy as jnp
    from repro.pim.compiler import RUN_STATS
    from repro.pim.mesh import read_blocks, words_layout
    from repro.pim.scheduler import read_planes, run_waves
    mesh = fleet_mesh(small_geom)
    n_words = _layout_words(small_geom, mesh)[i]
    layout = words_layout(mesh, n_words)
    a, b = random_operands("xnor2", n_words, seed=40 + i)
    args = _placed([jnp.asarray(a), jnp.asarray(b)], mesh)
    low = drim.compile("xnor2", geom=small_geom).lower(mesh=mesh)
    snap = dict(RUN_STATS)
    (got,) = low.run(*args)
    delta = {k: RUN_STATS[k] - snap.get(k, 0) for k in RUN_STATS}
    staged, tiles, waves = stage_rows(
        list(args), geom=small_geom,
        mesh=mesh if layout != "one" else None,
        blocks=layout == "blocks")
    outs = run_waves(staged, low.program, low.result_rows,
                     n_rows=low.n_rows,
                     mesh=mesh if layout != "one" else None)
    (want,) = (read_blocks(outs, [0], n_words, mesh) if layout == "blocks"
               else read_planes(outs, [0], n_words))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), ~(a ^ b))
    _check_layout(got, mesh, n_words)
    assert low.schedule == low.engine.lift_op(low, n_words * 32, tiles,
                                              waves)
    assert delta["programs"] == 1 and delta["h2d_transfers"] == 0
    if layout != "whole":
        assert delta.get("xchip_bytes", 0) == 0
