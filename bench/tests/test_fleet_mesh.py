"""The `fleet_mesh` entry and the fleet mesh's metric: a tiny mesh cell
run end to end on as many CPU devices as there are (one in the suite's
own process; four in the subprocess at the end, which forces them), its
files found by name, and its reader.  On one device the mesh is 1x1."""
import json
import os
import subprocess
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _tiny  # noqa: E402

from bench import spec  # noqa: E402

CHIPS = 4 if len(jax.devices()) >= 4 else 1
CELL = "tiny.xnor2-mesh"
CONFIGS = {"tiny-drim4": {"source": "https://arxiv.org/abs/1904.05782",
                          "reduced": ["geometry"],
                          "geometry": {"chips": 1, "banks": 4,
                                       "subarrays_per_bank": 8,
                                       "row_bits": 64},
                          "reference": "fleet"}}
# 5.125 waves of 2,048 lanes: 328 words, 82 a device on four
TRAFFIC = {"tiny-xnor2-mesh": {"entry": "fleet_mesh", "program": "xnor2",
                               "n_bits": 10496, "engine": None, "pool": 2,
                               "check": 3}}


@pytest.fixture(autouse=True)
def _jax_started(monkeypatch):
    _tiny.keep_jax_as_it_is(monkeypatch)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    bd = _tiny.make_bench(root, cells=[(CELL, "tiny-drim4",
                                        "tiny-xnor2-mesh")],
                          configs=CONFIGS, traffic=TRAFFIC)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["workloads"][0]["chips"] = CHIPS
    with open(path, "w") as f:
        json.dump(doc, f)
    return root, bd


def _run(tiny, capsys, seed=2**33 + 11, trace=0):
    from bench import run
    root, bd = tiny
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.3", "--trace", str(trace)], require_tpu=False,
                  peaks=_tiny.PEAKS, root=root, bench_dir=bd)
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


def test_mesh_cell_runs_correct(tiny, capsys):
    rc, line, _ = _run(tiny, capsys)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["wrong_words"] == {"value": 0, "limit": 0}
    assert line["device"]["count"] == CHIPS
    assert {"bitops_per_s", "setup_s"} <= set(line["metrics"])


def test_mesh_cell_traced_reads_cross_device_bytes(tiny, capsys):
    """The counters are the process's: start them empty for this run."""
    from repro.runtime import telemetry
    with telemetry.REGISTRY.fresh():
        rc, line, _ = _run(tiny, capsys, trace=1)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["h2d_bytes_per_run"]["value"] == 0
    if CHIPS > 1:
        assert line["metrics"]["xchip_bytes_per_run"]["value"] == 0
    else:
        assert "xchip_bytes_per_run" not in line["metrics"]


def test_mesh_cell_altered_answer_is_not_correct(tiny, capsys, monkeypatch):
    from repro.pim import compiler
    orig = compiler.Lowered.run

    def broken(self, *args, **kw):
        (first,) = orig(self, *args, **kw)
        return (first.at[-1].set(first[-1] ^ 1),)
    monkeypatch.setattr(compiler.Lowered, "run", broken)
    rc, line, _ = _run(tiny, capsys)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["wrong_words"]["value"] > 0


def test_mesh_cell_control_is_not_correct(tiny, capsys):
    from bench import control
    root, bd = tiny
    rc = control.main(["--workload", CELL, "--seeds", "3,2147483659",
                       "--seconds", "0.2"], require_tpu=False, root=root,
                      bench_dir=bd)
    assert rc == 0
    for x in map(json.loads, capsys.readouterr().out.splitlines()):
        assert x["program"]["wrong_words"] == 0
        assert x["control"]["wrong_words"] > 0


def test_mesh_cell_operands_take_the_program_layout():
    """The entry builds the layout itself (so that the parent commit
    runs the cell); it is the one the program exports."""
    import drim
    from repro.core import DrimGeometry
    from repro.pim.mesh import words_sharding
    mod = spec.load_module(_tiny.BENCH, "entries", "fleet_mesh")
    geom = DrimGeometry(**CONFIGS["tiny-drim4"]["geometry"])
    mesh = drim.fleet_mesh(geom, devices=jax.devices()[:CHIPS])
    assert mesh.devices.size == CHIPS
    assert mod.layout(mesh) == words_sharding(mesh) == drim.words_sharding(
        mesh)


def test_drim_s4_files_found_by_name():
    bench = spec.Benchmark()
    cell = bench.cell("drim-s4.xnor2-bulk")
    assert cell.chips == 4 and cell.config == "drim-s4"
    config = bench.config(cell.config)
    assert config["geometry"] == {"chips": 1, "banks": 256,
                                  "subarrays_per_bank": 152,
                                  "row_bits": 256}
    assert config["reduced"] == []
    traffic = bench.traffic(cell.traffic)
    assert traffic["entry"] == "fleet_mesh" and traffic["n_bits"] == 2**29
    assert hasattr(bench.entry(traffic["entry"]), "Entry")
    bench.reference(config["reference"])
    names = {m.name for m in bench.metrics_for(cell.name, "per_layer")}
    assert {"xchip_bytes_per_run", "wave_roofline", "device_idle",
            "h2d_bytes_per_run"} <= names
    assert "xchip_bytes_per_run" not in {
        m.name for m in bench.metrics_for("drim-r.xnor2-bulk", "per_layer")}


def test_xchip_reader_needs_the_counter():
    from repro.runtime import telemetry
    mod = spec.load_module(_tiny.BENCH, "metrics", "xchip_bytes_per_run")
    with telemetry.REGISTRY.fresh():
        assert mod.read(None) is None
        telemetry.REGISTRY.counters("run")["calls"] += 4
        assert mod.read(None) is None
        telemetry.REGISTRY.counters("run")["xchip_bytes"] += 96
        assert mod.read(None) == 24


@pytest.mark.skipif(not os.environ.get("FLEET_MESH_DEVICES"),
                    reason="only in the forced-device subprocess")
def test_forced_devices_are_seen():
    assert CHIPS == len(jax.devices()) == int(
        os.environ["FLEET_MESH_DEVICES"])


def test_four_device_mesh_cell_subprocess():
    """This file's tests again, in a fresh interpreter whose CPU backend
    has four devices: the cell on a real (1, 4) mesh."""
    if len(jax.devices()) >= 4:
        pytest.skip("already running with forced devices")
    env = dict(os.environ, JAX_PLATFORMS="cpu", FLEET_MESH_DEVICES="4",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", os.path.abspath(__file__), "-k",
         "not subprocess"], env=env, cwd=_tiny.ROOT, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "skipped" not in proc.stdout.splitlines()[-1]
