"""The harness is driven by data: a configuration, a cell and a metric
are added as new files and entries, and found by name; a checkout that
is not on a TPU runs nothing."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _tiny  # noqa: E402


@pytest.fixture(autouse=True)
def _jax_started(monkeypatch):
    _tiny.keep_jax_as_it_is(monkeypatch)

from bench import spec  # noqa: E402


def test_real_benchmark_names_only_files_that_exist():
    bench = spec.Benchmark()
    for cell in bench.cells.values():
        config = bench.config(cell.config)
        traffic = bench.traffic(cell.traffic)
        bench.entry(traffic["entry"])
        bench.reference(config["reference"])
        assert cell.chips in (1, 4)
    for m in bench.metrics:
        assert hasattr(bench.reader(m.name), "read")
    doc = bench.doc
    for c in doc["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        assert set(c["reduced"]) <= set(bench.config(c["name"]))


def test_every_cell_reports_setup_and_another_end_to_end_metric():
    bench = spec.Benchmark()
    for name in bench.cells:
        e2e = {m.name for m in bench.metrics_for(name, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert bench.metrics_for(name, "per_layer")


def test_new_files_are_found_by_name(tmp_path):
    """A later change adds a configuration, a traffic mix, a cell and a
    per-layer metric as new files and entries, and edits none."""
    root = str(tmp_path)
    bd = _tiny.make_bench(root)
    metrics = tmp_path / "extra_metrics"
    metrics.mkdir()
    real = os.path.join(_tiny.BENCH, "metrics")
    for f in os.listdir(real):
        os.symlink(os.path.join(real, f), metrics / f)
    os.unlink(os.path.join(bd, "metrics"))
    os.symlink(metrics, os.path.join(bd, "metrics"))
    (metrics / "calls_per_s.py").write_text(
        "def read(r):\n    return len(r.window.calls) / r.window.seconds\n")
    (tmp_path / "bench" / "configs" / "other-drim.json").write_text(
        json.dumps(dict(_tiny.CONFIGS["tiny-drim"],
                        geometry=dict(_tiny.TINY_GEOM, banks=4))))
    (tmp_path / "bench" / "traffic" / "other-xnor2.json").write_text(
        json.dumps(dict(_tiny.TRAFFIC["tiny-xnor2"], n_bits=8192)))
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "other.xnor2", "config": "other-drim",
                             "traffic": "other-xnor2", "chips": 1,
                             "why": "added as files"})
    doc["per_layer"].append({"name": "calls_per_s", "unit": "1/s",
                             "better": "higher", "source": "host_clock",
                             "layer": "device", "moves": "bitops_per_s",
                             "workloads": ["other.xnor2"]})
    for m in doc["end_to_end"]:
        if m["name"] == "bitops_per_s":
            m["workloads"].append("other.xnor2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    bench = spec.Benchmark(root, bd)
    assert bench.config("other-drim")["geometry"]["banks"] == 4
    assert "calls_per_s" in {m.name for m in
                             bench.metrics_for("other.xnor2", "per_layer")}
    assert "calls_per_s" not in {m.name for m in
                                 bench.metrics_for("tiny.k8", "per_layer")}

    from bench import run
    rc = run.main(["--workload", "other.xnor2", "--seed", "9",
                   "--seconds", "0.2", "--trace", "1"], require_tpu=False,
                  peaks=_tiny.PEAKS, root=root, bench_dir=bd)
    assert rc == 0


def test_unknown_names_are_refused(tmp_path, capsys):
    root = str(tmp_path)
    bd = _tiny.make_bench(root)
    bench = spec.Benchmark(root, bd)
    with pytest.raises(spec.SpecError):
        bench.cell("no.such-cell")
    with pytest.raises(spec.SpecError):
        bench.reader("no_such_metric")
    from bench import run
    assert run.main(["--workload", "no.such-cell", "--seed", "1",
                     "--seconds", "1"], root=root, bench_dir=bd) == 2


def test_unknown_device_kind_has_no_peaks():
    from bench import device
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("cpu")


def _run_cli(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def test_cpu_backend_exits_nonzero_without_a_result():
    p = _run_cli(["--workload", "drim-r.bnn-k128", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], cwd=_tiny.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [x for x in p.stdout.splitlines() if x.startswith("{")]


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no result."""
    import shutil
    shutil.copy(os.path.join(_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(["--workload", "drim-r.bnn-k128", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], cwd=str(tmp_path))
    assert p.returncode != 0
    assert not [x for x in p.stdout.splitlines() if x.startswith("{")]
