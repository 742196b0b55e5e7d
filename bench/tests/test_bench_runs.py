"""Whole runs of tiny cells on the CPU: the harness past its look for a
chip, every entry kind, the fault the timed path can have (an answer
altered where it is produced: the run must read `correct: false`), and
the controls."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _tiny  # noqa: E402


@pytest.fixture(autouse=True)
def _jax_started(monkeypatch):
    _tiny.keep_jax_as_it_is(monkeypatch)

CELLS = ["tiny.k8", "tiny.xnor2", "tiny.gemms"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    return root, _tiny.make_bench(root)


def _run(tiny, cell, capsys, seed=2**33 + 5, trace=0, seconds="0.3"):
    from bench import run
    root, bd = tiny
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   seconds, "--trace", str(trace)], require_tpu=False,
                  peaks=_tiny.PEAKS, root=root, bench_dir=bd)
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_cell_runs_correct(tiny, cell, capsys):
    rc, line, err = _run(tiny, cell, capsys)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    assert "setup_s" in line["metrics"]
    assert line["device"]["count"] == 1
    assert err.strip().splitlines()[-1].startswith("check ")


def test_tiny_traced_run_reads_per_layer_metrics(tiny, capsys):
    rc, line, _ = _run(tiny, "tiny.gemms", capsys, trace=1)
    assert rc == 0 and line["correct"] is True
    assert "tile_occupancy.bitlinear" in line["metrics"]
    assert "compile_s" in line["metrics"]
    assert "setup_s" not in line["metrics"]


def _flip_first_plane(monkeypatch):
    """A wrong answer where the fleet produces it: one bit of the first
    result plane of every `Lowered.run` inverted."""
    from repro.pim import compiler
    orig = compiler.Lowered.run

    def broken(self, *args, **kw):
        out = orig(self, *args, **kw)
        first = out[0] if isinstance(out, (tuple, list)) else out
        flipped = first.at[0].set(first[0] ^ np.uint32(1))
        if isinstance(out, (tuple, list)):
            return type(out)([flipped, *out[1:]])
        return flipped
    monkeypatch.setattr(compiler.Lowered, "run", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_fault_answer_altered_is_not_correct(tiny, cell, capsys,
                                             monkeypatch):
    _flip_first_plane(monkeypatch)
    rc, line, _ = _run(tiny, cell, capsys)
    assert rc == 0 and line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_not_correct(tiny, cell, capsys):
    """The control, at the cell's (tiny) size, fails a compared number
    that the program passes."""
    from bench import control, spec
    root, bd = tiny
    rc = control.main(["--workload", cell, "--seeds", "3,4",
                       "--seconds", "0.2"], require_tpu=False, root=root,
                      bench_dir=bd)
    assert rc == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [3, 4]
    bench = spec.Benchmark(root, bd)
    limits = _limits(bench, cell)
    for x in lines:
        assert all(x["program"][k] <= lim for k, lim in limits.items())
        assert any(x["control"][k] > lim for k, lim in limits.items())


def _limits(bench, cell):
    c = bench.cell(cell)
    kind = bench.traffic(c.traffic)["entry"]
    return {"wrong_entries": 0} if kind == "bitlinear_offload" \
        else {"wrong_words": 0}
