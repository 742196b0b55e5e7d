"""The program's `drim.*` host spans beside the benchmark's: the
reduction checked by hand, the two readers of the program's counters,
and the tool that records a cell's window with the spans kept."""
import gzip
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _tiny  # noqa: E402

from bench import host_spans, tracing  # noqa: E402
from bench.tracing import Event  # noqa: E402

DEV = "/device:TPU:0"


@pytest.fixture(autouse=True)
def _jax_started(monkeypatch):
    _tiny.keep_jax_as_it_is(monkeypatch)


def _op(s, e):
    return Event(DEV, tracing.OPS_LINE, "%fusion.1 = u32[8] fusion(...)",
                 s, e)


def _host(name, s, e):
    return Event("/host:CPU", "python3", name, s, e)


# A window of 1000 ns.  The host is in `bench.call` 0-600, inside it in
# `drim.run` 50-550, which holds `drim.run.feeds` 60-250 and
# `drim.run.stage` 250-300; the device runs 300-400 and 650-700.
BASE = [_host("bench.window", 0, 1000), _host("bench.call", 0, 600),
        _host("bench.sync", 600, 700), _op(300, 400), _op(650, 700)]
NESTED = BASE + [_host("drim.run", 50, 550),
                 _host("drim.run.feeds", 60, 250),
                 _host("drim.run.stage", 250, 300)]


def test_self_time_is_duration_less_children():
    s = host_spans.summarize(NESTED)
    assert s.self_s["bench.call"] == pytest.approx(100e-9)
    assert s.self_s["drim.run"] == pytest.approx(260e-9)
    assert s.self_s["drim.run.feeds"] == pytest.approx(190e-9)
    assert s.durations["drim.run"] == [pytest.approx(500e-9)]
    b = s.breakdown()
    assert b["spans"]["drim.run.stage"]["count"] == 1
    assert b["spans"]["drim.run.stage"]["median_ms"] == pytest.approx(5e-5)


def test_idle_gaps_take_the_innermost_span():
    s = host_spans.summarize(NESTED)
    assert s.busy_s == pytest.approx(150e-9)
    assert s.idle_by_span == pytest.approx({
        "call": 100e-9,                 # 0-50 and 550-600
        "drim.run": 160e-9,             # 50-60 and 400-550
        "drim.run.feeds": 190e-9,       # 60-250
        "drim.run.stage": 50e-9,        # 250-300
        "sync": 50e-9,                  # 600-650
        tracing.OUTSIDE: 300e-9})       # 700-1000
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s)


def test_trace_without_program_spans_reduces_as_before():
    ours = host_spans.summarize(BASE)
    theirs = tracing.summarize(BASE)
    assert ours.idle_by_span == pytest.approx(theirs.idle_by_host)
    assert ours.busy_s == pytest.approx(theirs.busy_s)
    assert ours.window_s == pytest.approx(theirs.window_s)
    assert host_spans.summarize(BASE[1:]) is None


def _reader(name):
    from bench import spec
    return spec.Benchmark().reader(name)


def test_h2d_bytes_per_run_reads_the_counters_by_hand():
    from repro.pim.compiler import RUN_STATS
    from repro.runtime import telemetry
    read = _reader("h2d_bytes_per_run").read
    with telemetry.fresh():
        assert read(None) is None                    # no run yet
        RUN_STATS["calls"] += 2
        assert read(None) == 0.0                     # device operands
        # a K=128 chunk of [4, 768] x [3072, 768]: 257 planes of 1536 B
        RUN_STATS["h2d_bytes"] += 2 * 257 * 1536
        assert read(None) == 394_752.0


def test_lower_s_reads_the_counter_by_hand():
    from repro.pim.compiler import LOWER_STATS
    from repro.runtime import telemetry
    read = _reader("lower_s").read
    with telemetry.fresh():
        assert read(None) is None
        LOWER_STATS["us"] += 1_250_000
        assert read(None) == 1.25


@pytest.mark.parametrize("cell,spans", [
    ("tiny.k8", {"drim.run", "drim.run.feeds", "drim.run.dispatch"}),
    ("tiny.gemms", {"drim.offload", "drim.offload.pack",
                    "drim.offload.unpack", "drim.run"}),
])
def test_tool_records_program_spans_in_a_window(tmp_path, capsys, cell,
                                                spans):
    root = str(tmp_path)
    bd = _tiny.make_bench(root)
    fixture = tmp_path / "out" / "spans.json.gz"
    rc = host_spans.main(["--workload", cell, "--seed", str(2**33 + 1),
                          "--units", "2", "--fixture", str(fixture)],
                         require_tpu=False, root=root, bench_dir=bd)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["calls"] >= 2
    with gzip.open(fixture, "rt") as f:
        names = {e["name"] for e in json.load(f)["events"]}
    assert spans <= names and "bench.window" in names


def test_recorded_chip_trace_with_program_spans():
    """Three warm `drim-r.bnn-k128` calls traced on a TPU v5e with the
    program's spans (op names cut to 120 characters): the device idle
    time the benchmark's reduction puts under `call` falls into the
    `drim.run.*` phases, and the bench reduction reads as before."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "k128_spans_trace.json.gz")
    with gzip.open(path, "rt") as f:
        events = [Event(**e) for e in json.load(f)["events"]]
    ours = host_spans.summarize(events)
    theirs = tracing.summarize(events)
    assert ours.busy_s == pytest.approx(theirs.busy_s)
    assert theirs.module_calls["jit_body"] == 3
    assert 0.9 < theirs.idle_share < 0.95
    program = {n: s for n, s in ours.idle_by_span.items()
               if n.startswith("drim.")}
    # what the bench reduction calls `call` is the program's spans and
    # the little of `bench.call` outside them
    assert sum(program.values()) + ours.idle_by_span["call"] == \
        pytest.approx(theirs.idle_by_host["call"])
    phases = sum(s for n, s in program.items() if n.startswith("drim.run."))
    assert phases / theirs.idle_by_host["call"] > 0.95
    for name in ("drim.run", "drim.run.feeds", "drim.run.stage", "drim.run.dispatch",
                 "drim.run.readback", "drim.run.schedule"):
        assert len(ours.durations[name]) == 3
