"""Telemetry layer: registry semantics, span tracing, Perfetto export.

Four contracts:

  * **Registry** — namespaced counters are identity-stable (the legacy
    module globals `ENCODE_CACHE_STATS` / `TRACE_COUNTS` /
    `LOWER_CACHE_STATS` ARE registry namespaces), `snapshot()` /
    `delta()` report exactly what changed, and `fresh()` /
    `fresh_encode_cache()` compose because both clear/restore the same
    Counter objects in place.

  * **Zero overhead when off** — the jaxpr of the wave executor is
    byte-identical with telemetry disarmed, armed in-process, and armed
    at import time in a fresh subprocess (`DRIM_TELEMETRY=1`): spans
    are host-side only and never touch a traced value.

  * **Bit-exactness when on** — arming changes no computed value, on
    clean partitioned runs and on chaos (queue-kill) runs alike.

  * **Perfetto schema** — `export_trace` writes well-formed Chrome
    trace JSON: complete spans carry ts/dur/pid/tid, compiler pass
    spans nest inside the `lower` span, and each recorded queue
    timeline renders exactly `n_queues` tracks with fence barriers,
    AAP streams, bus-contention stalls and chaos DEAD/requeue events.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

import drim
from drim import DrimGeometry, FaultModel, PASS_PIPELINE
from repro.pim import graph_ref_results
from repro.pim.bnn import bnn_dot_graph_carrysave
from repro.pim.compiler import LOWER_CACHE_STATS
from repro.pim.scheduler import (ENCODE_CACHE_STATS, TRACE_COUNTS,
                                 encoded_program, fresh_encode_cache,
                                 random_operands, run_waves, stage_rows)
from repro.runtime import telemetry
from repro.runtime.telemetry import MetricsRegistry

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

N_WORDS = 32


def _bnn_case(seed=7):
    graph, _ = bnn_dot_graph_carrysave(4)
    rng = np.random.default_rng(seed)
    feeds = {n: (np.zeros(N_WORDS, np.uint32) if n == "zero"
                 else rng.integers(0, 1 << 32, N_WORDS, dtype=np.uint32))
             for n in graph.input_names}
    return graph, feeds, graph_ref_results(graph, feeds)


def _assert_exact(outs, ref):
    for name in ref:
        np.testing.assert_array_equal(np.asarray(outs[name], np.uint32),
                                      np.asarray(ref[name], np.uint32))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_legacy_stats_globals_are_registry_namespaces():
    """The back-compat aliases are THE registry Counters, not copies."""
    assert ENCODE_CACHE_STATS is telemetry.REGISTRY.counters("encode_cache")
    assert TRACE_COUNTS is telemetry.REGISTRY.counters("wave_trace")
    assert LOWER_CACHE_STATS is telemetry.REGISTRY.counters("lower_cache")
    assert drim.obs is telemetry


def test_registry_snapshot_and_delta():
    r = MetricsRegistry()
    r.inc("cache.hits")
    r.gauge("fleet.alive", 8)
    r.observe("lat_s", 0.25)
    s0 = r.snapshot()
    assert s0["counters"] == {"cache.hits": 1}
    assert s0["gauges"] == {"fleet.alive": 8.0}
    assert s0["histograms"]["lat_s"]["count"] == 1
    assert s0["histograms"]["lat_s"]["p50"] == 0.25

    r.inc("cache.hits", 2)
    r.inc("cache.misses")
    r.observe("lat_s", 0.75)
    d = r.delta(s0)
    assert d["counters"] == {"cache.hits": 2, "cache.misses": 1}
    assert d["histograms"] == {"lat_s": {"count": 1}}
    # unqualified names land in the "default" namespace
    r.inc("plain")
    assert r.snapshot()["counters"]["default.plain"] == 1


def test_registry_fresh_restores_in_place():
    r = MetricsRegistry()
    c = r.counters("ns")
    c["k"] = 2
    r.gauge("g", 1.5)
    r.observe("h", 0.1)
    before = r.snapshot()
    with r.fresh() as rr:
        assert rr is r
        assert r.counters("ns") is c       # identity survives the scope
        assert not c                       # ...but it starts empty
        c["k"] += 5
        assert r.snapshot()["counters"] == {"ns.k": 5}
    assert r.counters("ns") is c
    assert r.snapshot() == before


def test_fresh_composes_with_fresh_encode_cache():
    """`telemetry.fresh()` around `fresh_encode_cache()` must not fight:
    both restore the SAME Counter in place, so unwinding either leaves
    the other's save intact."""
    pre = ENCODE_CACHE_STATS["hits"]
    ENCODE_CACHE_STATS["hits"] += 3
    with telemetry.fresh():
        assert ENCODE_CACHE_STATS["hits"] == 0
        with fresh_encode_cache() as stats:
            assert stats is ENCODE_CACHE_STATS
            encoded_program("xnor2")
            encoded_program("xnor2")
            assert stats["misses"] == 1 and stats["hits"] == 1
        assert ENCODE_CACHE_STATS["hits"] == 0   # inner scope unwound
    assert ENCODE_CACHE_STATS["hits"] == pre + 3  # outer scope unwound
    ENCODE_CACHE_STATS["hits"] -= 3               # leave process state


def test_module_snapshot_carries_tracer_status():
    snap = telemetry.snapshot()
    assert "armed" in snap and "trace_events" in snap
    assert set(("counters", "gauges", "histograms")) <= set(snap)


# ---------------------------------------------------------------------------
# Zero traced overhead when disarmed
# ---------------------------------------------------------------------------

def _wave_jaxpr(geom):
    low = drim.compile("xnor2", geom=geom).lower("resident")
    a, b = random_operands("xnor2", 64, seed=3)
    staged, _, _ = stage_rows([a, b], geom=geom)
    return str(jax.make_jaxpr(
        lambda s: run_waves(s, low.program, low.result_rows,
                            n_rows=low.n_rows, engine="resident"))(staged))


def test_jaxpr_identical_disarmed_vs_armed(small_geom):
    with telemetry.armed(False):
        off = _wave_jaxpr(small_geom)
    with telemetry.armed(True):
        on = _wave_jaxpr(small_geom)
    assert on == off


_SUBPROC_JAXPR = r"""
import jax
from repro.core import DrimGeometry
import drim
from repro.pim.scheduler import random_operands, run_waves, stage_rows

geom = DrimGeometry(chips=2, banks=4, subarrays_per_bank=8, row_bits=64)
low = drim.compile("xnor2", geom=geom).lower("resident")
a, b = random_operands("xnor2", 64, seed=3)
staged, _, _ = stage_rows([a, b], geom=geom)
print(jax.make_jaxpr(
    lambda s: run_waves(s, low.program, low.result_rows,
                        n_rows=low.n_rows, engine="resident"))(staged))
"""


def test_jaxpr_identical_to_import_armed_subprocess(small_geom):
    """A process armed from birth (`DRIM_TELEMETRY=1` before any repro
    import) traces the very same jaxpr a disarmed process does — the
    instrumentation never reaches XLA."""
    env = dict(os.environ)
    env["DRIM_TELEMETRY"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(_ROOT / "src"), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    out = subprocess.run([sys.executable, "-c", _SUBPROC_JAXPR],
                         capture_output=True, text=True, env=env,
                         cwd=str(_ROOT), check=True)
    with telemetry.armed(False):
        local = _wave_jaxpr(small_geom)
    assert out.stdout.strip() == local.strip()


def test_disarmed_pipeline_emits_no_events(small_geom):
    with telemetry.armed(False):
        telemetry.clear_trace()
        low = drim.compile("xnor2", geom=small_geom).lower("resident")
        a, b = random_operands("xnor2", 64, seed=5)
        low.run(a, b)
        assert telemetry.trace_events() == []


def test_span_is_shared_null_when_neither_sink_is_active():
    with telemetry.armed(False):
        assert telemetry.span("run") is telemetry.span("lower", aaps=3)


# ---------------------------------------------------------------------------
# Spans on the profiler clock: `drim.*` TraceAnnotations in a jax trace
# ---------------------------------------------------------------------------

# A fused run's phases: the feed checks and the one transfer, the one
# program (staging, waves and readback), the schedule.
RUN_SPANS = ["drim.run.feeds", "drim.run.dispatch", "drim.run.schedule"]


def _profiled_spans(tmp_path, block):
    """`block()` under a real `jax.profiler` trace; returns the host
    `drim.*` events as (name, parent name) in start order."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        block()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted(((e.start_ns, e.end_ns, e.name)
                    for plane in ProfileData.from_file(path[0]).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name.startswith("drim.")),
                   key=lambda x: (x[0], -x[1]))
    out, stack = [], []
    for s, t, name in spans:
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append((name, stack[-1][2] if stack else None))
        stack.append((s, t, name))
    return out


def test_run_spans_on_the_profiler_clock(tmp_path, small_geom):
    """A K=4 traced program: `lower` and its passes, then one `run`
    holding the three phases of a fused run, all named `drim.*` in the
    trace, armed or not."""
    from repro.pim.bnn import bitlinear_kernel
    rng = np.random.default_rng(3)
    planes = [rng.integers(0, 1 << 32, N_WORDS, dtype=np.uint32)
              for _ in range(8)]
    got = {}

    def block():
        low = drim.compile(bitlinear_kernel(4).trace(),
                           geom=small_geom).lower("resident")
        jax.block_until_ready(low.run(*planes))

    for on in (False, True):
        with telemetry.armed(on):
            got[on] = _profiled_spans(tmp_path / str(on), block)
    assert got[False] == got[True]
    assert got[False] == (
        [("drim.lower", None)]
        + [(f"drim.pass:{p.name}", "drim.lower") for p in PASS_PIPELINE]
        + [("drim.run", None)] + [(n, "drim.run") for n in RUN_SPANS])


@pytest.mark.parametrize("m,n,runs", [(2, 8, 1), (64, 64, 2)],
                         ids=["packed", "wave-sized"])
def test_offload_spans_on_the_profiler_clock(tmp_path, small_geom, m, n,
                                             runs):
    """`serve_bnn_matmul` over two K chunks: per run, pack, the run and
    its phases, unpack, all inside one `drim.offload`.  Two 16-lane
    chunks share one run; two 4096-lane chunks (a whole `small_geom`
    wave each) run one at a time."""
    from repro.pim.bnn import serve_bnn_matmul, serving_lowering
    rng = np.random.default_rng(4)
    a = rng.integers(0, 2, (m, 16), dtype=np.uint8)
    b = rng.integers(0, 2, (n, 16), dtype=np.uint8)
    serving_lowering(8, geom=small_geom)          # lowered before the trace
    got = _profiled_spans(
        tmp_path, lambda: serve_bnn_matmul(a, b, geom=small_geom, k_tile=8))
    run = ([("drim.offload.pack", "drim.offload"),
            ("drim.run", "drim.offload")]
           + [(name, "drim.run") for name in RUN_SPANS]
           + [("drim.offload.unpack", "drim.offload")])
    assert got == [("drim.offload", None)] + run * runs


def test_run_counters_book_host_planes(small_geom):
    """"run.h2d_bytes" counts the uint32 words of host planes only:
    device operands move nothing, and neither does a traced program's
    constant plane, made on the device; "lower.us" counts every
    lowering."""
    from repro.pim.bnn import bitlinear_kernel
    from repro.pim.compiler import LOWER_STATS, RUN_STATS
    rng = np.random.default_rng(5)
    host = [rng.integers(0, 1 << 32, N_WORDS, dtype=np.uint32)
            for _ in range(8)]
    dev = [jax.numpy.asarray(p) for p in host]
    with telemetry.fresh():
        xnor = drim.compile("xnor2", geom=small_geom).lower("resident")
        k4 = drim.compile(bitlinear_kernel(4).trace(),
                          geom=small_geom).lower("resident")
        assert LOWER_STATS["us"] > 0
        xnor.run(*dev[:2])
        assert dict(RUN_STATS) == {"calls": 1, "programs": 1,
                                   "h2d_transfers": 0}
        xnor.run(*host[:2])
        assert RUN_STATS["h2d_bytes"] == 2 * N_WORDS * 4
        k4.run(*dev)                           # the zero plane is not sent
        assert RUN_STATS["h2d_bytes"] == 2 * N_WORDS * 4
        k4.run(*host)                          # the 8 planes alone
        assert RUN_STATS["h2d_bytes"] == 10 * N_WORDS * 4
        assert RUN_STATS["calls"] == 4
        snap = telemetry.REGISTRY.snapshot()["counters"]
        assert snap["run.calls"] == 4 and snap["lower.us"] > 0


# ---------------------------------------------------------------------------
# Bit-exactness with telemetry armed
# ---------------------------------------------------------------------------

def test_partitioned_run_bit_exact_armed(small_geom):
    graph, feeds, ref = _bnn_case()
    low = drim.compile(graph, geom=small_geom).lower(partition=True,
                                                     n_queues=4)
    with telemetry.armed(False):
        _assert_exact(low.run(feeds), ref)
    with telemetry.armed(True):
        _assert_exact(low.run(feeds), ref)
        _assert_exact(low.run(feeds, faults=FaultModel(seed=0,
                                                       dead_queues=(2,))),
                      ref)


# ---------------------------------------------------------------------------
# Chaos report: compile/dispatch recovery split + death stages
# ---------------------------------------------------------------------------

def test_chaos_report_splits_compile_from_recovery(small_geom):
    graph, feeds, ref = _bnn_case(seed=11)
    low = drim.compile(graph, geom=small_geom).lower(partition=True,
                                                     n_queues=4)
    outs = low.run(feeds, faults=FaultModel(seed=0, dead_queues=(2,)))
    _assert_exact(outs, ref)
    rep = low.chaos_report
    assert rep is not None
    # the requeued segments are re-lowered AOT: that wall-clock is
    # compile time, reported separately from the dispatch recovery path
    assert rep.compile_s > 0.0
    assert rep.recovery_s >= 0.0
    assert dict(rep.death_stages) == {2: 0}
    # both sides land as registry gauges for the benchmark snapshot
    g = telemetry.REGISTRY.snapshot()["gauges"]
    assert g["chaos.compile_s"] == rep.compile_s
    assert g["chaos.recovery_s"] == rep.recovery_s
    assert telemetry.REGISTRY.counters("chaos")["requeued_segments"] > 0


# ---------------------------------------------------------------------------
# Perfetto trace schema
# ---------------------------------------------------------------------------

def test_perfetto_trace_schema(tmp_path, small_geom):
    graph, feeds, ref = _bnn_case(seed=13)
    n_queues = 4
    with telemetry.armed(True):
        telemetry.clear_trace()
        low = drim.compile(graph, geom=small_geom).lower(
            partition=True, n_queues=n_queues)
        _assert_exact(low.run(feeds), ref)
        _assert_exact(low.run(feeds, faults=FaultModel(seed=0,
                                                       dead_queues=(2,))),
                      ref)
        path = telemetry.export_trace(str(tmp_path / "trace.json"))
        telemetry.clear_trace()

    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert doc["otherData"]["exporter"] == "repro.runtime.telemetry"

    # -- every event is well-formed Chrome trace JSON
    for e in evs:
        assert e["ph"] in ("X", "i", "M"), e
        if e["ph"] == "X":
            assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
            assert e["dur"] >= 0.0
        if e["ph"] == "i":
            assert e["s"] in ("t", "p")

    names = [e["name"] for e in evs if e["ph"] == "X"]
    cats = {e.get("cat") for e in evs if e["ph"] != "M"}

    # -- compiler pass spans, one per pipeline pass, nested in `lower`
    assert {n for n in names if n.startswith("pass:")} == \
        {f"pass:{p.name}" for p in PASS_PIPELINE}
    lower = next(e for e in evs if e["ph"] == "X" and e["name"] == "lower")
    for e in evs:
        if e["ph"] == "X" and e["name"].startswith("pass:"):
            assert e["pid"] == lower["pid"] and e["tid"] == lower["tid"]
            assert e["ts"] >= lower["ts"] - 1e-6
            assert e["ts"] + e["dur"] <= lower["ts"] + lower["dur"] + 1e-6
    assert "run" in names

    # -- each recorded run renders its own sim process with one track
    #    per bank queue
    sim_pids = {e["pid"] for e in evs
                if e["ph"] == "M" and e["name"] == "process_name"
                and e["args"]["name"].startswith("drim-sim")}
    assert len(sim_pids) == 2          # clean run + chaos run
    for pid in sim_pids:
        tracks = [e for e in evs if e["ph"] == "M"
                  and e["name"] == "thread_name" and e["pid"] == pid]
        assert len(tracks) == n_queues
        assert [e["args"]["name"].startswith("queue ") for e in tracks] \
            == [True] * n_queues
        assert any(e.get("cat") == "fence" and e["pid"] == pid
                   for e in evs)
        assert any(e.get("cat") == "aap-stream" and e["pid"] == pid
                   for e in evs)

    # -- contention + chaos annotations made it onto the tracks
    assert "bus-contention" in cats
    dead = [e for e in evs if e.get("cat") == "chaos"
            and e["name"].endswith("DEAD")]
    assert len(dead) == 1 and dead[0]["args"]["queue"] == 2
    assert any(e.get("cat") == "chaos-requeue" for e in evs)


def test_export_trace_with_explicit_timeline(tmp_path, small_geom):
    """`queue_timeline_events` is usable standalone: render a uniform
    queued schedule and hand it to export via extra_events."""
    low = drim.compile("maj3", geom=small_geom).lower("queued", n_queues=2)
    sched = low.cost(small_geom.row_bits * small_geom.n_subarrays)
    evs = telemetry.queue_timeline_events(sched, label="maj3")
    tracks = [e for e in evs if e["ph"] == "M"
              and e["name"] == "thread_name"]
    assert len(tracks) == sched.n_queues
    assert any(e["ph"] == "X" and e.get("cat") == "aap-stream"
               for e in evs)
    assert any(e.get("cat") == "fence" for e in evs)
    path = telemetry.export_trace(str(tmp_path / "queued.json"),
                                  extra_events=evs)
    with open(path) as f:
        doc = json.load(f)
    assert any(e.get("args", {}).get("name", "").startswith("drim-sim")
               for e in doc["traceEvents"] if e["ph"] == "M")


# ---------------------------------------------------------------------------
# Benchmark records carry the shared "telemetry" key when armed
# ---------------------------------------------------------------------------

def test_bench_records_fold_registry_snapshot(tmp_path):
    from benchmarks import record
    record.clear("teltest")
    try:
        record.add("teltest", op="xnor2", wall_s=0.0)
        with telemetry.armed(False):
            paths = record.flush(str(tmp_path / "off"))
        with open(paths[0]) as f:
            assert "telemetry" not in json.load(f)
        with telemetry.armed(True):
            paths = record.flush(str(tmp_path / "on"))
        with open(paths[0]) as f:
            doc = json.load(f)
        assert doc["telemetry"]["armed"] is True
        assert "counters" in doc["telemetry"]
    finally:
        record.clear("teltest")
