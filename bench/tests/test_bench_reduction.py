"""The arithmetic of the benchmark, checked by hand on the CPU: trace
reduction, counted work, and rates and percentiles over a window."""
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _tiny  # noqa: E402,F401  (puts the checkout on sys.path)

from bench import tracing, window, work  # noqa: E402
from bench.tracing import Event  # noqa: E402

DEV = "/device:TPU:0"


def _op(name, s, e, plane=DEV):
    kind = name.split(".")[0]
    return Event(plane, tracing.OPS_LINE, f"%{name} = u32[8] {kind}(...)",
                 s, e)


def _mod(name, s, e, plane=DEV):
    return Event(plane, tracing.MODULES_LINE, f"{name}(123456)", s, e)


def _span(name, s, e):
    return Event("/host:CPU", "python", f"bench.{name}", s, e)


# A window of 1000 ns: a stager (100-300) and a wave program (300-400,
# ops overlapping) run while the host is in `call`; the device then
# idles 400-900 while the host syncs and 900-1000 outside any span.
RECORDED = [
    _span("window", 0, 1000),
    _span("call", 0, 350), _span("sync", 350, 900),
    _mod("jit_impl", 100, 300), _op("copy.1", 100, 300),
    _mod("jit_body", 300, 400), _op("fusion.4", 300, 380),
    _op("fusion.5", 350, 400),
    _op("before-window", -50, -10),
]


def test_trace_busy_union_and_idle_share():
    s = tracing.summarize(RECORDED)
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(300e-9)       # 100-400, overlap once
    assert s.idle_share == pytest.approx(0.7)
    assert s.n_devices == 1


def test_trace_per_program_and_per_op_device_time():
    s = tracing.summarize(RECORDED)
    assert s.modules == pytest.approx({"jit_impl": 200e-9,
                                       "jit_body": 100e-9})
    assert s.module_calls == {"jit_impl": 1, "jit_body": 1}
    assert s.ops["jit_body/fusion.4"] == pytest.approx(80e-9)
    assert s.ops["jit_impl/copy.1"] == pytest.approx(200e-9)
    assert not [n for n in s.ops if "before-window" in n]


def test_trace_idle_gaps_by_host_span():
    s = tracing.summarize(RECORDED)
    # 0-100 in `call`, 400-900 in `sync`, 900-1000 outside the spans
    assert s.idle_by_host["call"] == pytest.approx(100e-9)
    assert s.idle_by_host["sync"] == pytest.approx(500e-9)
    assert s.idle_by_host["outside the benchmark's spans"] == \
        pytest.approx(100e-9)
    b = s.breakdown(top=2)
    assert [n for n, _ in b["idle_gaps"]] == ["sync", "call"]
    assert b["device_ops"][0][0] == "jit_impl/copy.1"


def test_trace_busy_is_averaged_over_devices():
    two = RECORDED + [_op("x", 0, 1000, plane="/device:TPU:1")]
    s = tracing.summarize(two)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx((300e-9 + 1000e-9) / 2)


def _recorded_chip_trace():
    """Two warm `drim-r.bnn-k128` calls traced on a TPU v5e (op names
    cut to 120 characters), with the calls' least bytes."""
    import gzip
    import json
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "k128_trace.json.gz")
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return doc["min_bytes"], [Event(**e) for e in doc["events"]]


def test_recorded_chip_trace():
    min_bytes, events = _recorded_chip_trace()
    s = tracing.summarize(events)
    assert s.n_devices == 1
    # device busy: the union of op intervals, inside the window
    ops = [(e.start_ns, e.end_ns) for e in events
           if e.line == tracing.OPS_LINE]
    assert s.busy_s == pytest.approx(
        sum(t - u for u, t in tracing.union(ops)) / 1e9)
    assert 0.9 < s.idle_share < 0.95
    # two calls: two stager and two wave-runner programs
    assert s.module_calls["jit_impl"] == 2 == s.module_calls["jit_body"]
    assert s.modules["jit_impl"] > s.modules["jit_body"] > 0
    # the host was inside `call` for nearly all the device's idle time
    assert s.idle_by_host["call"] / (s.window_s - s.busy_s) > 0.95
    assert sum(s.idle_by_host.values()) == pytest.approx(
        s.window_s - s.busy_s)
    # the wave programs' roofline share, as the metric reads it
    share = min_bytes / 819e9 / s.modules["jit_body"]
    assert 0.3 < share < 0.5
    assert min_bytes == 2 * work.bnn_dot_min_bytes(512, 4096, 128)


def test_trace_without_window_or_device_reads_nothing():
    assert tracing.summarize(RECORDED[1:]) is None
    assert tracing.summarize([_span("window", 0, 10)]) is None


@pytest.mark.parametrize("cell,calls_bits", [
    ("drim-r.bnn-k128", 512 * 4096 * 128),
    ("drim-r.xnor2-bulk", 2**28),
    ("bitlinear gate/up", 4 * 3072 * 768),
    ("bitlinear down", 4 * 768 * 3072),
])
def test_bitops_by_hand(cell, calls_bits):
    got = {"drim-r.bnn-k128": work.bnn_dot_bitops(512, 4096, 128),
           "drim-r.xnor2-bulk": work.xnor2_bitops(2**28),
           "bitlinear gate/up": work.bnn_dot_bitops(4, 3072, 768),
           "bitlinear down": work.bnn_dot_bitops(4, 768, 3072)}[cell]
    assert got == calls_bits


def test_min_bytes_by_hand():
    # K=128: 256 operand planes and 8 counter planes of 256 KiB
    assert work.counter_planes(128) == 8
    assert work.bnn_dot_min_bytes(512, 4096, 128) == 264 * 256 * 1024
    # xnor2 at 2^28 bits: two operands and a result of 32 MiB
    assert work.xnor2_min_bytes(2**28) == 96 * 2**20
    # a ragged plane rounds up to whole words
    assert work.plane_bytes(33) == 8
    # one K=128 chunk of a [4,768] x [3072,768] GEMM: 12288 lanes
    assert work.bnn_dot_min_bytes(4, 3072, 128) == 264 * 12288 // 8
    assert work.occupied_tiles(4 * 3072, 256) == 48
    assert work.occupied_tiles(4 * 768, 256) == 12


def test_rate_ends_at_the_last_completion():
    calls = [window.Call(1.0, 2.0, {"bitops": 10}),
             window.Call(2.0, 4.0, {"bitops": 30})]
    w = window.Window(t0=1.0, calls=calls)
    assert w.seconds == 3.0
    assert w.rate("bitops") == pytest.approx(40 / 3.0)
    assert w.rate("tokens") == 0.0


def test_closed_loop_runs_whole_units_past_the_deadline():
    t = [0.0]

    def clock():
        return t[0]

    def unit():
        start = t[0]
        t[0] += 0.4
        return [window.Call(start, t[0], {"n": 1})]

    w = window.run_window(unit, 1.0, clock=clock)
    assert len(w.calls) == 3 and w.seconds == pytest.approx(1.2)


def test_percentiles():
    xs = [float(i) for i in range(1, 101)]
    assert window.percentile(xs, 95) == pytest.approx(95.05)
    assert window.percentile(xs, 50) == pytest.approx(50.5)
    assert window.percentile([3.0], 95) == 3.0
    assert math.isnan(window.percentile([], 95))
    w = window.Window(0.0, [window.Call(0, 1, {}, {"itl_s": [1, 2]}),
                            window.Call(1, 2, {}, {"itl_s": [3]})])
    assert w.values("itl_s") == [1, 2, 3]
