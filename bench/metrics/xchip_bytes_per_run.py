"""xchip_bytes_per_run: bytes of operand and result words that a run
over a fleet mesh delivers to a device other than the one that holds or
simulates them, per call: the program's "run.xchip_bytes" over
"run.calls" counters, over every call of the process before the read
(set-up's warm-up calls run the window's mix).  A program without the
counter reads nothing."""


def read(r):
    from repro.runtime import telemetry
    counters = telemetry.REGISTRY.snapshot()["counters"]
    calls = counters.get("run.calls", 0)
    if not calls or "run.xchip_bytes" not in counters:
        return None
    return counters["run.xchip_bytes"] / calls
