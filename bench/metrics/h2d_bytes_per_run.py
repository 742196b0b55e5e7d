"""h2d_bytes_per_run: bytes of host (numpy) operand and constant planes
that `Lowered.run` turns into device arrays, per call: the program's
"run.h2d_bytes" over "run.calls" counters, over every call of the
process before the read (set-up's warm-up calls run the window's mix).
A program without the counters reads nothing."""


def read(r):
    from repro.runtime import telemetry
    counters = telemetry.REGISTRY.snapshot()["counters"]
    calls = counters.get("run.calls", 0)
    if not calls:
        return None
    return counters.get("run.h2d_bytes", 0) / calls
