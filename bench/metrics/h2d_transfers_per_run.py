"""h2d_transfers_per_run: host-to-device transfers of operand planes
`Lowered.run` makes per call: the program's "run.h2d_transfers" over
"run.calls" counters, over every call of the process before the read
(set-up's warm-up calls run the window's mix).  A program without the
"run.h2d_transfers" counter reads nothing."""


def read(r):
    from repro.runtime import telemetry
    counters = telemetry.REGISTRY.snapshot()["counters"]
    calls = counters.get("run.calls", 0)
    if not calls or "run.h2d_transfers" not in counters:
        return None
    return counters["run.h2d_transfers"] / calls
