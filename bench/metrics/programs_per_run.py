"""programs_per_run: compiled device programs `Lowered.run` launches per
call: the program's "run.programs" over "run.calls" counters, over
every call of the process before the read (set-up's warm-up calls run
the window's mix).  A program without the "run.programs" counter reads
nothing."""


def read(r):
    from repro.runtime import telemetry
    counters = telemetry.REGISTRY.snapshot()["counters"]
    calls = counters.get("run.calls", 0)
    if not calls or "run.programs" not in counters:
        return None
    return counters["run.programs"] / calls
