"""lower_s: host seconds the DRIM compiler passes took (`Compiled.lower`,
the `verify` pass included), from the program's "lower.us" counter.
Lowering happens in set-up: the line before the result counts the
lowerings inside the window.  A program without the counter reads
nothing."""


def read(r):
    from repro.runtime import telemetry
    us = telemetry.REGISTRY.snapshot()["counters"].get("lower.us")
    return None if us is None else us / 1e6
