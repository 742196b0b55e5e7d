"""The chip: JAX start-up, the device's identity and peaks, memory peak,
and seconds spent compiling."""
from __future__ import annotations

import json
import os
from typing import Dict

from bench.spec import BENCH_DIR, ROOT

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def start_jax() -> str:
    """Start JAX the way the program serves: every op rounded to its
    type (`launch.serve.exact_rounding`, before a backend starts) and
    the persistent compilation cache at one fixed place in the checkout
    (or where `JAX_COMPILATION_CACHE_DIR` says), keeping every program
    however fast it compiled.  Returns the cache directory."""
    from repro.launch.serve import exact_rounding
    exact_rounding()
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def devices(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's backend is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def describe(devs) -> Dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def peaks(kind: str) -> Dict[str, float]:
    """Published peaks of one chip of `kind`; an unknown kind is an
    error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[kind]


class CompileClock:
    """XLA compilations and JAX traces, from JAX's own monitoring events:
    a cache hit compiles nothing and books no compile event."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.compile_s = 0.0
        self.compiles = 0
        self.traces = 0

        def on_event(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.compile_s += duration
                self.compiles += 1
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "traces": self.traces}
