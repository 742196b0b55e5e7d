"""`drim`: the SIMDRAM-style end-to-end front-end for the DRIM stack.

Write a kernel as a plain Python function over symbolic bit-planes,
trace it with `drim.jit`, and run one staged pipeline over every
engine, mesh, queue count, and partition strategy:

    import drim

    @drim.jit
    def kernel(a, b, c):
        x = drim.xnor(a, b)                 # single-cycle DRA
        s, carry = drim.full_add(x, c, b)   # Table-2 adder slice
        return {"s": s, "carry": carry}

    out = kernel(A, B, C)                   # trace->compile->lower->run
    low = drim.compile(kernel).lower(engine="queued", n_queues=4)
    print(low.cost(1 << 20).latency_s, low.verdict(1 << 20).winner)

This package is the stable import surface; the implementation lives in
`repro.pim.frontend` (tracing), `repro.pim.compiler` (pipeline + engine
registry) and `repro.pim.offload` (the unified placement Verdict).

Observability rides along as `drim.obs` (= `repro.runtime.telemetry`):
`drim.obs.armed()` turns on span tracing + per-queue Perfetto
timelines, `drim.obs.snapshot()` reads the metrics registry, and
`drim.obs.export_trace(path)` dumps a chrome://tracing-compatible file.
"""
from repro.core import DRIM_R, DRIM_S, DrimGeometry, FaultModel
from repro.pim.compiler import (ENGINE_REGISTRY, PARTITIONERS,
                                PASS_PIPELINE, Compiled, EccReport, Engine,
                                EngineRegistry, Lowered, compile, engines,
                                get_engine, lower)
from repro.pim.frontend import (BitTensor, JittedFunction, TraceError,
                                TracedProgram, copy, csa_reduce, full_add,
                                jit, maj, popcount, select, xnor)
from repro.pim.graph import BulkGraph
from repro.pim.harden import HARDEN_SCHEMES, harden_graph
from repro.pim.mesh import WORDS_SPEC, fleet_mesh, words_sharding
from repro.pim.offload import (TpuCost, Verdict, VerdictRow, build_verdict,
                               tpu_cost)
from repro.pim.queue import ChaosReport
from repro.pim import verify
from repro.pim.verify import VerifyError, VerifyReport, verify_lowered
from repro.runtime import telemetry as obs

__all__ = [
    "BitTensor", "BulkGraph", "ChaosReport", "Compiled", "DRIM_R",
    "DRIM_S", "DrimGeometry", "ENGINE_REGISTRY", "EccReport", "Engine",
    "EngineRegistry", "FaultModel", "HARDEN_SCHEMES", "JittedFunction",
    "Lowered", "PARTITIONERS", "PASS_PIPELINE", "TpuCost", "TraceError",
    "TracedProgram", "Verdict", "VerdictRow", "VerifyError",
    "VerifyReport", "WORDS_SPEC", "build_verdict", "compile", "copy",
    "csa_reduce", "engines", "fleet_mesh", "full_add", "get_engine",
    "harden_graph", "jit", "lower", "maj", "obs", "popcount", "select",
    "tpu_cost", "verify", "verify_lowered", "words_sharding", "xnor",
]
