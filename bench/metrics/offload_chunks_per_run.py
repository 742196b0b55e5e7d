"""offload_chunks_per_run: K chunks `serve_bnn_matmul` served per
`Lowered.run` it issued: the program's "offload.chunks" over
"offload.runs" counters, over every GEMM of the process before the read
(set-up's warm-up runs the window's mix).  A program without the
counters reads nothing."""


def read(r):
    from repro.runtime import telemetry
    counters = telemetry.REGISTRY.snapshot()["counters"]
    runs = counters.get("offload.runs", 0)
    if not runs:
        return None
    return counters.get("offload.chunks", 0) / runs
