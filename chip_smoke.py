#!/usr/bin/env python3
"""Chip smoke run: drim-bnn serving and the DRIM fleet engines on a TPU.

    python chip_smoke.py                # one chip: fleet + serving phases
    python chip_smoke.py --four-chips   # only the fleet mesh over 4 chips

Run it from the root of a checkout; it imports the program from `src/`.
It drives the two main paths once, through the entry points a user
calls, and checks every result:

  fleet:<engine>   `drim.compile(...).lower(engine=...).run(...)` at the
                   DRIM-S geometry for resident, queued and pallas —
                   `xnor2` over two waves plus a ragged tail, and the
                   K=32 carry-save BNN dot over one full wave — bit-exact
                   against `kernels/ref.py` and numpy;
  serve:native     `launch.serve.run_serve` on drim-bnn's published
                   widths (12 layers, d_model 768, d_ff 3072, vocab
                   32768), batch 4, prompt 128 (the flash kernel's
                   block), temperature 0; the served prefill (flash
                   kernel on) in float32 at `highest` matmul precision
                   against a float32 `attention_impl="xla"` reference
                   (see LOGIT_TOL);
  serve:packed     the same from bit-packed BitLinear weights
                   (`--packed`, whose dense-vs-packed assert runs
                   inside); tokens equal the native stream;
  serve:<engine>   decode GEMMs routed through the DRIM `resident` and
                   compiled `pallas` engines: one warm K=128 chunk of
                   `pim.bnn.serve_bnn_matmul` at the FFN's [4, 3072]
                   (timed) and every GEMM of the served tokens bit-exact
                   against `xnor_gemm_ref`, and the tokens equal to the
                   native stream.  The DRIM engines decode layer by
                   layer eagerly; `serve.exact_rounding` makes that
                   round like the jitted native step.

Weights and operands are random, made from `--seed`.  Every phase prints
one JSON line (seconds, compile seconds, what was checked); the last
line is `{"ok": true, "device": {...}}`.  A failed check, or a backend
that is not a TPU, exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Prefill logits vs the float32 reference: max |served - ref| over
# max |ref|, for the served path run in float32 at `highest` matmul
# precision.  The bf16 path cannot be held to any tolerance: BitLinear
# binarizes activations, so bf16 rounding flips signs near zero and the
# logits of a 12-layer random-weight model decorrelate from a float32
# run (relative max error ~1 on the host CPU); its error is printed, not
# checked.  Float32 flash vs XLA attention stays near 1e-6; a wrong
# kernel lands near 1.
LOGIT_TOL = 1e-3

BATCH, PROMPT, GEN, DRIM_GEN = 4, 128, 8, 2


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.total = 0.0

        def on_event(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.total += duration
        jax.monitoring.register_event_duration_secs_listener(on_event)


def phase(name: str, clock: CompileClock, fn) -> dict:
    t0, c0 = time.perf_counter(), clock.total
    info = fn()
    line = {"phase": name,
            "seconds": time.perf_counter() - t0,
            "compile_s": clock.total - c0, **info}
    print(json.dumps(line), flush=True)
    return line


# ---------------------------------------------------------------------------
# Fleet phases
# ---------------------------------------------------------------------------

def fleet_operands(geom, seed: int):
    """xnor2 words over two waves plus a ragged tail, and one wave of
    [M, 32] x [N, 32] BNN sign bits (M * N lanes = the wave's bits)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n_words = 2 * geom.parallel_bits // 32 + 5
    a, b = (rng.integers(0, 2**32, n_words, dtype=np.uint32)
            for _ in range(2))
    n = math.gcd(geom.parallel_bits, 4096)           # DRIM-S: 2432 x 4096
    m = geom.parallel_bits // n
    a_bits = rng.integers(0, 2, (m, 32), dtype=np.uint8)
    b_bits = rng.integers(0, 2, (n, 32), dtype=np.uint8)
    return a, b, a_bits, b_bits


def fleet_oracles(a, b, a_bits, b_bits):
    """kernels/ref.py and numpy answers; they must agree with each other."""
    import numpy as np
    from repro.kernels import ref
    x_ref = np.asarray(ref.bitwise_ref("xnor", a, b))
    x_np = ~(a ^ b)
    check(np.array_equal(x_ref, x_np), "ref.bitwise_ref != numpy xnor")
    c_ref = np.asarray(ref.xnor_gemm_ref(pack_words(a_bits),
                                         pack_words(b_bits), 32))
    pm = lambda bits: bits.astype(np.float32) * 2 - 1
    c_np = (pm(a_bits) @ pm(b_bits).T).astype(np.int32)
    check(np.array_equal(c_ref, c_np), "ref.xnor_gemm_ref != numpy dot")
    return x_ref, c_ref


def run_fleet(engine: str, geom, operands, mesh=None):
    """xnor2 and the carry-save K=32 dot on one engine; returns the
    results and the two measured schedules."""
    import numpy as np
    import drim
    from repro.pim.bnn import bnn_dot_drim
    a, b, a_bits, b_bits = operands
    low = drim.compile("xnor2", geom=geom).lower(engine=engine, mesh=mesh)
    (x,) = low.run(a, b)
    x = np.asarray(x)
    c, sched = bnn_dot_drim(a_bits, b_bits, geom=geom,
                            accumulate="carrysave", engine=engine,
                            mesh=mesh)
    return x, c, low.schedule, sched


def fleet_phase(engine: str, geom, operands, oracles, mesh=None,
                single=None):
    """One engine's fleet run, checked against the oracles and, on a
    mesh, against the single-device results in `single`."""
    import numpy as np
    from repro.pim.scheduler import TRACE_COUNTS

    def go():
        built = TRACE_COUNTS["pallas_compiled"]
        x, c, s_x, s_c = run_fleet(engine, geom, operands, mesh)
        check(np.array_equal(x, oracles[0]), f"{engine}: xnor2 != oracle")
        check(np.array_equal(c, oracles[1]), f"{engine}: bnn dot != oracle")
        info = {"engine": engine, "geom": (
                    f"{geom.chips}x{geom.banks}x{geom.subarrays_per_bank}"
                    f"x{geom.row_bits}"),
                "xnor2": {"words": int(x.size), "tiles": s_x.tiles,
                          "waves": s_x.waves,
                          "row_ops": s_x.tiles * s_x.aaps_per_tile},
                "bnn_k32": {"shape": list(c.shape), "tiles": s_c.tiles,
                            "waves": s_c.waves,
                            "row_ops": s_c.tiles * s_c.aaps_per_tile},
                "check": "bit-exact vs kernels/ref.py and numpy"}
        if mesh is not None:
            info["mesh"] = {k: int(v) for k, v in mesh.shape.items()}
            x1, c1 = single
            check(np.array_equal(x, x1) and np.array_equal(c, c1),
                  f"{engine}: mesh result != single-device result")
            info["check"] += " and the single-device run"
        if engine == "pallas":
            # The wave bodies this run traced, by the kernel mode they
            # were built in: all compiled, none interpreted.
            bodies = {"compiled": TRACE_COUNTS["pallas_compiled"] - built,
                      "interpret": TRACE_COUNTS["pallas_interpret"]}
            check(bodies["compiled"] > 0 and bodies["interpret"] == 0,
                  f"pallas kernel not compiled: wave bodies {bodies}")
            info["interpret"] = False
            info["pallas_wave_bodies"] = bodies
        return info
    return go


# ---------------------------------------------------------------------------
# Serving phases
# ---------------------------------------------------------------------------

def serve_args(seed: int, *extra: str):
    from repro.launch import serve
    return serve.parse_args(
        ["--arch", "drim-bnn", "--batch", str(BATCH), "--prompt-len",
         str(PROMPT), "--temperature", "0", "--seed", str(seed),
         *extra])


def float32_prefill_logits(sink, **cfg_over):
    """The served prefill (`jax.jit(prefill)`) of the same params and
    prompts with float32 activations at `highest` matmul precision.
    The params stay as served (bfloat16 storage, cast per op), so
    per-channel BitLinear scales round the same way.  With
    `attention_impl="xla"` it is the plain reference."""
    import jax
    import numpy as np
    from repro.models import prefill
    cfg = sink["cfg"].replace(dtype="float32", **cfg_over)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, b: prefill(p, cfg, b))(
            sink["params"], sink["batch"])
    return np.asarray(logits, np.float32)


def serve_native(seed: int, tokens: dict):
    import numpy as np
    from repro.launch import serve
    from repro.models.attention import _use_flash

    def go():
        sink = {}
        gen, stats = serve.run_serve(serve_args(seed, "--gen", str(GEN)),
                                     sink)
        check(gen.shape == (BATCH, GEN), f"token shape {gen.shape}")
        served = np.asarray(sink["logits"], np.float32)
        check(np.isfinite(served).all(), "non-finite prefill logits")
        ref = float32_prefill_logits(sink, attention_impl="xla")
        # The served prefill in float32 differs from the reference only
        # in the attention kernel and the accumulation order, so it is
        # held to LOGIT_TOL.
        served32 = float32_prefill_logits(sink)
        err = float(np.abs(served32 - ref).max() / np.abs(ref).max())
        check(err <= LOGIT_TOL,
              f"float32 prefill logits off the reference: {err} > "
              f"{LOGIT_TOL}")
        tokens["native"] = gen
        cfg = sink["cfg"]
        return {"widths": {"layers": cfg.n_layers, "d_model": cfg.d_model,
                           "d_ff": cfg.d_ff, "vocab": cfg.vocab_size},
                "flash": bool(_use_flash(cfg, PROMPT, 0)),
                "f32_logit_rel_max_err": err, "tol": LOGIT_TOL,
                "bf16_logit_rel_max_err": float(
                    np.abs(served - ref).max() / np.abs(ref).max()),
                "prefill_s": stats["prefill_s"],
                "decode_compile_s": stats["compile_s"],
                "check": "finite bf16 logits; float32 logits within tol "
                         "of the reference"}
    return go


def serve_packed(seed: int, tokens: dict):
    import numpy as np
    from repro.launch import serve

    def go():
        gen, stats = serve.run_serve(
            serve_args(seed, "--gen", str(GEN), "--packed"))
        check(np.array_equal(gen, tokens["native"]),
              "--packed tokens differ from the native stream")
        return {"prefill_s": stats["prefill_s"],
                "decode_compile_s": stats["compile_s"],
                "check": "dense==packed logits (serve assert), tokens == "
                         "native"}
    return go


def chunk_seconds(engine: str, seed: int) -> float:
    """One warm K=128 `serve_bnn_matmul` chunk at the FFN's [4, 3072]
    lanes, checked against `xnor_gemm_ref`."""
    import numpy as np
    from repro.kernels import ref
    from repro.pim.bnn import serve_bnn_matmul
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 2, (BATCH, 128), dtype=np.uint8)
    b = rng.integers(0, 2, (3072, 128), dtype=np.uint8)
    serve_bnn_matmul(a, b, engine=engine)             # compile + warm
    t0 = time.perf_counter()
    got = serve_bnn_matmul(a, b, engine=engine)
    dt = time.perf_counter() - t0
    want = np.asarray(ref.xnor_gemm_ref(pack_words(a), pack_words(b), 128))
    check(np.array_equal(got, want), f"{engine}: chunk != xnor_gemm_ref")
    return dt


class GemmAudit:
    """While installed, every DRIM-routed BitLinear GEMM is checked bit
    for bit against `kernels/ref.py:xnor_gemm_ref`: it wraps
    `pim.bnn.serve_bnn_matmul`, which the serving layer looks up at
    each call."""

    def __enter__(self):
        import numpy as np
        import repro.pim.bnn as bnn
        from repro.kernels import ref
        self.n, self._bnn, self._orig = 0, bnn, bnn.serve_bnn_matmul

        def audited(a_bits, b_bits, **kw):
            got = self._orig(a_bits, b_bits, **kw)
            want = np.asarray(ref.xnor_gemm_ref(
                pack_words(a_bits), pack_words(b_bits), a_bits.shape[1]))
            check(np.array_equal(got, want),
                  f"DRIM GEMM {a_bits.shape} x {b_bits.shape} != "
                  "xnor_gemm_ref")
            self.n += 1
            return got
        bnn.serve_bnn_matmul = audited
        return self

    def __exit__(self, *exc):
        self._bnn.serve_bnn_matmul = self._orig


def pack_words(bits):
    """[R, K] sign bits (K a multiple of 32) -> [R, K/32] uint32 words."""
    import numpy as np
    return np.ascontiguousarray(np.packbits(
        np.asarray(bits, np.uint8), axis=1, bitorder="little")) \
        .view(np.uint32)


def serve_drim(engine: str, seed: int, tokens: dict):
    """Decode GEMMs through a DRIM engine.  Checked: one warm chunk and
    every GEMM of the served tokens against `xnor_gemm_ref`, and the
    tokens against the native stream."""
    import numpy as np
    from repro.launch import serve

    def go():
        chunk_s = chunk_seconds(engine, seed)
        with GemmAudit() as audit:
            gen, stats = serve.run_serve(
                serve_args(seed, "--gen", str(DRIM_GEN), "--engine",
                           engine))
        check(audit.n > 0, f"--engine {engine} routed no GEMM")
        check(np.array_equal(gen, tokens["native"][:, :DRIM_GEN]),
              f"--engine {engine} tokens differ from the native stream")
        return {"engine": engine, "chunk_k128_s": chunk_s,
                "decode_warmup_s": stats["compile_s"],
                "gemms_checked": audit.n,
                "check": "chunk and every routed GEMM == xnor_gemm_ref; "
                         "tokens == native"}
    return go


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet mesh phase over 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no program at {SRC}; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.serve import exact_rounding
    from repro.runtime.compile_cache import enable_compile_cache
    exact_rounding()
    enable_compile_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.core import DRIM_S
    clock = CompileClock()
    operands = fleet_operands(DRIM_S, args.seed)
    oracles = fleet_oracles(*operands)
    try:
        if args.four_chips:
            from repro.pim.mesh import fleet_mesh
            mesh = fleet_mesh(DRIM_S, devices=devices[:4])
            check(mesh.devices.size == 4,
                  f"fleet mesh has {mesh.devices.size} devices, not 4")
            for engine in ("resident", "queued"):
                single = run_fleet(engine, DRIM_S, operands)[:2]
                phase(f"fleet4:{engine}", clock,
                      fleet_phase(engine, DRIM_S, operands, oracles,
                                  mesh, single))
        else:
            for engine in ("resident", "queued", "pallas"):
                phase(f"fleet:{engine}", clock,
                      fleet_phase(engine, DRIM_S, operands, oracles))
            tokens = {}
            phase("serve:native", clock, serve_native(args.seed, tokens))
            phase("serve:packed", clock, serve_packed(args.seed, tokens))
            for engine in ("resident", "pallas"):
                phase(f"serve:{engine}", clock,
                      serve_drim(engine, args.seed, tokens))
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
