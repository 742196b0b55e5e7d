"""Batched serving driver: prefill once, decode N tokens, report tok/s.

Serving path features:
  * static-shape KV caches sized to --ctx (sequence-sharded over `model`)
  * greedy or temperature sampling
  * --engine {tpu,resident,baseline,queued,pallas}: how BitLinear
    decode matmuls execute — "tpu" is the native XLA path (the
    EngineRegistry roofline comparator's contender); any DRIM device
    engine routes each decode GEMM through the drim.jit carry-save
    pipeline on the simulated fleet (traced once per layer shape,
    lowered once per engine signature), decoding eagerly per layer
  * --packed: BitLinear weights bit-packed in HBM (32x smaller weight
    reads; kernels/xnor_popcount on TPU, host unpack on DRIM engines),
    with a bit-exactness assert vs the dense STE path at temperature 0
  * --microbench: the prefill / insert / generate split (the maxtext
    experimental_decode_microbenchmark pattern) with compile time
    reported separately per stage
  * --continuous N: N staggered requests through the continuous-
    batching wave scheduler (launch.batching.WaveBatcher)

Timing: the first decode step runs UNTIMED as warm-up and is reported
as `compile_s`, so `decode_tok_per_s` and the p50/p99 step latencies
are steady-state.

CPU-scale example:
  PYTHONPATH=src python -m repro.launch.serve --arch drim-bnn \
      --smoke-config --batch 4 --prompt-len 32 --gen 16 --engine resident
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.batching import WaveBatcher, make_decode_fn
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import (decode_step, empty_caches, init_params, prefill)
from repro.models.layers import pack_bitlinear
from repro.runtime import telemetry
from repro.runtime.compile_cache import enable_compile_cache

# config-geometry override flags -> ModelConfig fields (0 = keep)
_CFG_OVERRIDES = (("layers", "n_layers"), ("d_model", "d_model"),
                  ("d_ff", "d_ff"), ("heads", "n_heads"),
                  ("kv_heads", "n_kv_heads"), ("d_head", "d_head"),
                  ("vocab", "vocab_size"))


# XLA may keep a bf16 intermediate at higher precision inside a fusion
# ("excess precision"), so on a TPU the rounding of the jitted native
# decode step depends on how XLA fused it, while the eager step the DRIM
# engines take rounds after every op; the binarized model turns that
# into different tokens.  Serving rounds every op, so that the engine
# never changes the tokens at temperature 0.
EXACT_ROUNDING_FLAG = "--xla_allow_excess_precision=false"


def exact_rounding() -> None:
    """Have XLA round every op to its dtype in every program this
    process compiles.  XLA reads `XLA_FLAGS` once, when JAX first
    starts a backend, so this must run before that."""
    flags = os.environ.get("XLA_FLAGS", "")
    if EXACT_ROUNDING_FLAG in flags.split():
        return
    from jax._src.xla_bridge import backends_are_initialized
    if backends_are_initialized():
        raise RuntimeError(
            "exact_rounding() called after JAX started a backend; set "
            f"XLA_FLAGS={EXACT_ROUNDING_FLAG} or call it first")
    os.environ["XLA_FLAGS"] = f"{flags} {EXACT_ROUNDING_FLAG}".strip()


def parse_args(argv=None) -> argparse.Namespace:
    from repro.pim.compiler import engines
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="drim-bnn")
    ap.add_argument("--smoke-config", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--ctx", type=int, default=0,
                    help="cache length (default prompt+gen)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="tpu", choices=sorted(engines()),
                    help="BitLinear decode matmul backend: 'tpu' = "
                    "native XLA; DRIM engines route decode GEMMs "
                    "through the drim.jit compile->lower->run pipeline")
    ap.add_argument("--n-queues", type=int, default=None,
                    help="queue count for --engine queued")
    ap.add_argument("--packed", action="store_true",
                    help="serve from bit-packed BitLinear weights "
                    "(pack_bitlinear offline conversion)")
    ap.add_argument("--microbench", action="store_true",
                    help="prefill/insert/generate microbenchmark split")
    ap.add_argument("--continuous", type=int, default=0, metavar="N",
                    help="run N requests through the continuous-"
                    "batching wave scheduler instead of one static "
                    "batch")
    ap.add_argument("--arrive-every", type=int, default=1,
                    help="waves between request arrivals in "
                    "--continuous mode (0 = all arrive at wave 0)")
    ap.add_argument("--resilient", action="store_true",
                    help="wrap the decode fn in retry-with-backoff and "
                    "a TPU-engine fallback; incidents land in the "
                    "stats record instead of killing the server")
    ap.add_argument("--telemetry", action="store_true",
                    help="arm the observability layer: span tracing "
                    "through lowering/decode, registry snapshot folded "
                    "into the stats record under 'telemetry'")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the Chrome-trace/Perfetto JSON here "
                    "after the run (implies --telemetry)")
    for flag, _field in _CFG_OVERRIDES:
        ap.add_argument(f"--{flag.replace('_', '-')}", type=int,
                        default=0, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def build_cfg(args):
    cfg = (get_smoke_config(args.arch) if args.smoke_config
           else get_config(args.arch))
    over = {field: getattr(args, flag) for flag, field in _CFG_OVERRIDES
            if getattr(args, flag)}
    if over:
        cfg = cfg.replace(**over)
    return cfg.replace(remat=False, param_dtype="bfloat16")


def splice_caches(caches, pre_caches):
    """Right-size prefill caches into the ctx-length decode caches.

    Every leaf must either match exactly or fit inside the decode cache
    at the same rank.  Anything else used to silently KEEP THE EMPTY
    cache (serving garbage attention state); now it raises, naming the
    offending cache path.
    """
    from jax.tree_util import keystr, tree_map_with_path

    def splice(path, full, pre):
        if full.shape == pre.shape:
            return pre.astype(full.dtype)
        if full.ndim == pre.ndim and all(
                p <= f for p, f in zip(pre.shape, full.shape)):
            return jax.lax.dynamic_update_slice(
                full, pre.astype(full.dtype), (0,) * full.ndim)
        raise ValueError(
            f"cache splice mismatch at {keystr(path)}: prefill leaf "
            f"{pre.shape} cannot splice into decode cache {full.shape}")

    return tree_map_with_path(splice, caches, pre_caches)


def pack_model_params(params):
    """Offline --packed conversion: every BitLinear param dict (marker:
    'bkernel') in the pytree becomes its bit-packed serving form; works
    on scan-stacked [L, d_in, d_out] leaves."""
    def walk(p):
        if isinstance(p, dict):
            if "bkernel" in p:
                return pack_bitlinear(p)
            return {k: walk(v) for k, v in p.items()}
        return p
    return walk(params)


def _assert_packed_bit_exact(cfg, dense_params, packed_params, tok,
                             caches, pos, ctx_len) -> None:
    """--packed at temperature 0 must reproduce the dense STE path
    bitwise: the packed XNOR-popcount dot and the bf16 STE matmul both
    produce the same exact integer, so logits — and served tokens —
    must match."""
    step = jax.jit(lambda p, t, c, q: decode_step(p, cfg, t, c, q,
                                                  ctx_len)[0])
    lg_dense = np.asarray(step(dense_params, tok, caches, pos),
                          np.float32)
    lg_packed = np.asarray(step(packed_params, tok, caches, pos),
                           np.float32)
    ids_dense = lg_dense[:, -1, :].argmax(-1)
    ids_packed = lg_packed[:, -1, :].argmax(-1)
    if not np.array_equal(ids_dense, ids_packed):
        raise RuntimeError(
            "--packed decode diverged from the dense STE path at "
            f"temperature 0: token ids {ids_packed.tolist()} vs "
            f"{ids_dense.tolist()}")
    np.testing.assert_allclose(lg_packed, lg_dense, rtol=1e-5, atol=1e-5,
                               err_msg="--packed logits drifted from "
                               "the dense STE path")


def make_resilient_decode(cfg, ctx_len: int, temperature: float,
                          engine: str, n_queues, *, max_retries: int = 2,
                          backoff_s: float = 0.05,
                          sleep: Callable[[float], None] = time.sleep,
                          make_fn: Callable = make_decode_fn,
                          ) -> Tuple[Callable, Dict[str, Any],
                                     List[Dict[str, Any]]]:
    """Graceful degradation for the serving hot path.

    A decode step that raises (a DRIM engine wedged mid-lowering, a
    dead queue surfacing as a dispatch error) is retried with
    exponential backoff on the SAME engine; once retries exhaust, the
    server rebuilds the decode fn on the "tpu" comparator engine —
    numerically the oracle the DRIM engines are held bit-identical to,
    so tokens keep flowing at reduced fidelity-of-simulation, not
    reduced correctness — and keeps serving.

    Every failure appends a STRUCTURED incident record — `t_s`
    (timestamp on the run clock, seconds since this wrapper was built),
    `engine`, `attempt`, `retries` (retries already burned on the
    current engine), `error`, `action`, and for fallbacks a
    `fallback_reason` — and books it on the telemetry registry
    (``serve.incident:*`` counters, trace instants when armed), so the
    operator sees the degradation in the stats record AND in every
    registry snapshot instead of a dead server.

    Returns (decode_fn, state, incidents); `state["engine"]` tracks
    the engine currently serving.  `sleep`/`make_fn` are injectable so
    tests can drive the failure path with fakes and no wall-clock.
    """
    state: Dict[str, Any] = {
        "engine": engine,
        "fn": make_fn(cfg, ctx_len, temperature, engine, n_queues)}
    incidents: List[Dict[str, Any]] = []
    clock0 = time.perf_counter()

    def book(rec: Dict[str, Any], kind: str) -> None:
        rec["action_kind"] = kind
        incidents.append(rec)
        telemetry.REGISTRY.counters("serve")[f"incident:{kind}"] += 1
        telemetry.event("serve:incident", cat="serve", tid="serve", **rec)

    def dec(*args):
        attempt, delay = 0, backoff_s
        while True:
            try:
                return state["fn"](*args)
            except Exception as e:  # noqa: BLE001 — any engine failure
                rec = {"t_s": round(time.perf_counter() - clock0, 6),
                       "engine": state["engine"], "attempt": attempt,
                       "retries": attempt,
                       "error": f"{type(e).__name__}: {e}"[:200]}
                attempt += 1
                if attempt <= max_retries:
                    rec["action"] = f"retry(backoff={delay:g}s)"
                    book(rec, "retry")
                    sleep(delay)
                    delay *= 2
                elif state["engine"] != "tpu":
                    rec["action"] = "fallback:tpu"
                    rec["fallback_reason"] = (
                        f"retries exhausted on engine "
                        f"{state['engine']!r} ({max_retries} retries)")
                    book(rec, "fallback")
                    state["engine"] = "tpu"
                    state["fn"] = make_fn(cfg, ctx_len, temperature,
                                          "tpu", n_queues)
                    attempt, delay = 0, backoff_s
                else:
                    rec["action"] = "abort"
                    book(rec, "abort")
                    raise

    return dec, state, incidents


def _percentiles_ms(step_times: List[float]) -> Tuple[float, float]:
    if not step_times:
        return 0.0, 0.0
    arr = np.asarray(step_times) * 1e3
    return float(np.percentile(arr, 50)), float(np.percentile(arr, 99))


def _setup(args, cfg, mesh):
    """Params + prompt batch + jitted prefill + spliced ctx caches."""
    ctx_len = args.ctx or (args.prompt_len + args.gen)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    toks = jax.random.randint(jax.random.fold_in(key, 1),
                              (args.batch, args.prompt_len), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.zeros(
            (args.batch, cfg.n_patches, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch["frames"] = jnp.zeros(
            (args.batch, cfg.n_frames, cfg.d_model), jnp.bfloat16)

    dense_params = params
    if args.packed:
        params = pack_model_params(params)

    t0 = time.time()
    logits, pre_caches = jax.jit(
        lambda p, b: prefill(p, cfg, b))(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    caches = splice_caches(empty_caches(cfg, args.batch, ctx_len),
                           pre_caches)
    return dict(ctx_len=ctx_len, key=key, params=params,
                dense_params=dense_params, batch=batch, logits=logits,
                caches=caches, prefill_s=t_prefill)


def run_serve(args, sink: Optional[Dict[str, Any]] = None,
              ) -> Tuple[np.ndarray, Dict[str, Any]]:
    """The static-batch serving loop; returns (generated ids [B, gen],
    stats dict).  `sink`, when given, receives what a caller checks the
    served numbers against: the config, the dense params, the prompt
    batch, the prefill logits and the decode caches they fill."""
    cfg = build_cfg(args)
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=(args.mesh == "multi")))
    with mesh:
        st = _setup(args, cfg, mesh)
        if sink is not None:
            sink.update(cfg=cfg, params=st["dense_params"],
                        batch=st["batch"], logits=st["logits"],
                        caches=st["caches"], ctx_len=st["ctx_len"])
        ctx_len, key, params, caches = (st["ctx_len"], st["key"],
                                        st["params"], st["caches"])
        if args.resilient:
            dec, eng_state, incidents = make_resilient_decode(
                cfg, ctx_len, args.temperature, args.engine,
                args.n_queues)
        else:
            dec = make_decode_fn(cfg, ctx_len, args.temperature,
                                 args.engine, args.n_queues)
            eng_state, incidents = {"engine": args.engine}, []

        tok = jnp.argmax(st["logits"][:, -1, :], -1)[:, None] \
            .astype(jnp.int32)
        pos0 = jnp.full((args.batch,), args.prompt_len, jnp.int32)

        if args.packed and args.temperature == 0:
            _assert_packed_bit_exact(cfg, st["dense_params"], params,
                                     tok, caches, pos0, ctx_len)

        # Untimed warm-up on the first step's exact shapes: jit compile
        # (or DRIM kernel trace + lowering) lands here, not in tok/s.
        t0 = time.time()
        wu_tok, _ = dec(params, tok, caches, pos0,
                        jax.random.fold_in(key, 100))
        jax.block_until_ready(wu_tok)
        compile_s = time.time() - t0

        out = [np.asarray(tok)]
        step_times: List[float] = []
        for i in range(args.gen - 1):
            pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
            t1 = time.time()
            with telemetry.span("decode:token", cat="serve", tid="serve",
                                token=i, engine=eng_state["engine"],
                                batch=args.batch):
                tok, caches = dec(params, tok, caches, pos,
                                  jax.random.fold_in(key, 100 + i))
                jax.block_until_ready(tok)
            step_times.append(time.time() - t1)
            out.append(np.asarray(tok))

        gen = np.concatenate(out, 1)
        p50, p99 = _percentiles_ms(step_times)
        tok_per_s = (args.batch * (args.gen - 1)
                     / max(sum(step_times), 1e-9))
        stats = {
            "arch": cfg.arch, "engine": eng_state["engine"],
            "packed": bool(args.packed), "batch": args.batch,
            "gen": args.gen, "prefill_s": round(st["prefill_s"], 3),
            "compile_s": round(compile_s, 3),
            "decode_tok_per_s": round(tok_per_s, 1),
            "decode_p50_ms": round(p50, 3),
            "decode_p99_ms": round(p99, 3),
            "sample_ids": gen[0, :8].tolist(),
        }
        if args.resilient:
            stats["requested_engine"] = args.engine
            stats["incidents"] = incidents
        if telemetry.enabled():
            stats["telemetry"] = telemetry.snapshot()
        return gen, stats


def run_microbench(args) -> Tuple[None, Dict[str, Any]]:
    """The maxtext-style decode microbenchmark split: prefill / insert /
    generate timed separately, each with compile time reported apart
    from steady-state (the same warm-up discipline as run_serve)."""
    cfg = build_cfg(args)
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=(args.mesh == "multi")))
    iters = 3
    with mesh:
        ctx_len = args.ctx or (args.prompt_len + args.gen)
        key = jax.random.PRNGKey(args.seed)
        params = init_params(key, cfg)
        if args.packed:
            params = pack_model_params(params)
        toks = jax.random.randint(jax.random.fold_in(key, 1),
                                  (args.batch, args.prompt_len), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks}

        # prefill: full-sequence forward building batch caches
        pf = jax.jit(lambda p, b: prefill(p, cfg, b))
        t0 = time.time()
        logits, pre_caches = pf(params, batch)
        jax.block_until_ready(logits)
        pf_compile = time.time() - t0
        times = []
        for _ in range(iters):
            t0 = time.time()
            lg, pre_caches = pf(params, batch)
            jax.block_until_ready(lg)
            times.append(time.time() - t0)
        prefill_stats = {"compile_s": round(pf_compile, 3),
                         "avg_s": round(float(np.mean(times)), 4)}

        # insert: splice prefill caches into the ctx-length decode caches
        empty = empty_caches(cfg, args.batch, ctx_len)
        ins = jax.jit(splice_caches)
        t0 = time.time()
        caches = ins(empty, pre_caches)
        jax.block_until_ready(caches)
        ins_compile = time.time() - t0
        times = []
        for _ in range(iters):
            t0 = time.time()
            caches = ins(empty, pre_caches)
            jax.block_until_ready(caches)
            times.append(time.time() - t0)
        insert_stats = {"compile_s": round(ins_compile, 3),
                        "avg_s": round(float(np.mean(times)), 4)}

        # generate: steady-state decode steps after one untimed warm-up
        dec = make_decode_fn(cfg, ctx_len, args.temperature, args.engine,
                             args.n_queues)
        tok = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(jnp.int32)
        pos0 = jnp.full((args.batch,), args.prompt_len, jnp.int32)
        t0 = time.time()
        wu_tok, _ = dec(params, tok, caches, pos0,
                        jax.random.fold_in(key, 100))
        jax.block_until_ready(wu_tok)
        gen_compile = time.time() - t0
        step_times = []
        for i in range(max(args.gen - 1, 1)):
            pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
            t1 = time.time()
            tok, caches = dec(params, tok, caches, pos,
                              jax.random.fold_in(key, 100 + i))
            jax.block_until_ready(tok)
            step_times.append(time.time() - t1)
        p50, p99 = _percentiles_ms(step_times)
        generate_stats = {
            "compile_s": round(gen_compile, 3),
            "tok_per_s": round(args.batch * len(step_times)
                               / max(sum(step_times), 1e-9), 1),
            "p50_ms": round(p50, 3), "p99_ms": round(p99, 3)}

        stats = {"arch": cfg.arch, "engine": args.engine,
                 "packed": bool(args.packed), "batch": args.batch,
                 "microbench": {"prefill": prefill_stats,
                                "insert": insert_stats,
                                "generate": generate_stats}}
        return None, stats


def run_continuous(args) -> Tuple[Dict[int, np.ndarray], Dict[str, Any]]:
    """N staggered requests through the wave batcher; arrivals join the
    next shared wave, positions and slots tracked per request."""
    cfg = build_cfg(args)
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=(args.mesh == "multi")))
    with mesh:
        ctx_len = args.ctx or (args.prompt_len + args.gen)
        key = jax.random.PRNGKey(args.seed)
        params = init_params(key, cfg)
        if args.packed:
            params = pack_model_params(params)
        batcher = WaveBatcher(cfg, params, n_slots=args.batch,
                              ctx_len=ctx_len,
                              temperature=args.temperature,
                              seed=args.seed, engine=args.engine,
                              n_queues=args.n_queues)
        prompts = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 1),
            (args.continuous, args.prompt_len), 0, cfg.vocab_size))
        for r in range(args.continuous):
            batcher.submit(prompts[r], args.gen,
                           arrival_wave=r * args.arrive_every)
        t0 = time.time()
        results = batcher.run()
        wall = time.time() - t0
        total_toks = sum(len(v) for v in results.values())
        occupancy = (float(np.mean([w["n_active"]
                                    for w in batcher.wave_log]))
                     if batcher.wave_log else 0.0)
        stats = {
            "arch": cfg.arch, "engine": args.engine,
            "packed": bool(args.packed), "n_requests": args.continuous,
            "n_slots": args.batch, "n_waves": batcher.wave,
            "total_tokens": total_toks,
            "tok_per_s": round(total_toks / max(wall, 1e-9), 1),
            "mean_active_slots": round(occupancy, 2),
            "request_tokens": {int(r): v.tolist()[:8]
                               for r, v in results.items()},
        }
        return results, stats


def main(argv=None):
    exact_rounding()
    args = parse_args(argv)
    enable_compile_cache()
    if args.telemetry or args.trace_out:
        telemetry.arm()
    if args.microbench:
        gen, stats = run_microbench(args)
    elif args.continuous:
        gen, stats = run_continuous(args)
    else:
        gen, stats = run_serve(args)
    if telemetry.enabled() and "telemetry" not in stats:
        stats["telemetry"] = telemetry.snapshot()
    if args.trace_out:
        stats["trace_out"] = telemetry.export_trace(args.trace_out)
    print(json.dumps(stats))
    return gen


if __name__ == "__main__":
    main()
