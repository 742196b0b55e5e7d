"""Serving through DRIM: cross-engine token identity, the packed path,
cache-splice strictness, trace-once caching, and continuous batching.

The load-bearing guarantee: at temperature 0 the greedy token stream is
IDENTICAL whichever engine executes the BitLinear decode matmuls — the
bf16 STE matmul and the exact XNOR-popcount integer dot produce the
same number bitwise, so "tpu", "resident" and "queued" must agree token
for token, and --packed must agree with the dense shadow weights.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import batching, serve
from repro.launch.mesh import make_host_mesh

# tiny drim-bnn geometry: K in {32, 64} keeps the carry-save lowerings
# single-chunk and fast on the CPU simulator
TINY = ["--arch", "drim-bnn", "--smoke-config", "--layers", "2",
        "--d-model", "32", "--d-ff", "64", "--heads", "2",
        "--kv-heads", "1", "--d-head", "16", "--vocab", "128",
        "--prompt-len", "8", "--gen", "5", "--batch", "2"]

MULTI_DEVICE = len(jax.devices()) >= 8


def _serve(*extra):
    return serve.run_serve(serve.parse_args(TINY + list(extra)))


@pytest.fixture(scope="module")
def tpu_run():
    return _serve("--engine", "tpu")


# --- cross-engine token identity ---------------------------------------------

def test_resident_matches_tpu_tokens(tpu_run):
    gen_t, stats_t = tpu_run
    gen_r, stats_r = _serve("--engine", "resident")
    np.testing.assert_array_equal(gen_r, gen_t)
    assert stats_r["sample_ids"] == stats_t["sample_ids"]


def test_run_serve_sink_holds_float32_prefill():
    """`run_serve(args, sink)` hands over the config, params, prompts
    and prefill logits: the served prefill of the sink's params and
    prompts gives the served logits bitwise, and in float32 (the chip
    smoke run's logit check) it agrees with the XLA-attention
    reference."""
    from repro.models import prefill
    sink = {}
    gen, _ = serve.run_serve(serve.parse_args(TINY), sink)
    assert gen.shape == (2, 5)
    assert sink["cfg"].dtype == "bfloat16"

    def run(cfg):
        return np.asarray(jax.jit(lambda p, b: prefill(p, cfg, b)[0])(
            sink["params"], sink["batch"]), np.float32)
    np.testing.assert_array_equal(run(sink["cfg"]),
                                  np.asarray(sink["logits"], np.float32))
    cfg32 = sink["cfg"].replace(dtype="float32")
    np.testing.assert_allclose(
        run(cfg32), run(cfg32.replace(attention_impl="xla")),
        rtol=1e-6, atol=1e-6)


def test_exact_rounding_needs_a_fresh_backend(monkeypatch):
    """XLA reads XLA_FLAGS once: once a backend has started,
    `exact_rounding` refuses rather than set a flag nothing would read;
    with the flag already set it has nothing to do.  (The serve CLI
    test below runs it in a fresh process.)"""
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    with pytest.raises(RuntimeError, match="after JAX started"):
        serve.exact_rounding()
    monkeypatch.setenv("XLA_FLAGS", serve.EXACT_ROUNDING_FLAG)
    serve.exact_rounding()
    assert os.environ["XLA_FLAGS"] == serve.EXACT_ROUNDING_FLAG


def test_queued_matches_tpu_tokens(tpu_run):
    gen_t, _ = tpu_run
    gen_q, _ = _serve("--engine", "queued")
    np.testing.assert_array_equal(gen_q, gen_t)


def test_packed_matches_dense_tokens(tpu_run):
    gen_t, _ = tpu_run
    gen_p, stats_p = _serve("--engine", "tpu", "--packed")
    assert stats_p["packed"] is True
    np.testing.assert_array_equal(gen_p, gen_t)


def test_packed_resident_matches_dense(tpu_run):
    gen_t, _ = tpu_run
    gen_pr, _ = _serve("--engine", "resident", "--packed")
    np.testing.assert_array_equal(gen_pr, gen_t)


def test_compile_time_reported_separately(tpu_run):
    _, stats = tpu_run
    # the warm-up fix: compile lands in compile_s, not in the timed
    # steps — steady-state p99 must be far below the compile time
    assert stats["compile_s"] > 0
    assert stats["decode_p99_ms"] / 1e3 < stats["compile_s"]
    assert stats["decode_tok_per_s"] > 0


# --- trace/lower once per layer shape ----------------------------------------

def test_serving_lowerings_cached_across_steps():
    from repro.pim.bnn import bitlinear_kernel
    from repro.pim.compiler import LOWER_CACHE_STATS
    _serve("--engine", "resident")
    misses0 = LOWER_CACHE_STATS["misses"]
    traces0 = bitlinear_kernel.cache_info().misses
    hits0 = LOWER_CACHE_STATS["hits"]
    _serve("--engine", "resident")
    # second run: every layer-shape kernel trace and lowering is a
    # cache hit — zero new traces, zero new lowerings
    assert bitlinear_kernel.cache_info().misses == traces0
    assert LOWER_CACHE_STATS["misses"] == misses0
    assert LOWER_CACHE_STATS["hits"] > hits0


# --- cache-splice strictness -------------------------------------------------

def test_splice_caches_exact_and_growing():
    full = {"k": jnp.zeros((2, 3, 8, 4)), "v": jnp.zeros((2, 3, 8, 4))}
    pre = {"k": jnp.ones((2, 3, 5, 4)), "v": jnp.ones((2, 3, 8, 4))}
    out = serve.splice_caches(full, pre)
    assert float(out["k"][:, :, :5].min()) == 1.0
    assert float(out["k"][:, :, 5:].max()) == 0.0
    assert float(out["v"].min()) == 1.0


def test_splice_caches_raises_naming_path_on_ndim_mismatch():
    # the old tree.map silently KEPT the empty cache here
    full = {"layers": {"kcache": jnp.zeros((2, 3, 8, 4))}}
    pre = {"layers": {"kcache": jnp.ones((3, 8, 4))}}
    with pytest.raises(ValueError, match=r"kcache"):
        serve.splice_caches(full, pre)


def test_splice_caches_raises_on_oversized_prefill():
    full = {"c": jnp.zeros((2, 4, 4))}
    pre = {"c": jnp.ones((2, 9, 4))}
    with pytest.raises(ValueError, match="cache splice mismatch"):
        serve.splice_caches(full, pre)


def test_insert_request_raises_naming_path():
    full = {"kcache": jnp.zeros((2, 4, 8, 4))}
    bad = {"kcache": jnp.ones((2, 8, 4))}          # missing batch axis
    with pytest.raises(ValueError, match=r"kcache"):
        batching.insert_request(full, bad, 0)


# --- microbenchmark split ----------------------------------------------------

def test_microbench_split_reports_all_stages():
    _, stats = serve.run_microbench(
        serve.parse_args(TINY + ["--microbench"]))
    mb = stats["microbench"]
    assert set(mb) == {"prefill", "insert", "generate"}
    for stage in mb.values():
        assert stage["compile_s"] >= 0
    # steady-state prefill must be far below its compile time (the
    # same first-iteration-compile bug class as decode_tok_per_s)
    assert mb["prefill"]["avg_s"] < mb["prefill"]["compile_s"]
    assert mb["generate"]["tok_per_s"] > 0


# --- continuous batching -----------------------------------------------------

def _tiny_model():
    args = serve.parse_args(TINY)
    cfg = serve.build_cfg(args)
    params = serve.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo_tokens(cfg, params, prompt, max_new, ctx_len=32):
    b = batching.WaveBatcher(cfg, params, n_slots=1, ctx_len=ctx_len)
    b.submit(prompt, max_new)
    return b.run()[0]


@pytest.fixture(scope="module")
def cont():
    """3 requests into 2 slots: r0/r1 at wave 0, r2 arrives at wave 2."""
    with make_host_mesh():
        cfg, params = _tiny_model()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, 8) for _ in range(3)]
        b = batching.WaveBatcher(cfg, params, n_slots=2, ctx_len=32)
        b.submit(prompts[0], 6, arrival_wave=0)
        b.submit(prompts[1], 4, arrival_wave=0)
        b.submit(prompts[2], 4, arrival_wave=2)
        results = b.run()
        solo = {r: _solo_tokens(cfg, params, prompts[r],
                                [6, 4, 4][r]) for r in range(3)}
        return b, results, solo


def test_wave0_admits_both_initial_requests(cont):
    b, _, _ = cont
    assert b.wave_log[0]["admitted"] == [0, 1]
    assert b.wave_log[0]["n_active"] == 2


def test_late_arrival_joins_next_shared_wave(cont):
    b, _, _ = cont
    # r2 (arrival_wave=2) is admitted at wave >= 2 — never earlier, and
    # it joins a SHARED wave with r0 still decoding, not a private stream
    admit = next(w for w in b.wave_log if 2 in w["admitted"])
    assert admit["wave"] >= 2
    assert admit["n_active"] >= 2 and 0 in admit["decoded"]


def test_positions_advance_independently(cont):
    b, _, _ = cont
    pos = {0: [], 1: [], 2: []}
    for w in b.wave_log:
        for rid, p in w["positions"].items():
            pos[rid].append(p)
    for rid, ps in pos.items():
        # each active wave advances a request's position by exactly 1
        assert ps == list(range(ps[0], ps[0] + len(ps))), (rid, ps)
    # requests admitted at different waves hold different positions
    # within the same shared wave
    shared = next(w for w in b.wave_log if len(w["positions"]) >= 2
                  and 2 in w["positions"])
    assert shared["positions"][2] != shared["positions"][0]


def test_token_budgets_respected(cont):
    _, results, _ = cont
    assert [len(results[r]) for r in range(3)] == [6, 4, 4]


def test_no_cross_request_cache_leakage(cont):
    """Batched-with-strangers tokens == solo-run tokens, including r2
    reusing the slot r1 freed (the zeroed-slot insert)."""
    _, results, solo = cont
    for rid in range(3):
        np.testing.assert_array_equal(results[rid], solo[rid],
                                      err_msg=f"request {rid}")


def test_queued_until_slot_frees():
    with make_host_mesh():
        cfg, params = _tiny_model()
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, cfg.vocab_size, 8) for _ in range(3)]
        b = batching.WaveBatcher(cfg, params, n_slots=2, ctx_len=32)
        for p in prompts:
            b.submit(p, 3, arrival_wave=0)      # 3 requests, 2 slots
        results = b.run()
        # r2 waits for a free slot: admitted only after r0/r1 finished
        admit = next(w for w in b.wave_log if 2 in w["admitted"])
        assert admit["wave"] >= 2
        solo = _solo_tokens(cfg, params, prompts[2], 3)
        np.testing.assert_array_equal(results[2], solo)


def test_continuous_through_drim_engine(tpu_run):
    """The wave scheduler composes with the DRIM decode path: same
    tokens as the native engine for the same request."""
    with make_host_mesh():
        cfg, params = _tiny_model()
        prompt = np.arange(8) % cfg.vocab_size
        native = batching.WaveBatcher(cfg, params, n_slots=1, ctx_len=32)
        native.submit(prompt, 4)
        drim = batching.WaveBatcher(cfg, params, n_slots=1, ctx_len=32,
                                    engine="resident")
        drim.submit(prompt, 4)
        np.testing.assert_array_equal(drim.run()[0], native.run()[0])


def test_submit_rejects_overlong_request():
    cfg, params = _tiny_model()
    b = batching.WaveBatcher(cfg, params, n_slots=1, ctx_len=16)
    with pytest.raises(ValueError, match="ctx_len"):
        b.submit(np.zeros(10, np.int32), 10)


# --- CLI differential (forced 8-device subprocess) ---------------------------

@pytest.mark.skipif(MULTI_DEVICE, reason="already on >=8 devices")
def test_forced_8device_serve_cli_subprocess():
    """The serve CLI end to end on a forced 8-device CPU mesh: identical
    sample_ids across engines, parsed from the printed JSON."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(repo, "src"))
    ids = {}
    for engine in ("tpu", "resident"):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve"] + TINY
            + ["--engine", engine],
            env=env, cwd=repo, capture_output=True, text=True,
            timeout=540)
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        ids[engine] = stats["sample_ids"]
    assert ids["tpu"] == ids["resident"]


# --- graceful degradation (--resilient) --------------------------------------

def _fake_decode_factory(fail_plan):
    """make_fn stand-in: `fail_plan[engine]` is the number of times a
    decode step on that engine raises before succeeding."""
    calls = {"made": []}

    def make_fn(cfg, ctx_len, temperature, engine, n_queues):
        calls["made"].append(engine)
        remaining = {"n": fail_plan.get(engine, 0)}

        def dec(*args):
            if remaining["n"] > 0:
                remaining["n"] -= 1
                raise RuntimeError(f"{engine} queue wedged")
            return ("tok", engine)

        return dec

    return make_fn, calls


def test_resilient_decode_retries_then_recovers():
    """Transient failures are absorbed by backoff retries on the SAME
    engine, each logged as an incident; the engine never changes."""
    naps = []
    make_fn, calls = _fake_decode_factory({"resident": 2})
    dec, state, incidents = serve.make_resilient_decode(
        None, 16, 0.0, "resident", None, max_retries=2,
        backoff_s=0.01, sleep=naps.append, make_fn=make_fn)
    assert dec() == ("tok", "resident")
    assert state["engine"] == "resident" and calls["made"] == ["resident"]
    assert [i["action"] for i in incidents] \
        == ["retry(backoff=0.01s)", "retry(backoff=0.02s)"]
    assert naps == [0.01, 0.02]
    assert all(i["engine"] == "resident" and "queue wedged" in i["error"]
               for i in incidents)
    # recovered: further steps are clean and append nothing
    assert dec() == ("tok", "resident") and len(incidents) == 2


def test_resilient_decode_falls_back_to_tpu():
    """Retries exhausted on a wedged DRIM engine -> rebuild on the tpu
    comparator and keep serving; the incident log shows the handover."""
    make_fn, calls = _fake_decode_factory({"queued": 99})
    dec, state, incidents = serve.make_resilient_decode(
        None, 16, 0.0, "queued", 4, max_retries=1, backoff_s=0.0,
        sleep=lambda s: None, make_fn=make_fn)
    assert dec() == ("tok", "tpu")
    assert state["engine"] == "tpu"
    assert calls["made"] == ["queued", "tpu"]
    assert [i["action"] for i in incidents] \
        == ["retry(backoff=0s)", "fallback:tpu"]


def test_resilient_decode_aborts_when_tpu_dies():
    """The oracle fallback failing too is unrecoverable: re-raise, with
    the full incident trail preserved for the operator."""
    make_fn, _ = _fake_decode_factory({"resident": 99, "tpu": 99})
    dec, state, incidents = serve.make_resilient_decode(
        None, 16, 0.0, "resident", None, max_retries=1, backoff_s=0.0,
        sleep=lambda s: None, make_fn=make_fn)
    with pytest.raises(RuntimeError, match="tpu queue wedged"):
        dec()
    assert [i["action"] for i in incidents] \
        == ["retry(backoff=0s)", "fallback:tpu", "retry(backoff=0s)",
            "abort"]
    assert state["engine"] == "tpu"


def test_resilient_serve_end_to_end(tpu_run):
    """--resilient on a healthy engine is a no-op for tokens: same
    greedy stream, zero incidents, stats carry the resilience fields."""
    gen_t, _ = tpu_run
    gen_r, stats = _serve("--engine", "resident", "--resilient")
    np.testing.assert_array_equal(gen_r, gen_t)
    assert stats["requested_engine"] == "resident"
    assert stats["engine"] == "resident"
    assert stats["incidents"] == []
