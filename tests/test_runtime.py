"""Runtime substrate tests: checkpoint round-trip + elastic reshard,
1-bit EF compression invariants (hypothesis), fault-tolerance helpers,
data pipeline determinism, and sharding-rule unit checks."""
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st  # hypothesis, or seeded fallback

from repro.checkpoint.checkpoint import Checkpointer
from repro.data.pipeline import (MemmapLM, Prefetcher, SyntheticLM,
                                 attach_modality_stub, host_batch_slice)
from repro.optim.compress import (compress_grad, compress_tree,
                                  decompress_tree, init_errors)
from repro.runtime.ft import (HeartbeatMonitor, elastic_plan,
                              run_with_restarts)
from repro.runtime import sharding as shd
from jax.sharding import PartitionSpec as P


# --- checkpoint ------------------------------------------------------------

def _tree():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                       "b": jnp.ones((4,))},
            "opt": {"m": jnp.zeros((3, 4))},
            "step": jnp.asarray(7, jnp.int32)}


def test_checkpoint_roundtrip_and_latest():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        t = _tree()
        ck.save(1, t)
        t2 = jax.tree.map(lambda x: x + 1, t)
        ck.save(2, t2)
        ck.wait()
        step, got = ck.restore_latest(jax.eval_shape(lambda: t))
        assert step == 2
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(t2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_policy_and_crash_safety():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        t = _tree()
        for s in (1, 2, 3):
            ck.save(s, t)
        ck.wait()
        steps = sorted(p for p in os.listdir(d) if p.startswith("step_"))
        assert len(steps) == 2 and int(steps[-1].split("_")[1]) == 3
        # a partially-written step dir must be ignored (LATEST decides)
        os.makedirs(os.path.join(d, "step_000099"))
        step, _ = ck.restore_latest(jax.eval_shape(lambda: t))
        assert step == 3


def test_checkpoint_elastic_reshard():
    """Leaves are host-gathered: restore works under a different mesh."""
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        t = _tree()
        ck.save(5, t)
        ck.wait()
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        shard = jax.sharding.NamedSharding(mesh, P(None, "model"))
        step, got = ck.restore_latest(jax.eval_shape(lambda: t),
                                      shardings=None)
        assert step == 5
        w = jax.device_put(got["params"]["w"], shard)
        np.testing.assert_array_equal(np.asarray(w),
                                      np.asarray(t["params"]["w"]))


# --- 1-bit EF compression ----------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(0.01, 100.0))
def test_ef_compression_error_feedback_invariant(seed, scale):
    """sign*scale + residual == corrected gradient, exactly."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.standard_normal(64).astype(np.float32) * scale)
    err = jnp.asarray(rng.standard_normal(64).astype(np.float32) * 0.1)
    sign, s, new_err = compress_grad(g, err)
    decoded = sign.astype(jnp.float32) * s
    np.testing.assert_allclose(np.asarray(decoded + new_err),
                               np.asarray(g + err), rtol=1e-5, atol=1e-5)
    assert set(np.unique(np.asarray(sign))) <= {-1, 1}


def test_ef_compression_error_stays_bounded():
    """EF residual reaches a steady state (no unbounded drift) and the
    accumulated transmitted signal tracks the true gradient.  The
    mean-scale signSGD bound is dimension-dependent (outliers need ~d
    steps for the scale to catch up), so use a small d and check
    STATIONARITY after burn-in rather than a tight constant."""
    rng = np.random.default_rng(0)
    g_fixed = jnp.asarray(rng.standard_normal(16).astype(np.float32))
    err = jnp.zeros_like(g_fixed)
    sent = jnp.zeros_like(g_fixed)
    norms = []
    for i in range(300):
        sign, s, err = compress_grad(g_fixed, err)
        sent = sent + sign.astype(jnp.float32) * s
        norms.append(float(jnp.linalg.norm(err)))
    # steady state: the residual norm stops growing after burn-in
    assert max(norms[200:]) < 1.25 * max(norms[100:200])
    # direction of the accumulated signal matches the true gradient
    corr = float(jnp.sum(sent * g_fixed)
                 / (jnp.linalg.norm(sent) * jnp.linalg.norm(g_fixed)))
    assert corr > 0.9


def test_compress_tree_shapes():
    params = {"a": jnp.ones((4, 4)), "b": {"c": jnp.ones((3,))}}
    errs = init_errors(params)
    signs, scales, new_errs = compress_tree(params, errs)
    dec = decompress_tree(signs, scales)
    assert jax.tree.structure(dec) == jax.tree.structure(params)
    for leaf in jax.tree.leaves(new_errs):
        assert bool(jnp.isfinite(leaf).all())


# --- fault tolerance ----------------------------------------------------------

def test_heartbeat_straggler_detection(tmp_path):
    path = str(tmp_path / "hb.jsonl")
    h0 = HeartbeatMonitor(path, host_id=0)
    h1 = HeartbeatMonitor(path, host_id=1)
    h0.beat(10)
    h1.beat(4)
    tab = h0.table()
    assert tab[0].last_step == 10 and tab[1].last_step == 4
    stragglers, dead = h0.report(now=tab[0].last_seen + 1.0)
    assert dead == []  # both hosts beat recently


def test_elastic_plan_shrinks_data_axis():
    plan = elastic_plan(n_alive_hosts=3, devices_per_host=4,
                        global_batch=24, model_parallel=2)
    assert plan["model"] == 2
    assert plan["data"] == 6 and 24 % plan["data"] == 0
    assert plan["per_host_batch"] == 8


def test_elastic_plan_rejects_ragged_batch():
    """A global batch that does not split over the survivors must be a
    loud error naming the largest fleet that fits — silently flooring
    the per-host batch would change training semantics on resize."""
    with pytest.raises(ValueError, match="resize the fleet to 6 hosts"):
        elastic_plan(n_alive_hosts=7, devices_per_host=1,
                     global_batch=24, model_parallel=1)
    with pytest.raises(ValueError, match="at least one alive host"):
        elastic_plan(n_alive_hosts=0, devices_per_host=1,
                     global_batch=24, model_parallel=1)
    with pytest.raises(ValueError, match="not divisible by TP"):
        elastic_plan(n_alive_hosts=3, devices_per_host=1,
                     global_batch=24, model_parallel=2)


def test_heartbeat_first_beat_reports_zero_latency(tmp_path):
    """Construct-to-beat gap is NOT a step latency: a slow-to-start
    host must not look like a straggler before running a step."""
    path = str(tmp_path / "hb.jsonl")
    h = HeartbeatMonitor(path, host_id=0)
    h._last_beat = None
    h.beat(0)
    assert h.table()[0].step_latency == 0.0
    h.beat(1)
    assert h.table()[0].step_latency >= 0.0


def test_heartbeat_table_skips_torn_writes(tmp_path):
    path = str(tmp_path / "hb.jsonl")
    h = HeartbeatMonitor(path, host_id=0)
    h.beat(3)
    h.beat(4)
    with open(path, "a") as f:
        f.write('{"host": 1, "step": 5, "t"')  # host died mid-write
    tab = h.table()
    assert set(tab) == {0} and tab[0].last_step == 4


def test_heartbeat_prune_drops_dead_hosts(tmp_path):
    path = str(tmp_path / "hb.jsonl")
    h0 = HeartbeatMonitor(path, host_id=0, dead_after_s=10.0)
    h1 = HeartbeatMonitor(path, host_id=1, dead_after_s=10.0)
    h0.beat(1)
    h1.beat(1)
    with open(path, "a") as f:
        f.write("{torn")  # dying host's partial record rides along
    now = max(h.last_seen for h in h0.table().values())
    # within the deadline nothing is pruned (torn line included: it is
    # only dropped once a rewrite actually happens)
    assert h0.prune(now=now + 9.0) == []
    assert set(h0.table()) == {0, 1}
    # past the deadline both hosts are dead: table rewritten atomically
    assert h0.prune(now=now + 1000.0) == [0, 1]
    assert h0.table() == {} and os.path.exists(path)


def test_run_with_restarts_recovers_then_exhausts():
    calls = []

    def flaky(start):
        calls.append(start)
        if len(calls) < 3:
            raise RuntimeError("host crash")
        return 42

    assert run_with_restarts(flaky, max_restarts=3) == 42
    assert calls == [None, None, None]

    def always_down(start):
        raise RuntimeError("rack on fire")

    with pytest.raises(RuntimeError, match="rack on fire"):
        run_with_restarts(always_down, max_restarts=2)


# --- data pipeline --------------------------------------------------------------

def test_synthetic_lm_deterministic_and_resumable():
    a = SyntheticLM(1000, 16, 4, seed=3)
    b = SyntheticLM(1000, 16, 4, seed=3)
    ba, bb = a.batch_at(17), b.batch_at(17)
    np.testing.assert_array_equal(ba["tokens"], bb["tokens"])
    np.testing.assert_array_equal(ba["labels"], bb["labels"])
    assert ba["tokens"].shape == (4, 16)
    assert (ba["tokens"] < 1000).all() and (ba["tokens"] >= 0).all()


def test_memmap_lm_roundtrip(tmp_path):
    toks = (np.arange(1000, dtype=np.uint32) % 97)
    path = str(tmp_path / "tokens.bin")
    toks.tofile(path)
    ds = MemmapLM(path, seq_len=8, batch=2)
    b0 = ds.batch_at(0)
    assert b0["tokens"].shape == (2, 8)
    np.testing.assert_array_equal(b0["labels"][:, :-1], b0["tokens"][:, 1:])


def test_prefetcher_matches_source():
    src = SyntheticLM(50, 4, 2, seed=1)
    direct = [src.batch_at(i)["tokens"] for i in range(5)]
    pf = Prefetcher(iter(SyntheticLM(50, 4, 2, seed=1)), depth=2)
    got = [next(iter(pf))["tokens"] if i == 0 else next(pf)["tokens"]
           for i in range(5)]
    for d, g in zip(direct, got):
        np.testing.assert_array_equal(d, g)
    pf.close()


def test_host_batch_slice_partitions():
    n = 4
    sizes = [host_batch_slice(256, h, n) for h in range(n)]
    assert sum(sizes) == 256


# --- sharding rules ---------------------------------------------------------------

def test_sanitize_drops_nondividing_axes():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # force axis sizes: use a fake mesh dict-alike via production mesh rules
    spec = shd.sanitize_spec(P("data", "model"), (7, 6), mesh)
    # both axes have size 1 -> always divide
    assert spec == P("data", "model") or spec == P()


def test_param_rules_family_ssm_replicated():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params = {"layers": {"mixer": {"in_proj": {"kernel":
                                               jnp.ones((8, 16))}}},
              "head": jnp.ones((8, 32))}
    specs = shd.param_pspecs(params, mesh, family="ssm")
    assert all(s == P() for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))


def test_zero1_spec_adds_data_axis():
    mesh = jax.sharding.AbstractMesh((2, 1), ("data", "model"))
    out = shd.zero1_spec(P(None, "model"), (8, 4), mesh)
    assert out == P("data", "model")
