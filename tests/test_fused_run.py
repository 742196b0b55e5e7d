"""A run of a SIMD wave engine is one transfer and one compiled program.

`Lowered.run` on the "resident", "baseline" and "pallas" engines stacks
its host planes into one host-to-device transfer and launches one
program (`scheduler.run_program`) that stages, runs the waves and cuts
the result planes out; a traced program's constant planes are made in
it.  The step-by-step path stays exported: `stage_rows` -> `run_waves`
-> `read_planes`, each plane its own device array.  Every case here
runs both and asserts the same result planes, bit for bit, the same
`Lowered.schedule`, the same ECC verdict, and the run's counters: one
program, one transfer for host planes and none for device planes, and
no constant plane among the bytes sent.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import drim
from repro.core import DrimGeometry, FaultModel
from repro.pim.bnn import bitlinear_kernel
from repro.pim.compiler import RUN_STATS
from repro.pim.graph import _make_fused_schedule
from repro.pim.scheduler import read_planes, run_waves, stage_rows

# 1 chip x 2 banks x 4 sub-arrays of 64-bit rows: 8 slots, 16 words a wave
GEOM = DrimGeometry(chips=1, banks=2, subarrays_per_bank=4, row_bits=64)
WAVE_WORDS = GEOM.n_subarrays * GEOM.row_bits // 32
HOT = FaultModel(p_dra=0.25, p_tra=0.35, seed=3)


def _source(program: str):
    if program == "xnor2":
        return "xnor2"
    return bitlinear_kernel(int(program[1:])).trace()


def _planes(low, n_words: int, where: str, seed: int):
    """The run's positional planes: all on the host, all on the device,
    or alternating."""
    n = 2 if low.kind == "op" else len(low.traced.arg_names)
    rng = np.random.default_rng(seed)
    host = [rng.integers(0, 1 << 32, n_words, dtype=np.uint32)
            for _ in range(n)]
    on_device = {"host": lambda i: False, "device": lambda i: True,
                 "mixed": lambda i: i % 2 == 1}[where]
    return [jnp.asarray(p) if on_device(i) else p
            for i, p in enumerate(host)]


def _step_by_step(low, planes, n_bits, faults=None):
    """The run as the exported steps, fed plane by plane as before the
    fused program (host zero planes for a traced program's constants).
    Returns (named result planes, schedule)."""
    n_words = int(np.size(planes[0]))
    if low.kind == "op":
        names, arrays = None, planes
        program, rows, n_rows = low.program, low.result_rows, low.n_rows
        cols = range(len(rows))
    else:
        fp = low.fp
        feeds = low.traced.feeds_for(planes)
        arrays = [feeds[n] for n in fp.loaded_inputs]
        program, rows, n_rows = fp.program, fp.readback_rows, \
            fp.template_rows
        col = {row: i for i, row in enumerate(rows)}
        names = [name for name, _ in fp.device_outputs]
        cols = [col[row] for _, row in fp.device_outputs]
    arrays = [jnp.asarray(a, jnp.uint32).reshape(-1) for a in arrays]
    staged, tiles, waves = stage_rows(arrays, geom=low.geom)
    outs = run_waves(staged, program, rows, n_rows=n_rows,
                     engine=low.engine.name,
                     faults=low._resolve_faults(faults))
    planes_out = read_planes(outs, cols, n_words)
    if low.kind == "op":
        return planes_out, low.engine.lift_op(low, n_bits, tiles, waves)
    sched = _make_fused_schedule(low.fp, n_bits, tiles, waves, low.geom)
    return dict(zip(names, planes_out)), low.engine.lift_graph(low, sched)


def _ecc_bits(results) -> int:
    """The parity verdict `Lowered._check_ecc` computes, on the host."""
    results = dict(results)
    parity = np.asarray(results.pop("__ecc__"), np.uint32)
    for plane in results.values():
        parity = parity ^ np.asarray(plane, np.uint32)
    return int(np.unpackbits(parity.view(np.uint8)).sum())


def _check(low, planes, n_bits, faults=None):
    """One fused run against the step-by-step path: results, schedule
    and counters."""
    snap = dict(RUN_STATS)
    got = low.run(*planes, n_bits=n_bits, faults=faults)
    delta = {k: RUN_STATS[k] - snap.get(k, 0) for k in RUN_STATS}
    want, sched = _step_by_step(low, planes, n_bits, faults)
    if isinstance(want, dict):
        if "__ecc__" in want:
            assert low.last_ecc.mismatch_bits == _ecc_bits(want)
            want.pop("__ecc__")
        want = low.traced.restructure(want)
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert isinstance(g, jax.Array)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert low.schedule == sched == low.cost(n_bits)
    host = [p for p in planes if not isinstance(p, jax.Array)]
    assert delta["calls"] == 1
    assert delta["programs"] == 1
    assert delta["h2d_transfers"] == int(bool(host))
    # the planes given, and never a constant plane
    assert delta.get("h2d_bytes", 0) == 4 * sum(p.size for p in host)
    return got


@pytest.mark.parametrize("where", ["host", "device", "mixed"])
@pytest.mark.parametrize("program", ["xnor2", "k4", "k8"])
@pytest.mark.parametrize("words,tail", [
    (WAVE_WORDS, 0),                  # one whole wave
    (3 * WAVE_WORDS + 5, 7),          # a padded third wave, ragged n_bits
], ids=["wave", "ragged"])
def test_fused_run_matches_step_by_step(program, where, words, tail):
    low = drim.compile(_source(program), geom=GEOM).lower()
    planes = _planes(low, words, where, seed=words + len(program))
    _check(low, planes, words * 32 - tail)


@pytest.mark.parametrize("engine", ["baseline", "pallas"])
@pytest.mark.parametrize("program", ["xnor2", "k4"])
def test_fused_run_on_the_other_simd_engines(engine, program):
    """The baseline interpreter and the Pallas kernel (interpret mode
    off the chip) compose into the same one program."""
    low = drim.compile(_source(program), geom=GEOM).lower(engine)
    _check(low, _planes(low, 2 * WAVE_WORDS + 3, "mixed", seed=4),
           (2 * WAVE_WORDS + 3) * 32 - 5)


@pytest.mark.parametrize("harden", [None, "ecc"])
@pytest.mark.parametrize("engine", ["resident", "pallas"])
def test_fused_run_under_seeded_faults(engine, harden):
    """Seeded flips land on the same bits as on the step-by-step path,
    and the ECC parity verdict is the same one."""
    low = drim.compile(bitlinear_kernel(4).trace(), geom=GEOM).lower(
        engine, harden=harden)
    words = 2 * WAVE_WORDS + 3
    got = _check(low, _planes(low, words, "mixed", seed=9), words * 32,
                 faults=HOT)
    clean = drim.compile(bitlinear_kernel(4).trace(), geom=GEOM).lower(
        engine, harden=harden).run(*_planes(low, words, "mixed", seed=9))
    # the flips are real: the faulted answer is not the clean one
    assert any(not np.array_equal(np.asarray(g), np.asarray(c))
               for g, c in zip(got, jax.tree.leaves(clean)))
    if harden == "ecc":
        assert low.last_ecc.corrupted


def test_fused_program_is_built_once_per_signature():
    """Host and device planes of one shape reuse their compiled program;
    a new word count builds one more."""
    from repro.pim.scheduler import run_program
    low = drim.compile("xnor2", geom=GEOM).lower()
    a, b = _planes(low, 40, "host", seed=1)
    low.run(a, b)
    built = run_program.cache_info().currsize
    low.run(b, a)
    low.run(*_planes(low, 40, "host", seed=2))
    assert run_program.cache_info().currsize == built
    low.run(*_planes(low, 41, "host", seed=3))
    assert run_program.cache_info().currsize == built + 1


def test_alias_output_comes_back_from_the_program():
    """A graph output that is one of its inputs leaves the program as
    that input's words, beside the computed outputs."""
    def f(a, b):
        return a, drim.xnor(a, b)

    low = drim.compile(drim.jit(f), geom=GEOM).lower()
    a, b = _planes(drim.compile("xnor2", geom=GEOM).lower(), 20, "host",
                   seed=5)
    snap = dict(RUN_STATS)
    same, x = low.run(a, jnp.asarray(b))
    assert RUN_STATS["programs"] - snap["programs"] == 1
    np.testing.assert_array_equal(np.asarray(same), a)
    np.testing.assert_array_equal(np.asarray(x), ~(a ^ b))
