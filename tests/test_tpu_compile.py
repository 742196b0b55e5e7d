"""The main-path kernels compile for a TPU v5e (no chip needed).

Interpret-mode tests check what the kernels compute; they cannot see
what Mosaic, the TPU's kernel compiler, refuses: unaligned block shapes,
primitives it has no lowering for, unsigned reductions.  Each test here
compiles one kernel at the shape the serving or fleet path runs it for a
described (not attached) v5e chip and checks that a Mosaic kernel came
out.  The topology is described inside a fixture, so a worker that is
never given this file never loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import drim
from repro.core import DRIM_S, FaultModel
from repro.pim.bnn import bnn_dot_graph_carrysave


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


@pytest.mark.parametrize("faulted", [False, True],
                         ids=["plain", "faulted"])
def test_aap_interpreter_compiles_at_drim_s(one_chip, faulted):
    """The K=32 carry-save BNN dot's stream over one DRIM-S wave tile."""
    from repro.kernels.aap_interpreter import pallas_wave_fn
    graph, _ = bnn_dot_graph_carrysave(32)
    low = drim.compile(graph, geom=DRIM_S).lower(engine="pallas")
    faults = (FaultModel(p_dra=0.01, p_tra=0.05, seed=3,
                         stuck_rows=((1, 1),)) if faulted else None)
    fn = pallas_wave_fn(low.program, low.result_rows, low.n_rows,
                        interpret=False, faults=faults)
    tile = (len(graph.input_names), DRIM_S.chips, DRIM_S.banks,
            DRIM_S.subarrays_per_bank, DRIM_S.row_bits // 32)
    _compile(fn, one_chip, (tile, jnp.uint32))


def test_xnor_gemm_packed_compiles_at_ffn_width(one_chip):
    """drim-bnn's packed gate/up decode GEMM: [4, 24] x [3072, 24] words."""
    from repro.kernels.xnor_popcount import xnor_gemm_packed
    _compile(lambda a, b: xnor_gemm_packed(a, b, 768), one_chip,
             ((4, 24), jnp.uint32), ((3072, 24), jnp.uint32))


def test_pack_signs_compiles_at_d_model(one_chip):
    from repro.kernels.packbits import pack_signs
    _compile(pack_signs, one_chip, ((4, 768), jnp.bfloat16))


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_compiles_at_prefill_shape(one_chip, direction):
    """drim-bnn prefill: [4, 12, 128, 64] queries over 4 KV heads."""
    from repro.kernels.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, 3)

    fn = fwd if direction == "forward" else jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    _compile(fn, one_chip, ((4, 12, 128, 64), jnp.bfloat16),
             ((4, 4, 128, 64), jnp.bfloat16),
             ((4, 4, 128, 64), jnp.bfloat16))
