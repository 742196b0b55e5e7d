"""Pallas TPU kernel: flash attention (fwd + bwd), GQA-aware.

The dense archs' memory roofline term is dominated by materialised
[B, H, S, S] score tensors (f32 logits + softmax temporaries): for
qwen3-14b train_4k that is ~86 GB of HBM traffic per layer.  Flash
attention streams KV blocks through VMEM with an online-softmax
accumulator, so per-layer HBM traffic collapses to O(q + k + v + o)
(~5 GB) — the classic compute-for-bandwidth trade the TPU memory
hierarchy wants.

Layout: q [B, H, Sq, D], k/v [B, Hkv, Sk, D] (GQA: H = Hkv * n_rep; the
kv BlockSpec maps query-head h -> kv-head h // n_rep, so KV blocks are
shared across the rep group without materialising the repeat).  Grid
(B, H, Sq/BQ, Sk/BK) with the KV dimension innermost; the f32 running
(acc, m, l) state lives in VMEM scratch across the KV sweep.  Causal
masking prunes nothing (all blocks are visited; masked lanes get -inf)
— correctness-first; block-pruning is a straightforward follow-up.

Backward: recompute-based (flash-attn v2 style), two passes:
  * dkv pass: grid (B, Hkv, Sk/BK, Sq/BQ) accumulates dk/dv over Sq and
    the GQA rep group (n_rep folded into the Sq sweep via index maps).
  * dq pass: grid (B, H, Sq/BQ, Sk/BK) accumulates dq over Sk.
Both recompute p = exp(qk - lse) from the saved per-row LSE, so nothing
[S, S]-shaped ever touches HBM.  The LSE and delta rows travel
lane-replicated as [B, H, Sq, 128] (see `LANES`).

`flash_attention(..., interpret=True)` runs the kernel body in Python on
CPU — that is how tests/test_flash_attention.py sweeps shapes against
ref.sdpa_ref.  On-TPU numerics: bf16 operands, f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30
# Per-row softmax statistics (running max, running sum, LSE, delta) are
# kept lane-replicated as [rows, LANES]: a block whose trailing dims are
# (bq, 128) is one the TPU tiling accepts, where a bare [bq] row is not.
LANES = 128


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, bq, bk, sk):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # [BQ, D]
    k = k_ref[0, 0].astype(jnp.float32)            # [BK, D]
    v = v_ref[0, 0].astype(jnp.float32)            # [BK, D]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qi = pl.program_id(2) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        kj = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qi >= kj, s, NEG_INF)

    m_prev = m_ref[...]                             # [BQ, LANES]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == (sk // bk) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=("causal", "n_rep", "bq", "bk",
                                             "interpret"))
def _flash_fwd(q, k, v, *, causal, n_rep, bq, bk, interpret):
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    scale = 1.0 / (d ** 0.5)
    grid = (b, h, sq // bq, sk // bk)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, sk=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),       # acc
            pltpu.VMEM((bq, LANES), jnp.float32),   # m (running max)
            pltpu.VMEM((bq, LANES), jnp.float32),   # l (running sum)
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------------------
# backward (recompute from LSE)
# --------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, bq, bk, sq, n_rep):
    ib = pl.program_id(3)          # combined (rep, Sq-block) sweep
    nqb = sq // bq
    qb = ib % nqb

    @pl.when(ib == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)             # [BQ, D]
    k = k_ref[0, 0].astype(jnp.float32)             # [BK, D]
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)           # [BQ, D]
    lse = lse_ref[0, 0][:, :1]                      # [BQ, 1]
    delta = delta_ref[0, 0][:, :1]                  # [BQ, 1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qi = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = pl.program_id(2) * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        s = jnp.where(qi >= kj, s, NEG_INF)
    p = jnp.exp(s - lse)                            # [BQ, BK]

    dv_acc[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ib == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, bq, bk, sk):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, :1]
    delta = delta_ref[0, 0][:, :1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qi = pl.program_id(2) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        kj = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qi >= kj, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_acc[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kb == (sk // bk) - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "n_rep", "bq", "bk",
                                             "interpret"))
def _flash_bwd(q, k, v, out, lse, do, *, causal, n_rep, bq, bk, interpret):
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    scale = 1.0 / (d ** 0.5)
    delta = jnp.broadcast_to(
        jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                axis=-1, keepdims=True), lse.shape)       # [B, H, Sq, LANES]

    # dk/dv: one kv-head per grid row; sweep (rep, Sq-blocks) innermost.
    grid_kv = (b, hkv, sk // bk, n_rep * (sq // bq))
    nqb = sq // bq

    def qmap(b_, h_, j, i):
        return (b_, h_ * n_rep + i // nqb, i % nqb, 0)

    def lmap(b_, h_, j, i):
        return (b_, h_ * n_rep + i // nqb, i % nqb, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, sq=sq, n_rep=n_rep),
        grid=grid_kv,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), qmap),                      # q
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bq, d), qmap),                      # do
            pl.BlockSpec((1, 1, bq, LANES), lmap),                  # lse
            pl.BlockSpec((1, 1, bq, LANES), lmap),                  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),   # dk accumulator
            pltpu.VMEM((bk, d), jnp.float32),   # dv accumulator
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, sk=sk),
        grid=(b, h, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)[0]
    return dq, dk, dv


# --------------------------------------------------------------------------
# public API (custom_vjp)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, n_rep: int = 1,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False):
    """q [B,H,Sq,D]; k, v [B,Hkv,Sk,D]; H = Hkv * n_rep.  Returns [B,H,Sq,D].

    Sq % bq == 0 and Sk % bk == 0 required (pad upstream).
    """
    out, _ = _flash_fwd(q, k, v, causal=causal, n_rep=n_rep, bq=bq, bk=bk,
                        interpret=interpret)
    return out


def _fa_fwd(q, k, v, causal, n_rep, bq, bk, interpret):
    out, lse = _flash_fwd(q, k, v, causal=causal, n_rep=n_rep, bq=bq,
                          bk=bk, interpret=interpret)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, n_rep, bq, bk, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, causal=causal,
                            n_rep=n_rep, bq=bq, bk=bk, interpret=interpret)
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)
