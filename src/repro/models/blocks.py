"""Decoder/encoder block variants with pre-norm residuals.

A block is (init, apply_full, apply_decode) where apply_full handles
train/prefill (full sequence, returns cache) and apply_decode consumes a
cache for one-token serving.  Families:

  dense / vlm / audio-decoder : GQA attention + SwiGLU MLP
  moe                         : GQA-or-MLA attention + MoE FFN
  ssm                         : Mamba2 mixer only
  audio-encoder               : non-causal GQA + MLP (no cache)

The zamba2 hybrid shared block is a dense block with its own init reused
at every application site (weights shared, caches per site) — assembled
in transformer.py.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import Params, mlp, mlp_init, rmsnorm, rmsnorm_init

# numpy, not jnp: importing the model must not start a JAX backend
# (`launch.serve.exact_rounding` sets XLA flags before that).
ZERO_AUX = {"load_balance": np.float32(0), "router_z": np.float32(0)}


# --- dense -------------------------------------------------------------------

def dense_block_init(key, cfg, *, dtype=jnp.float32) -> Params:
    k1, k2 = jax.random.split(key)
    bl = cfg.bitlinear in ("ffn", "all")
    return {"attn_norm": rmsnorm_init(cfg.d_model),
            "attn": attn.gqa_init(k1, cfg, dtype=dtype),
            "mlp_norm": rmsnorm_init(cfg.d_model),
            "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype,
                            bitlinear_on=bl)}


def _sp(x, cfg):
    """Sequence-parallel residual stream: constrain [B,S,D] to S-over-
    `model` (Megatron SP).  GSPMD then all-gathers (bf16) entering each
    TP block and reduce-scatters its row-parallel partial sums — half
    the wire bytes of the all-reduce it replaces — while norms/residual
    adds (and their f32 internals) run on 1/TP of the sequence."""
    if getattr(cfg, "seq_parallel", True) and x.ndim == 3 and x.shape[1] > 1:
        from .layers import constrain
        return constrain(x, "dp", "model")
    return x


def dense_block(p: Params, cfg, x, positions, *, window=0, causal=True
                ) -> Tuple[jax.Array, Dict, Dict]:
    x = _sp(x, cfg)
    if causal:
        a, cache = attn.gqa_attend(p["attn"], cfg, rmsnorm(p["attn_norm"], x),
                                   positions, window)
    else:  # encoder: full bidirectional attention
        a, cache = _bidir_attend(p["attn"], cfg, rmsnorm(p["attn_norm"], x),
                                 positions)
    x = x + _sp(a, cfg)
    x = x + _sp(mlp(p["mlp"], rmsnorm(p["mlp_norm"], x)), cfg)
    return x, cache, ZERO_AUX


def _bidir_attend(p, cfg, x, positions):
    b, s, _ = x.shape
    q, k, v = attn._qkv(p, cfg, x, positions)
    mask = jnp.ones((1, s, s), bool)
    out = attn._sdpa(q, k, v, mask, cfg.n_heads // cfg.n_kv_heads)
    from .layers import linear
    return linear(p["wo"], out.reshape(b, s, -1)), {"k": k, "v": v}


def dense_block_decode(p: Params, cfg, x, cache, pos, *, window=0
                       ) -> Tuple[jax.Array, Dict]:
    a, cache = attn.gqa_decode(p["attn"], cfg, rmsnorm(p["attn_norm"], x),
                               cache, pos, window)
    x = x + a
    x = x + mlp(p["mlp"], rmsnorm(p["mlp_norm"], x))
    return x, cache


# --- moe ---------------------------------------------------------------------

def moe_block_init(key, cfg, *, dtype=jnp.float32) -> Params:
    k1, k2 = jax.random.split(key)
    a = (attn.mla_init(k1, cfg, dtype=dtype) if cfg.mla
         else attn.gqa_init(k1, cfg, dtype=dtype))
    return {"attn_norm": rmsnorm_init(cfg.d_model), "attn": a,
            "mlp_norm": rmsnorm_init(cfg.d_model),
            "moe": moe_mod.moe_init(k2, cfg, dtype=dtype)}


def moe_block(p: Params, cfg, x, positions, *, window=0
              ) -> Tuple[jax.Array, Dict, Dict]:
    at = attn.mla_attend if cfg.mla else attn.gqa_attend
    x = _sp(x, cfg)
    a, cache = at(p["attn"], cfg, rmsnorm(p["attn_norm"], x), positions,
                  window)
    x = x + _sp(a, cfg)
    y, aux = moe_mod.moe_ffn(p["moe"], cfg, rmsnorm(p["mlp_norm"], x))
    return x + _sp(y, cfg), cache, aux


def moe_block_decode(p: Params, cfg, x, cache, pos, *, window=0
                     ) -> Tuple[jax.Array, Dict]:
    at = attn.mla_decode if cfg.mla else attn.gqa_decode
    a, cache = at(p["attn"], cfg, rmsnorm(p["attn_norm"], x), cache, pos,
                  window)
    x = x + a
    y, _ = moe_mod.moe_ffn(p["moe"], cfg, rmsnorm(p["mlp_norm"], x))
    return x + y, cache


# --- ssm ---------------------------------------------------------------------

def ssm_block_init(key, cfg, *, dtype=jnp.float32) -> Params:
    return {"norm": rmsnorm_init(cfg.d_model),
            "mixer": ssm_mod.ssm_init(key, cfg, dtype=dtype)}


def ssm_block(p: Params, cfg, x, positions=None, *, window=0
              ) -> Tuple[jax.Array, Dict, Dict]:
    # Pure-SSM archs run sequence-parallel end-to-end (weights are
    # replicated — see runtime/sharding.py): the whole mixer, conv halo
    # included, stays S-local.  Hybrids keep the residual unsharded
    # (their interleaved attention re-gathers S anyway).
    if cfg.family == "ssm":
        x = _sp(x, cfg)
    y, cache = ssm_mod.ssm_mix(p["mixer"], cfg, rmsnorm(p["norm"], x))
    return x + y, cache, ZERO_AUX


def ssm_block_decode(p: Params, cfg, x, cache, pos=None, *, window=0
                     ) -> Tuple[jax.Array, Dict]:
    y, cache = ssm_mod.ssm_decode(p["mixer"], cfg, rmsnorm(p["norm"], x),
                                  cache)
    return x + y, cache


# --- whisper decoder block (self + cross) ------------------------------------

def xdec_block_init(key, cfg, *, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"self_norm": rmsnorm_init(cfg.d_model),
            "self": attn.gqa_init(k1, cfg, dtype=dtype),
            "cross_norm": rmsnorm_init(cfg.d_model),
            "cross": attn.cross_init(k2, cfg, dtype=dtype),
            "mlp_norm": rmsnorm_init(cfg.d_model),
            "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, dtype=dtype)}


def xdec_block(p: Params, cfg, x, positions, enc, *, window=0
               ) -> Tuple[jax.Array, Dict, Dict]:
    a, self_cache = attn.gqa_attend(p["self"], cfg,
                                    rmsnorm(p["self_norm"], x), positions,
                                    window)
    x = x + a
    xkv = attn.cross_kv(p["cross"], cfg, enc)
    x = x + attn.cross_attend(p["cross"], cfg, rmsnorm(p["cross_norm"], x),
                              xkv)
    x = x + mlp(p["mlp"], rmsnorm(p["mlp_norm"], x))
    return x, {"self": self_cache, "cross": xkv}, ZERO_AUX


def xdec_block_decode(p: Params, cfg, x, cache, pos, *, window=0
                      ) -> Tuple[jax.Array, Dict]:
    a, self_cache = attn.gqa_decode(p["self"], cfg,
                                    rmsnorm(p["self_norm"], x),
                                    cache["self"], pos, window)
    x = x + a
    x = x + attn.cross_attend(p["cross"], cfg, rmsnorm(p["cross_norm"], x),
                              cache["cross"])
    x = x + mlp(p["mlp"], rmsnorm(p["mlp_norm"], x))
    return x, {"self": self_cache, "cross": cache["cross"]}
