"""Foundational layers: norms, dense, rotary, MLP, and BitLinear.

Pure-functional: every layer is (init(key, ...) -> params, apply(params, x)).
Params are plain dicts so they stack cleanly under scan-over-layers and
shard by path-pattern rules (runtime/sharding.py).

BitLinear is the paper's technique as a first-class layer: weights are
sign-binarized with a per-output-channel scale (XNOR-Net).  Training uses
a straight-through estimator over the dense shadow weights; serving can
run from bit-packed weights via the XNOR-popcount kernel (32x weight
compression — the DRIM "operands live in the memory array" insight mapped
to HBM).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

Params = Dict[str, Any]


# --- DRIM serving engine routing --------------------------------------------

# Ambient serving state installed by `serving_engine(...)`.  None engine
# means the native XLA path (the EngineRegistry "tpu" comparator's
# contender); a device engine name routes every BitLinear matmul below
# through the drim.jit carry-save pipeline on the simulated fleet.
_SERVING: Dict[str, Any] = {"engine": None, "n_queues": None, "geom": None}


@contextlib.contextmanager
def serving_engine(engine: Optional[str] = None, *,
                   n_queues: Optional[int] = None, geom=None):
    """Route BitLinear matmuls through the DRIM pipeline for the scope.

    `engine` is any `pim.compiler.ENGINE_REGISTRY` name: None or "tpu"
    keeps today's native path; "resident" / "queued" / "pallas" /
    "baseline" make `bitlinear` / `bitlinear_packed` execute their sign
    GEMM on the simulated fleet via `pim.bnn.serve_bnn_matmul` —
    traced once per reduction width, lowered once per engine signature
    (`compiler.lower_cached`).  DRIM engines execute host-side, so the
    decode step must run eagerly (`models.decode_step_eager`); tracing
    a BitLinear under an active DRIM engine raises RuntimeError.
    """
    if engine is not None:
        from repro.pim.compiler import get_engine
        eng = get_engine(engine)          # fail fast on unknown names
        if not eng.device:
            engine = None                 # "tpu" == the native path
    prev = dict(_SERVING)
    _SERVING.update(engine=engine, n_queues=n_queues, geom=geom)
    try:
        yield
    finally:
        _SERVING.update(prev)


def serving_engine_name() -> Optional[str]:
    """The active DRIM serving engine, or None for the native path."""
    return _SERVING["engine"]


def _drim_gemm(x: jax.Array, wb_bits: np.ndarray) -> jax.Array:
    """x [..., K] activations vs wb_bits [N, K] weight sign bits, as a
    ±1 dot on the DRIM fleet; returns [..., N] int32 (exact)."""
    from repro.pim.bnn import serve_bnn_matmul
    if isinstance(x, jax.core.Tracer):
        raise RuntimeError(
            f"BitLinear is routed through DRIM serving engine "
            f"{_SERVING['engine']!r}, which executes host-side — the "
            "decode step must run eagerly (models.decode_step_eager / "
            "launch.serve --engine), not under jit/scan/vmap")
    lead = x.shape[:-1]
    xb = np.asarray(kops.sign_bits(x.astype(jnp.float32))) \
        .reshape(-1, x.shape[-1])
    dot = serve_bnn_matmul(xb, wb_bits, engine=_SERVING["engine"],
                           geom=_SERVING["geom"],
                           n_queues=_SERVING["n_queues"])
    return jnp.asarray(dot, jnp.int32).reshape(*lead, wb_bits.shape[0])


def ambient_mesh():
    """The mesh installed by the enclosing `with mesh:` context, or None.

    Model code stays mesh-agnostic; shard_map-based blocks (the MoE EP
    path) fetch the mesh here and fall back to constraint-based or local
    execution when there is none (CPU smoke tests).
    """
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from jax.interpreters import pxla
        m = pxla.thread_resources.env.physical_mesh
    return None if m.empty else m


def constrain(x: jax.Array, *spec) -> jax.Array:
    """Best-effort sharding constraint inside model code.

    Tries the full ("pod","data") DP grouping first, then "data"-only,
    and silently no-ops when there is no mesh in context (CPU smoke
    tests) — model code stays mesh-agnostic while giving GSPMD the
    dispatch boundaries it cannot infer (e.g. the MoE all-to-all).
    Entries named "dp" expand to the DP axes of the context mesh.
    """
    from jax.sharding import PartitionSpec as P
    for dp in (("pod", "data"), "data"):
        full = tuple(dp if e == "dp" else e for e in spec)
        try:
            return jax.lax.with_sharding_constraint(x, P(*full))
        except (RuntimeError, ValueError):
            continue
    return x


def _he(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


# --- norms -------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dt)


# --- dense / BitLinear -------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32) -> Params:
    p = {"kernel": _he(key, (d_in, d_out), dtype)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def dense(params: Params, x: jax.Array) -> jax.Array:
    y = x @ params["kernel"].astype(x.dtype)
    if "bias" in params:
        y = y + params["bias"].astype(x.dtype)
    return y


def bitlinear_init(key, d_in: int, d_out: int, *, bias: bool = False,
                   dtype=jnp.float32) -> Params:
    # "bkernel" (vs "kernel") marks the layer as binarized for apply +
    # sharding rules without adding non-differentiable marker leaves.
    p = dense_init(key, d_in, d_out, bias=bias, dtype=dtype)
    p["bkernel"] = p.pop("kernel")
    return p


def _ste_sign(w: jax.Array) -> jax.Array:
    """sign(w) with straight-through gradient (identity inside clip)."""
    s = jnp.where(w >= 0, 1.0, -1.0).astype(w.dtype)
    return w + jax.lax.stop_gradient(s - w)


def bitlinear(params: Params, x: jax.Array) -> jax.Array:
    """XNOR-Net linear: y = (sign(x_c) xnor-dot sign(w)) * alpha.

    Dense STE formulation (training + AOT analysis): binarized operands go
    through the regular MXU dot; per-output-channel alpha = mean|w| keeps
    the magnitude.  Bit-packed serving path: bitlinear_packed below.
    """
    w = params["bkernel"]
    alpha = jnp.mean(jnp.abs(w), axis=0).astype(x.dtype)  # [d_out]
    if _SERVING["engine"] is not None:
        if isinstance(w, jax.core.Tracer):
            raise RuntimeError(
                "BitLinear weights are traced under an active DRIM "
                "serving engine — run the decode step eagerly "
                "(models.decode_step_eager)")
        wb_bits = np.asarray(kops.sign_bits(w)).T       # [d_out, d_in]
        # Exact int dot -> x.dtype: identical rounding to the bf16 STE
        # matmul below (the dot is an exact small integer either way),
        # so engine choice never changes served tokens at temp 0.
        y = _drim_gemm(x, wb_bits).astype(x.dtype) * alpha
    else:
        wb = _ste_sign(w).astype(x.dtype)
        xb = _ste_sign(x.astype(jnp.float32)).astype(x.dtype)
        y = (xb @ wb) * alpha
    if "bias" in params:
        y = y + params["bias"].astype(x.dtype)
    return y


def pack_bitlinear(params: Params) -> Params:
    """Offline conversion: dense shadow weights -> packed serving weights.

    Works on a single layer ([d_in, d_out]) or scan-stacked leaves
    ([L, d_in, d_out] — what `launch.serve --packed` converts): the
    reduction dim is always axis -2, packed little-endian into uint32
    words along the last axis.
    """
    w = params["bkernel"]                            # [..., d_in, d_out]
    wt = jnp.swapaxes(w, -1, -2)                     # [..., d_out, d_in]
    return {
        "w_packed": kops.pack_signs(wt),             # [..., d_out, ceil(d_in/32)]
        "alpha": jnp.mean(jnp.abs(w), axis=-2),      # [..., d_out]
        "k_bits": jnp.full(w.shape[:-2], w.shape[-2], jnp.int32),
        **({"bias": params["bias"]} if "bias" in params else {}),
    }


def bitlinear_packed(packed: Params, x: jax.Array, k_bits: int) -> jax.Array:
    """Serving path: activations sign-packed on the fly, weights stay
    bit-packed in HBM (32x smaller reads — decode is weight-BW bound).

    Under an active DRIM `serving_engine`, the packed words are
    unpacked host-side to sign bits and the GEMM runs on the simulated
    fleet instead of the XNOR-popcount TPU kernel.
    """
    if _SERVING["engine"] is not None:
        wp = packed["w_packed"]
        if isinstance(wp, jax.core.Tracer):
            raise RuntimeError(
                "packed BitLinear weights are traced under an active "
                "DRIM serving engine — run the decode step eagerly "
                "(models.decode_step_eager)")
        wb_bits = kops.unpack_sign_bits_np(wp, k_bits)   # [d_out, K]
        y = _drim_gemm(x, wb_bits).astype(x.dtype)
    else:
        y = kops.binary_matmul(x, packed["w_packed"], k_bits,
                               dtype=x.dtype)
    y = y * packed["alpha"].astype(x.dtype)
    if "bias" in packed:
        y = y + packed["bias"].astype(x.dtype)
    return y


def linear_init(key, d_in, d_out, *, bias=False, dtype=jnp.float32,
                bitlinear_on: bool = False) -> Params:
    return (bitlinear_init if bitlinear_on else dense_init)(
        key, d_in, d_out, bias=bias, dtype=dtype)


def linear(params: Params, x: jax.Array) -> jax.Array:
    if "bkernel" in params:
        return bitlinear(params, x)
    if "w_packed" in params:
        # k_bits must be static for the packed kernel; the activation's
        # feature dim is the reduction width by construction (the
        # "k_bits" leaf is traced under jit and only kept for audit).
        return bitlinear_packed(params, x, x.shape[-1])
    return dense(params, x)


# --- rotary embeddings -------------------------------------------------------

def rope_frequencies(d_head: int, theta: float) -> jax.Array:
    # A constant table made on the host.  As a jnp expression it would
    # be folded on the host under jit but run on the device eagerly, and
    # on a TPU the two pow results differ in the last bits — enough for
    # the eager and the jitted decode step to pick different tokens.
    return jnp.asarray(1.0 / theta ** (np.arange(0, d_head, 2) / d_head),
                       jnp.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, d_head]; positions [..., S] (int)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # [d/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, d/2]
    cos = jnp.cos(angles)[..., None, :]                      # [..., S, 1, d/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# --- gated MLP (SwiGLU) ------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, *, dtype=jnp.float32,
             bitlinear_on: bool = False) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": linear_init(k1, d_model, d_ff, dtype=dtype,
                            bitlinear_on=bitlinear_on),
        "up": linear_init(k2, d_model, d_ff, dtype=dtype,
                          bitlinear_on=bitlinear_on),
        "down": linear_init(k3, d_ff, d_model, dtype=dtype,
                            bitlinear_on=bitlinear_on),
    }


def mlp(params: Params, x: jax.Array) -> jax.Array:
    return linear(params["down"],
                  jax.nn.silu(linear(params["gate"], x))
                  * linear(params["up"], x))
