"""Wire-level proof of the 1-bit EF compressed all-reduce.

The paper's bulk bit-wise payload applied to distributed optimization:
under shard_map, `compressed_allreduce` must (a) put an INT8 all-reduce
on the wire (float sign payloads get promoted back to f32 by XLA's
reduction-precision passes — that was a refuted first attempt), and
(b) decode to mean(signs) * mean(scales) with the EF residual kept
locally.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from jax.sharding import PartitionSpec as P  # noqa: E402
from repro.optim.compress import (compress_grad,  # noqa: E402
                                  compressed_allreduce)

needs_devices = pytest.mark.skipif(len(jax.devices()) < 8,
                                   reason="needs 8 fake devices")


def _run():
    mesh = jax.make_mesh((8,), ("data",))
    g = jnp.arange(64, dtype=jnp.float32).reshape(8, 8) - 30.0
    errs = jnp.zeros((8, 8), jnp.float32)

    def f(g, e):
        def body(gl, el):
            m, ne = compressed_allreduce({"g": gl[0]}, {"g": el[0]},
                                         ("data",))
            return m["g"][None], ne["g"][None]
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P("data"), P("data")),
                             out_specs=(P("data"), P("data")))(g, e)

    with mesh:
        hlo = jax.jit(f).lower(g, errs).compile().as_text()
        mean, new_err = jax.jit(f)(g, errs)
    return g, errs, hlo, mean, new_err


@needs_devices
def test_wire_payload_is_int8():
    _, _, hlo, _, _ = _run()
    ars = [ln for ln in hlo.splitlines()
           if re.search(r"all-reduce(-start)?\(", ln)]
    assert any("s8[" in a for a in ars), ars
    # and no full-size f32 gradient AR remains (scales are scalars)
    assert not any(re.search(r"f32\[8\]", a) for a in ars), ars


@needs_devices
def test_decode_semantics_and_error_feedback():
    g, errs, _, mean, new_err = _run()
    # host copies: indexing an array sharded over an explicit mesh axis
    # needs an out_sharding under jax 0.9
    mean, new_err = np.asarray(mean), np.asarray(new_err)
    signs, scales = [], []
    for i in range(8):
        s_, sc_, e_ = compress_grad(g[i], errs[i])
        signs.append(np.asarray(s_, np.float32))
        scales.append(float(sc_))
        np.testing.assert_allclose(new_err[i], np.asarray(e_),
                                   rtol=1e-5, atol=1e-5)
    want = np.mean(signs, 0) * np.mean(scales)
    np.testing.assert_allclose(mean[0], want,
                               rtol=1e-5, atol=1e-5)
