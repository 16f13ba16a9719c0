"""int8 gradient compression: quantisation error bounds, error feedback,
and the shard_map int8 all-reduce (subprocess with 8 fake devices)."""

import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.compression import (compress_decompress, dequantize,
                                        init_residuals, quantize)

_REPO = pathlib.Path(__file__).resolve().parents[1]


def test_quantize_bounds():
    g = jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 3.0
    q, scale = quantize(g)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize(q, scale) - g))
    assert err.max() <= float(scale) / 2 + 1e-6  # round-to-nearest bound


def test_error_feedback_reduces_bias():
    """With EF, the RUNNING SUM of compressed grads tracks the true sum
    (quantisation error is carried, not lost)."""
    key = jax.random.PRNGKey(1)
    grads = {"w": jax.random.normal(key, (32, 32)) * 0.01}
    res = init_residuals(grads)
    total_hat = np.zeros((32, 32), np.float32)
    total_true = np.zeros((32, 32), np.float32)
    for i in range(20):
        g = {"w": grads["w"] * (1.0 + 0.1 * i)}
        g_hat, res = compress_decompress(g, res)
        total_hat += np.asarray(g_hat["w"], np.float32)
        total_true += np.asarray(g["w"], np.float32)
    # residual carries what the sum is missing
    gap = np.abs(total_true - total_hat - np.asarray(res["w"]))
    assert gap.max() < 1e-4


def test_compress_is_noop_for_zero():
    g = {"w": jnp.zeros((8, 8))}
    g_hat, res = compress_decompress(g, init_residuals(g))
    np.testing.assert_array_equal(np.asarray(g_hat["w"]), 0.0)
    np.testing.assert_array_equal(np.asarray(res["w"]), 0.0)


def test_int8_psum_multidevice():
    """shard_map int8 all-reduce over a real 8-device 'pod' axis matches
    the f32 mean within quantisation tolerance (subprocess: fake devices)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.parallel.compression import int8_psum

        mesh = make_mesh((8,), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 128)) * 2.0

        f = jax.shard_map(lambda a: int8_psum(a, "pod"), mesh=mesh,
                          in_specs=P("pod"), out_specs=P("pod"))
        got = np.asarray(f(x))
        want = np.broadcast_to(np.asarray(x).mean(0, keepdims=True), (8, 128))
        err = np.abs(got - np.repeat(want[:1], 8, 0))
        scale = np.abs(np.asarray(x)).max() / 127.0
        assert err.max() <= scale * 1.5, (err.max(), scale)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=_REPO, timeout=300)
    assert "OK" in out.stdout, out.stderr[-2000:]
