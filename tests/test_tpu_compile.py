"""Compile-only guards: every catalog kernel lowers through Mosaic for a
described TPU v5e chip, at the widths of the model that uses it.

Interpret mode (every other kernel test) cannot see the TPU compiler's
refusals: block shapes off the (8, 128) tiling, matmuls without a 32-bit
accumulator, VMEM overflow.  These tests compile each kernel with
``interpret=False`` against a ``v5e:2x2`` topology described without a
chip, and assert the compiled program holds the kernel
(``tpu_custom_call``).  Nothing runs, so no result or time is checked.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and the
fixture keeps every other test worker from trying.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

_DEVICE = "tpu_v5e"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cases():
    """kernel -> (fn, [(shape, dtype)]) at each model's published widths."""
    q7 = get_config("qwen2-7b")
    H, KV, hd = q7.n_heads, q7.n_kv_heads, q7.hd
    moe = get_config("qwen3-moe-235b-a22b")
    ssm_cfg = get_config("mamba2-370m")
    s = ssm_cfg.ssm
    nh = s.expand * ssm_cfg.d_model // s.head_dim
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    B, S, T, page = 8, 512, 2048, 512
    pool = (B * T // page + 1, KV, page, hd)       # + the null block
    kw = dict(device=_DEVICE, interpret=False)
    return {
        "flash_attention": (
            lambda q, k, v: ops.flash_attention(q, k, v, **kw),
            [((1, S, H, hd), bf), ((1, S, KV, hd), bf),
             ((1, S, KV, hd), bf)]),
        "decode_attention": (
            lambda q, k, v, n: ops.decode_attention(q, k, v, n, **kw),
            [((B, H, hd), bf), ((B, T, KV, hd), bf), ((B, T, KV, hd), bf),
             ((B,), i32)]),
        "paged_decode_attention": (
            lambda q, k, v, t, n: ops.paged_decode_attention(
                q, k, v, t, n, **kw),
            [((B, H, hd), bf), (pool, bf), (pool, bf),
             ((B, T // page), i32), ((B,), i32)]),
        # the serve engine's read: layer i of a stack of layer pools
        "paged_decode_attention_stacked": (
            lambda q, k, v, t, n, i: ops.paged_decode_attention(
                q, k, v, t, n, i, **kw),
            [((B, H, hd), bf), ((4,) + pool, bf), ((4,) + pool, bf),
             ((B, T // page), i32), ((B,), i32), ((), i32)]),
        "mfma_gemm": (
            lambda a, b, c: ops.mfma_gemm(a, b, c, **kw),
            [((S, q7.d_model), bf), ((q7.d_model, q7.d_ff), bf),
             ((S, q7.d_ff), f32)]),
        "moe_gmm": (
            lambda x, w: ops.moe_gmm(x, w, **kw),
            [((8, 256, moe.d_model), bf),
             ((8, moe.d_model, moe.moe.d_ff_expert), bf)]),
        "mamba2_ssd": (
            lambda x, dt, A, Bm, Cm: ops.mamba2_ssd(x, dt, A, Bm, Cm, **kw),
            [((1, S, nh, s.head_dim), bf), ((1, S, nh), f32), ((nh,), f32),
             ((1, S, s.n_groups, s.d_state), bf),
             ((1, S, s.n_groups, s.d_state), bf)]),
    }


@pytest.mark.parametrize("kernel", ["flash_attention", "decode_attention",
                                    "paged_decode_attention",
                                    "paged_decode_attention_stacked",
                                    "mfma_gemm", "moe_gmm", "mamba2_ssd"])
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, specs = _cases()[kernel]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
