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
        # latent mode at DeepSeek-V2 widths: 16 heads over one head of
        # 512-wide latent rows plus 64-wide rope keys kept transposed
        "paged_decode_attention_latent": (
            lambda q, c, r, t, n, i: ops.paged_decode_attention(
                q, c, None, t, n, i, k_rope_pool=r, scale=0.1147, **kw),
            [((16, 16, 576), bf), ((4, 33, 1, page, 512), bf),
             ((4, 33, 1, 64, page), bf), ((16, 2), i32), ((16,), i32),
             ((), i32)]),
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
                                    "paged_decode_attention_latent",
                                    "mfma_gemm", "moe_gmm", "mamba2_ssd"])
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, specs = _cases()[kernel]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_folds_mistral_nemo_heads_on_v5e(one_chip):
    """At mistral-nemo-12b's decode shape (23 slots, 8 KV heads of 4
    query heads, hd 128, 8 pages of 512 in a 10-layer stack of 185
    blocks) the decode step's dispatch decision stays on the kernel,
    whose plan reads all 8 heads of a page in one grid step, and that
    folded kernel compiles for a described v5e."""
    from repro.kernels import dispatch
    cfg = get_config("mistral-nemo-12b")
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    B, NB, page, L, P = 23, 8, 512, 10, 185
    dec = dispatch.decide(
        "paged_decode_attention",
        {"B": B, "T": NB * page, "H": H, "KV": KV, "hd": hd, "page": page},
        dtype=jnp.bfloat16, device=_DEVICE)
    assert dec.use_kernel, dec.reason
    assert dec.plan.blocks == {"block_kv": page, "kv_step": KV}
    assert dec.plan.grid == (B, NB)
    bf, i32 = jnp.bfloat16, jnp.int32
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in [((B, H, hd), bf),
                              ((L, P, KV, page, hd), bf),
                              ((L, P, KV, page, hd), bf),
                              ((B, NB), i32), ((B,), i32), ((), i32)]]
    compiled = jax.jit(
        lambda q, k, v, t, n, i: ops.paged_decode_attention(
            q, k, v, t, n, i, plan=dec.plan, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_deepseek_v2_lite_share_steps_fit_one_v5e(step, one_chip,
                                                  monkeypatch):
    """The engine's decode and prefill programs for DeepSeek-V2-Lite's
    one-chip expert share (all 27 layers, 8 of 64 experts, 16 slots x
    16384 rows, 513 blocks of 512) compile for a described v5e: the
    weights, the latent pools and the step's temporaries fit the chip,
    the pools are donated (aliased to the outputs), and no temporary is
    pool-sized — the layer scan writes and reads the pool stacks in
    place.  Both kernels are in the programs."""
    import dataclasses

    from repro.kernels import compat, dispatch
    from repro.models.model import init_params
    from repro.serve.paged_cache import init_pools
    from repro.serve.paged_engine import serve_steps
    # compile the programs the chip runs: bf16 dots, kernels not
    # interpreted (the CPU backend would pick both otherwise)
    monkeypatch.setenv("REPRO_CPU_F32_DOTS", "0")
    monkeypatch.setattr(compat, "default_interpret", lambda: False)
    base = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(
        base, use_pallas=True, pallas_device=_DEVICE,
        moe=dataclasses.replace(base.moe, n_held=8, held_offset=0))
    B, max_len, page = 16, 16384, 512
    NB = max_len // page
    P = B * NB + 1

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: init_params(cfg, k),
                                    jax.random.PRNGKey(0)))
    pools = on_chip(jax.eval_shape(lambda: init_pools(cfg, P, page)))
    pool_bytes = sum(a.size * 2 for a in jax.tree.leaves(pools))
    assert pool_bytes == P * 27 * page * 576 * 2          # 8.17 GB
    i32 = jnp.int32
    decode, prefill = serve_steps(cfg, aligned=True)
    if step == "decode":
        fn, args = decode, (jax.ShapeDtypeStruct((B, 1), i32),
                            jax.ShapeDtypeStruct((B, NB), i32),
                            jax.ShapeDtypeStruct((B,), i32))
    else:
        fn, args = prefill, (jax.ShapeDtypeStruct((1, 512), i32),
                             jax.ShapeDtypeStruct((1, NB), i32),
                             jax.ShapeDtypeStruct((1,), i32),
                             jax.ShapeDtypeStruct((1,), i32))
    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 for a in args)
    with dispatch.decision_scope() as decs:
        lowered = fn.lower(params, pools, *args)
    assert decs["moe_gmm"].use_kernel
    if step == "decode":
        assert decs["paged_decode_attention"].use_kernel
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes          # donated in place
    assert ma.temp_size_in_bytes < pool_bytes / 20       # no pool copy
    # weights 6.22 GB + pools 8.17 GB + temporaries under the ~15.75 GiB
    # the TPU compiler gives a program on one 16 GiB v5e
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < 15.75 * 2**30
    assert "tpu_custom_call" in compiled.as_text()
