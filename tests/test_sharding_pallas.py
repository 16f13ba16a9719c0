"""Sharded ``use_pallas=True`` parity: the Pallas kernels on the mesh.

Every catalog-backed mixer (flash attention, decode attention, Mamba-2
SSD, MoE grouped GEMM) must match its GSPMD reference when the kernels
execute under ``shard_map`` on an active mesh, and ``last_decisions()``
must prove the kernel path actually ran sharded — zero ``mesh-sharded``
fallbacks for shardable shapes.  The suite adapts to whatever host
topology exists: the CI mesh leg runs it with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (a (2, 4)
data x model mesh, so batch, heads and experts genuinely partition);
under plain tier-1 (one device) the mesh degenerates to (1, 1) and the
shard_map plumbing still executes with replicated specs.  A subprocess
test pins the real 8-device topology into tier-1 itself, and the
fallback-contract tests pin when the legacy ``mesh-sharded`` reason is
still allowed to appear: kernels without a logical-axis contract and
local shards that genuinely fail the tiling/VMEM contract.
"""

import dataclasses
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.arch import get_device
from repro.kernels import dispatch as kdispatch
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig, MoESpec, SSMSpec
from repro.launch.mesh import make_mesh
from repro.parallel.api import set_mesh

KEY = jax.random.PRNGKey(0)
_REPO = pathlib.Path(__file__).resolve().parents[1]

_TOL = {"float32": dict(rtol=2e-3, atol=2e-3),
        "bfloat16": dict(rtol=5e-2, atol=5e-2)}


def _mesh():
    """Largest (data, model) mesh the host supports; (1, 1) on one CPU."""
    n = jax.device_count()
    model = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 1)
    return make_mesh((n // model, model), ("data", "model"))


def _close(got, want, dtype="float32"):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_TOL[dtype])


def _assert_kernel_sharded(decs, kernel):
    dec = decs.get(kernel)
    assert dec is not None, f"{kernel}: no dispatch decision recorded"
    assert dec.use_kernel, f"{kernel}: fell back ({dec.reason})"
    assert dec.sharded and dec.plan is not None and dec.local_dims
    assert "mesh-sharded" not in dec.reason
    return dec


# ---------------------------------------------------------------------------
# mixer parity under the mesh
# ---------------------------------------------------------------------------

def _attn_cfgs(dtype="float32"):
    # 8 Q / 4 KV heads: both divide the 4-way model axis, so heads
    # genuinely shard on the 8-device topology (and the GQA ratio holds)
    cfg = ModelConfig(name="shard-parity", family="dense", n_layers=2,
                      d_model=256, n_heads=8, n_kv_heads=4, d_ff=256,
                      vocab_size=512, head_dim=32, dtype=dtype)
    return cfg, dataclasses.replace(cfg, use_pallas=True)


@pytest.mark.parametrize("S,dtype", [(128, "float32"), (100, "float32"),
                                     (128, "bfloat16")])
def test_attn_train_sharded_parity(S, dtype):
    """S=100 is the ragged case: each shard pads/masks its local block."""
    cfg, cfgp = _attn_cfgs(dtype)
    mesh = _mesh()
    w = attn.init_attn(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, S, cfg.d_model),
                          jnp.float32).astype(
                              jnp.bfloat16 if dtype == "bfloat16"
                              else jnp.float32)
    pos = jnp.arange(S)
    with set_mesh(mesh):
        with kdispatch.decision_scope() as decs:
            y_pal = attn.attn_train(cfgp, w, x, pos)
        dec = _assert_kernel_sharded(decs, "flash_attention")
        mm = mesh.shape["model"]
        assert dec.local_dims["H"] == cfg.n_heads // mm
        assert dec.local_dims["KV"] == cfg.n_kv_heads // mm
        y_ref = attn.attn_train(cfg, w, x, pos)
    _close(y_pal, y_ref, dtype)


def test_attn_decode_sharded_parity():
    cfg, cfgp = _attn_cfgs()
    mesh = _mesh()
    w = attn.init_attn(cfg, KEY)
    cache = attn.init_attn_cache(cfg, 4, 128)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 1, cfg.d_model),
                          jnp.float32)
    with set_mesh(mesh):
        with kdispatch.decision_scope() as decs:
            y_pal, c_pal = attn.attn_decode(cfgp, w, x, cache,
                                            jnp.int32(37))
        dec = _assert_kernel_sharded(decs, "decode_attention")
        assert dec.local_dims["H"] == cfg.n_heads // mesh.shape["model"]
        y_ref, c_ref = attn.attn_decode(cfg, w, x, cache, jnp.int32(37))
    _close(y_pal, y_ref)
    np.testing.assert_array_equal(np.asarray(c_pal["k"]),
                                  np.asarray(c_ref["k"]))


@pytest.mark.parametrize("S", [64, 52])
def test_ssm_train_sharded_parity(S):
    """nh=8 heads shard; the single B/C group (G=1) broadcasts."""
    cfg = ModelConfig(name="shard-ssm", family="ssm", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=4, d_ff=0,
                      vocab_size=512, dtype="float32",
                      ssm=SSMSpec(d_state=16, head_dim=16, chunk=32))
    cfgp = dataclasses.replace(cfg, use_pallas=True)
    mesh = _mesh()
    w = ssm_mod.init_ssm(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, S, cfg.d_model),
                          jnp.float32)
    with set_mesh(mesh):
        with kdispatch.decision_scope() as decs:
            y_pal = ssm_mod.ssm_train(cfgp, w, x)
        dec = _assert_kernel_sharded(decs, "mamba2_ssd")
        assert dec.local_dims["nh"] == 8 // mesh.shape["model"]
        assert dec.local_dims["G"] == 1
        y_ref = ssm_mod.ssm_train(cfg, w, x)
    _close(y_pal, y_ref)


def test_moe_apply_sharded_parity():
    """E=8 experts shard over the model axis; the dispatch/combine
    gathers (the EP collectives) stay in the surrounding XLA program."""
    cfg = ModelConfig(name="shard-moe", family="moe", n_layers=2,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=512, head_dim=32, dtype="float32",
                      moe=MoESpec(n_experts=8, top_k=2, d_ff_expert=64))
    cfgp = dataclasses.replace(cfg, use_pallas=True)
    mesh = _mesh()
    w = moe_mod.init_moe(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 32, cfg.d_model),
                          jnp.float32)
    with set_mesh(mesh):
        with kdispatch.decision_scope() as decs:
            y_pal, aux_pal = moe_mod.moe_apply(cfgp, w, x)
        dec = _assert_kernel_sharded(decs, "moe_gmm")
        assert dec.local_dims["E"] == 8 // mesh.shape["model"]
        y_ref, aux_ref = moe_mod.moe_apply(cfg, w, x)
    _close(y_pal, y_ref)
    np.testing.assert_allclose(float(aux_pal), float(aux_ref), rtol=1e-5)


def test_sharded_kernels_survive_jit():
    """The launch path jits the step function: decisions still record at
    trace time and the shard_map kernels compile inside the jit."""
    cfg, cfgp = _attn_cfgs()
    mesh = _mesh()
    w = attn.init_attn(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 128, cfg.d_model),
                          jnp.float32)
    pos = jnp.arange(128)
    step = jax.jit(lambda x: attn.attn_train(cfgp, w, x, pos))
    with set_mesh(mesh):
        with kdispatch.decision_scope() as decs:
            y_pal = step(x)
        _assert_kernel_sharded(decs, "flash_attention")
        y_ref = attn.attn_train(cfg, w, x, pos)
    _close(y_pal, y_ref)


# ---------------------------------------------------------------------------
# fallback contract: when "mesh-sharded" may still appear
# ---------------------------------------------------------------------------

class _FakeMesh:
    """Duck-typed mesh (.shape only) — dispatch plans without devices."""

    def __init__(self, shape):
        self.shape = shape


def test_no_logical_contract_keeps_legacy_fallback():
    """paged_decode_attention has no logical map: a bare pallas_call is
    single-device, so the whole-op reference fallback survives."""
    with kdispatch.decision_scope():
        dec = kdispatch.decide(
            "paged_decode_attention",
            {"B": 2, "T": 512, "H": 4, "KV": 2, "hd": 32, "page": 128},
            sharded=True, mesh=_FakeMesh({"data": 2, "model": 4}))
    assert not dec.use_kernel
    assert "mesh-sharded" in dec.reason
    assert "GSPMD cannot partition" in dec.reason


def test_untileable_local_shard_falls_back_with_planner_reason():
    """A local shard whose working set busts VMEM is genuinely
    untileable: the fallback reason carries the planner's error."""
    tiny = get_device("tpu_v5e").derive("tpu_nano_vmem", vmem_bytes=1 << 10)
    with kdispatch.decision_scope():
        dec = kdispatch.decide(
            "flash_attention",
            {"B": 2, "S": 4096, "T": 4096, "H": 8, "KV": 4, "hd": 128},
            device=tiny, sharded=True,
            mesh=_FakeMesh({"data": 2, "model": 4}))
    assert not dec.use_kernel
    assert "mesh-sharded local shard" in dec.reason


def test_misaligned_local_shard_without_pad_falls_back():
    """pad=False keeps the strict tiling contract per shard: a ragged
    local dim is a recorded fallback, not an exception."""
    with kdispatch.decision_scope():
        dec = kdispatch.decide(
            "moe_gmm", {"E": 4, "C": 20, "K": 100, "N": 60},
            pad=False, sharded=True, mesh=_FakeMesh({"model": 4}))
    assert not dec.use_kernel
    assert "mesh-sharded local shard" in dec.reason


def test_shardable_shapes_never_hit_mesh_fallback():
    """The acceptance bar: for shardable shapes the sharded Decision is
    a kernel Decision — the blanket mesh-sharded fallback is gone."""
    with kdispatch.decision_scope() as decs:
        for kernel, shapes in (
            ("flash_attention", {"B": 4, "S": 128, "T": 128, "H": 8,
                                 "KV": 4, "hd": 32}),
            ("decode_attention", {"B": 4, "T": 128, "H": 8, "KV": 4,
                                  "hd": 32}),
            ("mamba2_ssd", {"B": 4, "S": 64, "nh": 8, "hd": 16, "ds": 16,
                            "G": 1}),
            ("moe_gmm", {"E": 8, "C": 64, "K": 128, "N": 128}),
        ):
            kdispatch.decide(kernel, shapes, sharded=True,
                             mesh=_FakeMesh({"data": 2, "model": 4}))
    assert all(d.use_kernel and d.sharded for d in decs.values()), \
        {k: d.reason for k, d in decs.items() if not d.use_kernel}


# ---------------------------------------------------------------------------
# the real 8-device topology, pinned into tier-1 via a subprocess
# ---------------------------------------------------------------------------

def test_sharded_parity_8_devices():
    """Heads shard 4-way and batch 2-way on a true (2, 4) host mesh; the
    kernel output matches the GSPMD reference and the decision record
    proves the shard_map path ran."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_CPU_F32_DOTS"] = "1"
        import sys; sys.path.insert(0, "src")
        import dataclasses
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.kernels import dispatch as kdispatch
        from repro.models import attention as attn
        from repro.models.config import ModelConfig
        from repro.launch.mesh import make_mesh
        from repro.parallel.api import set_mesh

        assert jax.device_count() == 8
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = ModelConfig(name="m", family="dense", n_layers=2,
                          d_model=256, n_heads=8, n_kv_heads=4, d_ff=256,
                          vocab_size=512, head_dim=32, dtype="float32")
        cfgp = dataclasses.replace(cfg, use_pallas=True)
        w = attn.init_attn(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, cfg.d_model),
                              jnp.float32)
        pos = jnp.arange(128)
        with set_mesh(mesh):
            with kdispatch.decision_scope() as decs:
                y_pal = attn.attn_train(cfgp, w, x, pos)
            dec = decs["flash_attention"]
            assert dec.use_kernel and dec.sharded, dec.reason
            assert dec.local_dims == {"B": 2, "S": 128, "T": 128, "H": 2,
                                      "KV": 1, "hd": 32}, dec.local_dims
            y_ref = attn.attn_train(cfg, w, x, pos)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=_REPO, timeout=600)
    assert "OK" in out.stdout, out.stderr[-2000:]
