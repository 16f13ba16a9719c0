"""Catalog-driven parity + planner contracts.

The interpret-mode numerical-parity sweep runs EVERY catalog kernel
against its ``kernels/ref.py`` oracle across fp32/bf16 with
planner-chosen tiles on EVERY registered device (mi200 -> tpu_v5p) —
the compute layer cannot silently rot for any (kernel, device, dtype)
cell again.  The planner contracts pin the acceptance criteria:
MXU-aligned, VMEM-budget-respecting tiles for every device, and the
scoreboard engine consuming the identical TilePlan the kernel executes.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.arch import get_device, list_devices
from repro.kernels import get_kernel, list_kernels, plan_for
from repro.kernels.plan import tile_align

RNG = np.random.RandomState(42)

DEVICES = list(list_devices())

#: Small but multi-tile shapes, MXU-aligned where the contract requires.
SHAPES = {
    "mfma_gemm": {"M": 128, "N": 128, "K": 256},
    "moe_gmm": {"E": 2, "C": 128, "K": 128, "N": 128},
    "flash_attention": {"B": 1, "S": 128, "T": 128, "H": 2, "KV": 1,
                        "hd": 64},
    "decode_attention": {"B": 1, "T": 256, "H": 4, "KV": 2, "hd": 32},
    "paged_decode_attention": {"B": 2, "T": 512, "H": 4, "KV": 2, "hd": 32,
                               "page": 128},
    "mamba2_ssd": {"B": 1, "S": 64, "nh": 2, "hd": 16, "ds": 16},
}

#: Big shapes for the alignment/budget contract (planner must tile, not
#: swallow, these).
BIG_SHAPES = {
    "mfma_gemm": {"M": 4096, "N": 4096, "K": 4096},
    "moe_gmm": {"E": 16, "C": 1024, "K": 4096, "N": 2048},
    "flash_attention": {"B": 8, "S": 4096, "T": 4096, "H": 32, "KV": 8,
                        "hd": 128},
    "decode_attention": {"B": 8, "T": 8192, "H": 32, "KV": 8, "hd": 128},
    "paged_decode_attention": {"B": 8, "T": 8192, "H": 32, "KV": 8,
                               "hd": 128, "page": 512},
    "mamba2_ssd": {"B": 8, "S": 4096, "nh": 32, "hd": 64, "ds": 128},
}


def _case(kernel: str, s, dt):
    """(op args, ref args) for one kernel; dtype applies to activations."""
    if kernel == "mfma_gemm":
        a = jnp.asarray(RNG.randn(s["M"], s["K"]), dt)
        b = jnp.asarray(RNG.randn(s["K"], s["N"]), dt)
        c = jnp.asarray(RNG.randn(s["M"], s["N"]), jnp.float32)
        return (a, b, c), (a, b, c)
    if kernel == "moe_gmm":
        x = jnp.asarray(RNG.randn(s["E"], s["C"], s["K"]), dt)
        w = jnp.asarray(RNG.randn(s["E"], s["K"], s["N"]), dt)
        return (x, w), (x, w)
    if kernel == "flash_attention":
        q = jnp.asarray(RNG.randn(s["B"], s["S"], s["H"], s["hd"]), dt)
        k = jnp.asarray(RNG.randn(s["B"], s["T"], s["KV"], s["hd"]), dt)
        v = jnp.asarray(RNG.randn(s["B"], s["T"], s["KV"], s["hd"]), dt)
        return (q, k, v), (q, k, v)
    if kernel == "decode_attention":
        q = jnp.asarray(RNG.randn(s["B"], s["H"], s["hd"]), dt)
        k = jnp.asarray(RNG.randn(s["B"], s["T"], s["KV"], s["hd"]), dt)
        v = jnp.asarray(RNG.randn(s["B"], s["T"], s["KV"], s["hd"]), dt)
        kv_len = jnp.int32(s["T"] - 63)
        return (q, k, v, kv_len), (q, k, v, kv_len)
    if kernel == "paged_decode_attention":
        page, B = s["page"], s["B"]
        nb = s["T"] // page
        P = B * nb + 1                       # + the reserved null block
        q = jnp.asarray(RNG.randn(B, s["H"], s["hd"]), dt)
        k_pool = jnp.asarray(RNG.randn(P, s["KV"], page, s["hd"]), dt)
        v_pool = jnp.asarray(RNG.randn(P, s["KV"], page, s["hd"]), dt)
        # shuffled tables: logical order != physical order, like a real
        # free-list allocation pattern
        perm = RNG.permutation(np.arange(1, P))
        tables = jnp.asarray(perm.reshape(B, nb), jnp.int32)
        # ragged per-request lengths incl. a partial last block
        kv_len = jnp.asarray(
            [s["T"] - 63 - 17 * (i % 3) for i in range(B)], jnp.int32)
        args = (q, k_pool, v_pool, tables, kv_len)
        return args, args
    if kernel == "mamba2_ssd":
        x = jnp.asarray(RNG.randn(s["B"], s["S"], s["nh"], s["hd"]) * 0.5, dt)
        dt_in = jnp.asarray(
            np.abs(RNG.randn(s["B"], s["S"], s["nh"])) * 0.4 + 0.05,
            jnp.float32)
        A = jnp.asarray(-np.abs(RNG.randn(s["nh"])) - 0.1, jnp.float32)
        Bm = jnp.asarray(RNG.randn(s["B"], s["S"], 1, s["ds"]) * 0.5,
                         jnp.float32)
        Cm = jnp.asarray(RNG.randn(s["B"], s["S"], 1, s["ds"]) * 0.5,
                         jnp.float32)
        return (x, dt_in, A, Bm, Cm), (x, dt_in, A, Bm, Cm)
    raise AssertionError(kernel)


def _tol(kernel, dt):
    if dt == jnp.bfloat16:
        return dict(rtol=5e-2, atol=5e-2)
    loose = kernel in ("flash_attention", "decode_attention",
                       "paged_decode_attention", "mamba2_ssd")
    return dict(rtol=2e-3, atol=2e-3) if loose else dict(rtol=5e-4, atol=5e-4)


def test_catalog_is_complete():
    assert list(list_kernels()) == ["decode_attention", "flash_attention",
                                    "mamba2_ssd", "mfma_gemm", "moe_gmm",
                                    "paged_decode_attention"]


@pytest.mark.parametrize("device", DEVICES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel", sorted(SHAPES))
def test_catalog_parity_every_device(kernel, dt, device):
    """Planner-chosen tiles on ``device``, interpret mode, vs the oracle."""
    entry = get_kernel(kernel)
    shapes = SHAPES[kernel]
    args, ref_args = _case(kernel, shapes, dt)
    plan = plan_for(kernel, shapes, dtype=dt, device=device)
    y = entry.op_fn(*args, plan=plan, interpret=True)
    yr = entry.ref_fn(*ref_args)
    if isinstance(y, tuple):
        for got, want in zip(y, yr):
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       **_tol(kernel, dt))
    else:
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32),
                                   **_tol(kernel, dt))


@pytest.mark.parametrize("device", DEVICES)
@pytest.mark.parametrize("kernel", sorted(BIG_SHAPES))
def test_plan_aligned_and_budgeted_every_device(kernel, device):
    """Acceptance criterion: MXU-aligned, VMEM-budget-respecting tiles for
    every device in the repro.arch registry."""
    spec = get_device(device)
    plan = plan_for(kernel, BIG_SHAPES[kernel], dtype="bfloat16",
                    device=device)
    align = tile_align(spec)
    for name, block in plan.blocks.items():
        if name == "chunk":
            assert block % 8 == 0, plan
        elif name == "kv_step":              # a head count, not a tile
            assert BIG_SHAPES[kernel]["KV"] % block == 0, plan
        else:
            assert block % align == 0, plan
    assert plan.vmem_bytes <= plan.vmem_budget, plan
    assert plan.vmem_budget <= spec.vmem_bytes
    assert all(g >= 1 for g in plan.grid), plan


@pytest.mark.parametrize("arch", ["qwen2-7b", "mistral-nemo-12b",
                                  "deepseek-v2-lite-16b"])
def test_paged_plan_reads_every_head_of_a_page_on_v5e(arch):
    """On a v5e the chip configurations keep their 512-row page, chosen
    as for one head, and read all their KV heads of a page in one grid
    step (4, 8, and the latent pool's one)."""
    from repro.configs import get_config
    from repro.serve.paged_cache import default_page_size, pool_heads
    cfg = get_config(arch)
    kv, width = pool_heads(cfg)
    assert default_page_size(cfg, "tpu_v5e", cap=4096) == 512
    shapes = {"B": 23, "T": 4096, "H": cfg.n_heads, "KV": kv, "hd": width}
    for pinned in ({}, {"page": 512}):
        plan = plan_for("paged_decode_attention", dict(shapes, **pinned),
                        device="tpu_v5e")
        assert plan.blocks == {"block_kv": 512, "kv_step": kv}, plan
        assert plan.grid == (23, 8)
        assert plan.vmem_bytes <= plan.vmem_budget


def test_paged_plan_splits_heads_that_do_not_fit():
    """32 KV heads of 128 (MHA) do not fit one step's budget: the plan
    takes the largest divisor that does, and the page stays what one
    head gets."""
    shapes = {"B": 8, "T": 4096, "H": 32, "KV": 32, "hd": 128}
    plan = plan_for("paged_decode_attention", shapes, device="tpu_v5e")
    one = plan_for("paged_decode_attention", dict(shapes, H=1, KV=1),
                   device="tpu_v5e")
    step = plan.blocks["kv_step"]
    assert plan.blocks["block_kv"] == one.blocks["block_kv"] == 512
    assert 1 < step < 32 and 32 % step == 0, plan
    assert plan.vmem_bytes <= plan.vmem_budget < 2 * plan.vmem_bytes
    assert plan.grid == (8 * 32 // step, 8)


def test_plan_respects_tight_budget():
    """A small-VMEM derived device forces smaller tiles than its base."""
    base = get_device("tpu_v5e")
    tiny = base.derive("tpu_tiny_vmem", vmem_bytes=1 << 20)
    big = plan_for("mfma_gemm", BIG_SHAPES["mfma_gemm"], device=base)
    small = plan_for("mfma_gemm", BIG_SHAPES["mfma_gemm"], device=tiny)
    assert small.vmem_bytes <= (1 << 20) // 2
    assert sum(small.blocks.values()) < sum(big.blocks.values())


def test_plan_override_pins_block():
    p = plan_for("mfma_gemm", {"M": 1024, "N": 1024, "K": 1024},
                 block_m=128)
    assert p.blocks["block_m"] == 128
    with pytest.raises(ValueError, match="block_m"):
        plan_for("mfma_gemm", {"M": 1024, "N": 1024, "K": 1024}, block_m=96)


def test_plan_unknown_override_rejected():
    with pytest.raises(ValueError, match="unknown block override"):
        plan_for("decode_attention",
                 {"B": 1, "T": 256, "H": 4, "KV": 2, "hd": 32}, block_m=128)


# ---------------------------------------------------------------------------
# Ragged-tail planning: pad=True models padded execution; pad=False keeps
# the descriptive ValueError contract
# ---------------------------------------------------------------------------

#: Sub-128 and non-divisor shapes real model configs produce (odd seq
#: lengths, capacity-trimmed MoE groups, small smoke dims).
RAGGED_SHAPES = {
    "mfma_gemm": {"M": 100, "N": 60, "K": 200},
    "moe_gmm": {"E": 4, "C": 20, "K": 100, "N": 60},
    "flash_attention": {"B": 1, "S": 100, "T": 100, "H": 4, "KV": 2,
                        "hd": 32},
    "decode_attention": {"B": 2, "T": 100, "H": 4, "KV": 2, "hd": 32},
    "paged_decode_attention": {"B": 2, "T": 100, "H": 4, "KV": 2, "hd": 32},
    "mamba2_ssd": {"B": 1, "S": 52, "nh": 2, "hd": 16, "ds": 16},
}

#: dim name -> (block keyword tiling it, quantum class): "mxu" aligns to
#: tile_align(spec); "sublane" to 8.
_RAGGED_DIMS = {
    "mfma_gemm": {"M": ("block_m", "mxu"), "N": ("block_n", "mxu"),
                  "K": ("block_k", "mxu")},
    "moe_gmm": {"C": ("block_m", "mxu"), "K": ("block_k", "mxu"),
                "N": ("block_n", "mxu")},
    "flash_attention": {"S": ("block_q", "mxu"), "T": ("block_kv", "mxu")},
    "decode_attention": {"T": ("block_kv", "mxu")},
    "paged_decode_attention": {"T": ("block_kv", "mxu")},
    "mamba2_ssd": {"S": ("chunk", "sublane")},
}


@pytest.mark.parametrize("kernel", sorted(RAGGED_SHAPES))
def test_ragged_plan_pads_and_records_mask_metadata(kernel):
    """pad=True: every planned dim is rounded up to its quantum, blocks
    tile the PADDED sizes, and the plan records the padded geometry
    (``dims`` + ``padded=True``) the ops-layer pad/mask/slice path needs."""
    shapes = RAGGED_SHAPES[kernel]
    spec = get_device(DEVICES[0])
    plan = plan_for(kernel, shapes, dtype="float32", device=spec, pad=True)
    assert plan.padded
    align = tile_align(spec)
    for dim, (block_name, klass) in _RAGGED_DIMS[kernel].items():
        q = align if klass == "mxu" else 8
        padded = plan.dims[dim]
        assert padded >= shapes[dim]
        assert padded % q == 0, (dim, plan)
        assert padded - shapes[dim] < q                   # minimal padding
        assert padded % plan.blocks[block_name] == 0, (dim, plan)


@pytest.mark.parametrize("kernel", sorted(RAGGED_SHAPES))
def test_ragged_plan_without_pad_keeps_error_contract(kernel):
    """pad=False: the same shapes raise a descriptive ValueError naming
    an offending dim WITH its size (no silent clamping, no padding)."""
    shapes = RAGGED_SHAPES[kernel]
    named_dim = "|".join(f"{d}={shapes[d]}" for d in _RAGGED_DIMS[kernel])
    with pytest.raises(ValueError, match=named_dim) as err:
        plan_for(kernel, shapes, dtype="float32",
                 device=DEVICES[0], pad=False)
    assert "pad" in str(err.value)       # the message points at the fix


def test_aligned_plan_pad_true_is_identity():
    """pad=True on already-aligned shapes changes nothing but the flag."""
    aligned = plan_for("mfma_gemm", SHAPES["mfma_gemm"], dtype="float32")
    padded = plan_for("mfma_gemm", SHAPES["mfma_gemm"], dtype="float32",
                      pad=True)
    assert padded.blocks == aligned.blocks
    assert padded.dims == dict(SHAPES["mfma_gemm"])
    assert padded.grid == aligned.grid


# ---------------------------------------------------------------------------
# Per-shard planning: the local shapes shard_map hands the kernels.
# BIG_SHAPES partitioned through each kernel's KernelEntry.logical
# contract on a production-class (pod-less) 8 x 8 mesh slice must still
# plan on every registered device — this is exactly what
# dispatch.decide(sharded=True) does per shard.
# ---------------------------------------------------------------------------

class _FakeMesh:
    """Duck-typed mesh (.shape only): planning needs no devices."""

    def __init__(self, shape):
        self.shape = shape


_SHARD_MESH = _FakeMesh({"data": 8, "model": 8})

#: mesh-eligible kernels (KernelEntry.logical is the source of truth).
_SHARDED_KERNELS = ["decode_attention", "flash_attention", "mamba2_ssd",
                    "moe_gmm"]


def _local_big(kernel):
    from repro.parallel.api import local_shapes
    shapes = dict(BIG_SHAPES[kernel])
    if kernel == "mamba2_ssd":
        shapes["G"] = 8                      # grouped B/C projections
    return shapes, local_shapes(shapes, get_kernel(kernel).logical,
                                _SHARD_MESH)


@pytest.mark.parametrize("device", DEVICES)
@pytest.mark.parametrize("kernel", _SHARDED_KERNELS)
def test_per_shard_plan_every_device(kernel, device):
    """Head-sharded attention (H 32->4, KV 8->1), expert-sharded GMM
    rows (E 16->2) and head-sharded SSD locals plan with MXU-aligned,
    VMEM-budgeted tiles on every device."""
    shapes, local = _local_big(kernel)
    assert local != shapes                   # something actually sharded
    assert all(shapes[d] % local[d] == 0 for d in shapes)
    spec = get_device(device)
    plan = plan_for(kernel, local, dtype="bfloat16", device=device)
    align = tile_align(spec)
    for name, block in plan.blocks.items():
        assert block % (8 if name == "chunk" else align) == 0, plan
    assert plan.vmem_bytes <= plan.vmem_budget <= spec.vmem_bytes
    assert all(g >= 1 for g in plan.grid), plan


@pytest.mark.parametrize("device", DEVICES)
def test_sequence_sharded_ssd_chunks_plan(device):
    """Context-parallel SSD: an S/16 local slice still chunks exactly
    (chunked SSD is exact at any chunk, so CP shards stay eligible)."""
    local = dict(BIG_SHAPES["mamba2_ssd"],
                 S=BIG_SHAPES["mamba2_ssd"]["S"] // 16)
    plan = plan_for("mamba2_ssd", local, dtype="bfloat16", device=device)
    chunk = plan.blocks["chunk"]
    assert chunk <= local["S"] and local["S"] % chunk == 0, plan


def test_shard_too_small_to_tile_keeps_fallback_contract():
    """A local shard below the alignment quantum (pad=False) or over the
    VMEM budget must surface as a planner ValueError — the raw material
    of dispatch's mesh-sharded fallback reason."""
    # 16 rows per expert shard vs the 128 quantum, strict contract
    with pytest.raises(ValueError, match="C=16"):
        plan_for("moe_gmm", {"E": 1, "C": 16, "K": 128, "N": 128},
                 dtype="bfloat16", device=DEVICES[0], pad=False)
    # even one minimal tile of this head-sharded shard busts 1 KiB VMEM
    tiny = get_device("tpu_v5e").derive("tpu_shard_vmem",
                                        vmem_bytes=1 << 10)
    with pytest.raises(ValueError):
        plan_for("flash_attention",
                 {"B": 1, "S": 4096, "T": 4096, "H": 4, "KV": 1,
                  "hd": 128}, dtype="bfloat16", device=tiny, pad=True)
