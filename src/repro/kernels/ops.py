"""jit'd public wrappers for the Pallas kernels — plan-driven.

Tile sizes are no longer hard-coded per call site: each wrapper derives a
:class:`~repro.kernels.plan.TilePlan` from the operand shapes and the
target :class:`~repro.arch.DeviceSpec` (``device=`` may be a registry
name, a spec, or a machine; ``None`` plans for the default TPU).  A
caller can pass a precomputed ``plan=`` (e.g. the one a perf engine
reported) or pin individual blocks (``block_m=...``), which are validated
by the same alignment contract the planner enforces.

Ragged tails: with ``pad=True`` the wrapper plans padded geometry
(``plan_for(..., pad=True)``), zero-pads the operands up to the plan's
``dims``, masks the epilogue where padding would change the math
(``kv_len``-style key masking for attention; ``dt=0`` identity steps for
the SSD; zero contraction blocks are exact for the GEMMs) and slices the
output back to the caller's shape — so non-128-multiple model shapes run
the kernel path instead of raising.  The default ``pad=False`` keeps the
strict contract: misaligned shapes raise a descriptive ``ValueError``.

On CPU (this container) the kernels execute in interpret mode — the
kernel body runs in Python per grid step, validating correctness; on a
real TPU backend the same call sites compile to Mosaic.
``interpret=None`` (the default) auto-detects via ``repro.kernels.compat``.

Mesh execution: every wrapper whose catalog entry carries a
``KernelEntry.logical`` contract accepts ``sharded=True``, which wraps
the single-device call in ``jax.shard_map`` over the active mesh
(``parallel.api.set_mesh``).  In/out specs are derived from the same
logical-axis rules the dispatcher planned against
(``parallel.api.shard_assignment``), the body re-resolves the tile plan
on its *local* shapes (always with the pad/mask/slice path, so ragged
local shards stay eligible), and any resharding collectives GSPMD needs
to honor the in-specs stay in the surrounding XLA program — the
``pallas_call`` itself only ever sees one shard.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import (compat, decode_attention as _da,
                           flash_attention as _fa, mamba2_ssd as _ssd,
                           mfma_gemm as _gemm, moe_gmm as _gmm)
from repro.kernels.plan import TilePlan, get_kernel, plan_for
from repro.parallel import api as _papi

__all__ = ["mfma_gemm", "flash_attention", "decode_attention",
           "paged_decode_attention", "mamba2_ssd", "moe_gmm"]


def _mesh_assignment(kernel: str, shapes: Mapping[str, int],
                     plan: Optional[TilePlan]):
    """(mesh, ShardAssignment) for a ``sharded=True`` wrapper call."""
    if plan is not None:
        raise ValueError(
            f"{kernel}: sharded=True re-resolves the plan per shard; pass "
            "device= (and block pins) instead of plan=")
    mesh = _papi.current_mesh()
    if mesh is None:
        raise ValueError(
            f"{kernel}: sharded=True requires an active mesh "
            "(parallel.api.set_mesh)")
    logical = get_kernel(kernel).logical
    if logical is None:
        raise ValueError(
            f"{kernel}: no logical-axis contract in the catalog; this "
            "kernel cannot run under shard_map")
    return mesh, _papi.shard_assignment(shapes, logical, mesh)


def _resolve(kernel: str, plan: Optional[TilePlan],
             shapes: Mapping[str, int], dtype, device,
             overrides: Dict[str, Optional[int]],
             pad: bool) -> Tuple[TilePlan, Dict[str, int]]:
    """(plan, block kwargs): explicit plan > pinned blocks > planner."""
    if plan is None:
        plan = plan_for(kernel, shapes, dtype=dtype, device=device, pad=pad,
                        **overrides)
    elif plan.kernel != kernel:
        raise ValueError(f"{kernel}: got a plan for {plan.kernel!r}; "
                         f"derive one with plan_for({kernel!r}, ...)")
    blocks = plan.kwargs()
    blocks.update({k: v for k, v in overrides.items() if v is not None})
    return plan, blocks


def _padded(plan: TilePlan, dim: str, size: int) -> int:
    """The padded size the plan tiles for ``dim`` (>= the input size)."""
    target = plan.dims.get(dim, size)
    if target < size:
        raise ValueError(
            f"{plan.kernel}: plan tiles {dim}={target} but the operand has "
            f"{dim}={size}; re-plan for the actual shapes")
    return target


def _pad_axis(x, axis: int, target: int):
    """Zero-pad ``x`` along ``axis`` up to ``target`` (no-op when equal)."""
    have = x.shape[axis]
    if have == target:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - have)
    return jnp.pad(x, widths)


def mfma_gemm(a, b, c, *, device=None, plan: Optional[TilePlan] = None,
              block_m: Optional[int] = None, block_n: Optional[int] = None,
              block_k: Optional[int] = None, pad: bool = False,
              interpret: Optional[bool] = None):
    M, N, K = a.shape[0], b.shape[1], a.shape[1]
    plan, blocks = _resolve("mfma_gemm", plan, {"M": M, "N": N, "K": K},
                            a.dtype, device,
                            dict(block_m=block_m, block_n=block_n,
                                 block_k=block_k), pad)
    if pad:
        # zero rows/cols and zero contraction blocks are exact
        Mp, Np, Kp = (_padded(plan, d, s)
                      for d, s in (("M", M), ("N", N), ("K", K)))
        a = _pad_axis(_pad_axis(a, 0, Mp), 1, Kp)
        b = _pad_axis(_pad_axis(b, 0, Kp), 1, Np)
        c = _pad_axis(_pad_axis(c, 0, Mp), 1, Np)
    out = _gemm.mfma_gemm(a, b, c, **blocks,
                          interpret=compat.resolve_interpret(interpret))
    return out[:M, :N] if pad else out


def flash_attention(q, k, v, *, causal=True, kv_len=None, device=None,
                    plan: Optional[TilePlan] = None,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None, pad: bool = False,
                    interpret: Optional[bool] = None, sharded: bool = False):
    B, S, H, hd = q.shape
    T = k.shape[1]
    if sharded:
        mesh, asn = _mesh_assignment(
            "flash_attention",
            {"B": B, "S": S, "T": T, "H": H, "KV": k.shape[2], "hd": hd},
            plan)
        qkv_specs = (asn.spec("B", None, "H", None),
                     asn.spec("B", None, "KV", None),
                     asn.spec("B", None, "KV", None))

        def _body(ql, kl, vl, lens=None):
            return flash_attention(ql, kl, vl, causal=causal, kv_len=lens,
                                   device=device, block_q=block_q,
                                   block_kv=block_kv, pad=True,
                                   interpret=interpret)

        if kv_len is None:
            fn = jax.shard_map(_body, mesh=mesh, in_specs=qkv_specs,
                               out_specs=qkv_specs[0], check_vma=False)
            return fn(q, k, v)
        lens = jnp.asarray(kv_len, jnp.int32)
        len_spec = asn.spec("B") if lens.ndim else P()
        fn = jax.shard_map(_body, mesh=mesh, in_specs=qkv_specs + (len_spec,),
                           out_specs=qkv_specs[0], check_vma=False)
        return fn(q, k, v, lens)
    plan, blocks = _resolve("flash_attention", plan,
                            {"B": B, "S": S, "T": T, "H": H,
                             "KV": k.shape[2], "hd": hd},
                            q.dtype, device,
                            dict(block_q=block_q, block_kv=block_kv), pad)
    if pad:
        # padded keys are masked via kv_len; padded query rows are sliced
        Sp = _padded(plan, "S", S)
        Tp = _padded(plan, "T", T)
        q = _pad_axis(q, 1, Sp)
        k = _pad_axis(k, 1, Tp)
        v = _pad_axis(v, 1, Tp)
        if kv_len is None and Tp != T:
            kv_len = T
    out = _fa.flash_attention(q, k, v, causal=causal, kv_len=kv_len,
                              **blocks,
                              interpret=compat.resolve_interpret(interpret))
    return out[:, :S] if pad else out


def decode_attention(q, k, v, kv_len, *, device=None,
                     plan: Optional[TilePlan] = None,
                     block_kv: Optional[int] = None, pad: bool = False,
                     interpret: Optional[bool] = None, sharded: bool = False):
    B, H, hd = q.shape
    T = k.shape[1]
    if sharded:
        mesh, asn = _mesh_assignment(
            "decode_attention",
            {"B": B, "T": T, "H": H, "KV": k.shape[2], "hd": hd}, plan)
        lens = jnp.asarray(kv_len, jnp.int32)
        if lens.ndim == 0:
            lens = jnp.broadcast_to(lens, (B,))

        def _body(ql, kl, vl, ll):
            return decode_attention(ql, kl, vl, ll, device=device,
                                    block_kv=block_kv, pad=True,
                                    interpret=interpret)

        fn = jax.shard_map(_body, mesh=mesh,
                           in_specs=(asn.spec("B", "H", None),
                                     asn.spec("B", None, "KV", None),
                                     asn.spec("B", None, "KV", None),
                                     asn.spec("B")),
                           out_specs=asn.spec("B", "H", None), check_vma=False)
        return fn(q, k, v, lens)
    plan, blocks = _resolve("decode_attention", plan,
                            {"B": B, "T": T, "H": H, "KV": k.shape[2],
                             "hd": hd},
                            q.dtype, device, dict(block_kv=block_kv), pad)
    if pad:
        # the kernel's kv_len mask already ignores the padded cache tail
        Tp = _padded(plan, "T", T)
        k = _pad_axis(k, 1, Tp)
        v = _pad_axis(v, 1, Tp)
    return _da.decode_attention(q, k, v, kv_len, **blocks,
                                interpret=compat.resolve_interpret(interpret))


def paged_decode_attention(q, k_pool, v_pool, block_tables, kv_len,
                           layer=None, *, k_rope_pool=None,
                           scale: Optional[float] = None, device=None,
                           plan: Optional[TilePlan] = None,
                           block_kv: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Flash-decode over a block-paged KV pool.

    q (B, H, hd); k_pool/v_pool a (L, P, KV, page, hd) stack of layer
    pools read at int32 index ``layer``, or one (P, KV, page, hd) pool
    with ``layer=None``; block_tables (B, NB) int32 physical block ids;
    kv_len (B,) int32 per-request lengths; ``scale`` the softmax scale
    (default 1/sqrt(hd)).  Latent mode: ``v_pool=None``, ``k_pool`` the
    latent rows (keys and values at once) and ``k_rope_pool`` the
    transposed rope keys (see
    :func:`repro.kernels.decode_attention.paged_decode_attention`).
    The pool's page size IS the kv tile, so the plan's ``block_kv`` must
    equal it — the ``shapes["page"]`` pin makes the planner agree on
    every device; there is no ``pad=`` mode (pool geometry is aligned by
    construction via :class:`~repro.serve.PagedKVCache`).  The plan's
    ``kv_step`` sets how many KV heads of a page one grid step reads.
    """
    B, H, hd = q.shape
    KV, page = k_pool.shape[-3], k_pool.shape[-2]
    NB = block_tables.shape[1]
    plan, blocks = _resolve("paged_decode_attention", plan,
                            {"B": B, "T": NB * page, "H": H, "KV": KV,
                             "hd": hd, "page": page},
                            q.dtype, device, dict(block_kv=block_kv), False)
    if blocks["block_kv"] != page:
        raise ValueError(
            f"paged_decode_attention: plan tiles block_kv="
            f"{blocks['block_kv']} but the KV pool's page size is {page}; "
            "plan with shapes['page'] (or block_kv=) pinned to the pool's "
            "page so the gather granularity matches")
    return _da.paged_decode_attention(
        q, k_pool, v_pool, block_tables, kv_len, layer,
        k_rope_pool=k_rope_pool, scale=float(scale or 0.0),
        kv_step=blocks["kv_step"],
        interpret=compat.resolve_interpret(interpret))


def mamba2_ssd(x, dt, A, Bm, Cm, *, device=None,
               plan: Optional[TilePlan] = None,
               chunk: Optional[int] = None, pad: bool = False,
               interpret: Optional[bool] = None, sharded: bool = False):
    B, S, nh, hd = x.shape
    if sharded:
        mesh, asn = _mesh_assignment(
            "mamba2_ssd",
            {"B": B, "S": S, "nh": nh, "hd": hd, "ds": Bm.shape[3],
             "G": Bm.shape[2]}, plan)

        def _body(xl, dtl, Al, Bl, Cl):
            return mamba2_ssd(xl, dtl, Al, Bl, Cl, device=device,
                              chunk=chunk, pad=True, interpret=interpret)

        fn = jax.shard_map(_body, mesh=mesh,
                           in_specs=(asn.spec("B", None, "nh", None),
                                     asn.spec("B", None, "nh"),
                                     asn.spec("nh"),
                                     asn.spec("B", None, "G", None),
                                     asn.spec("B", None, "G", None)),
                           out_specs=(asn.spec("B", None, "nh", None),
                                      asn.spec("B", "nh", None, None)),
                           check_vma=False)
        return fn(x, dt, A, Bm, Cm)
    plan, blocks = _resolve("mamba2_ssd", plan,
                            {"B": B, "S": S, "nh": nh, "hd": hd,
                             "ds": Bm.shape[3]},
                            x.dtype, device, dict(chunk=chunk), pad)
    if pad:
        # dt=0 padded steps are identity state updates (exp(0)=1 decay,
        # zero input contribution), so the final state stays exact
        Sp = _padded(plan, "S", S)
        x = _pad_axis(x, 1, Sp)
        dt = _pad_axis(dt, 1, Sp)
        Bm = _pad_axis(Bm, 1, Sp)
        Cm = _pad_axis(Cm, 1, Sp)
    y, state = _ssd.mamba2_ssd(x, dt, A, Bm, Cm, **blocks,
                               interpret=compat.resolve_interpret(interpret))
    return (y[:, :S], state) if pad else (y, state)


def moe_gmm(x, w, *, device=None, plan: Optional[TilePlan] = None,
            block_m: Optional[int] = None, block_n: Optional[int] = None,
            block_k: Optional[int] = None, pad: bool = False,
            interpret: Optional[bool] = None, sharded: bool = False):
    E, C, K = x.shape
    N = w.shape[2]
    if sharded:
        mesh, asn = _mesh_assignment(
            "moe_gmm", {"E": E, "C": C, "K": K, "N": N}, plan)

        def _body(xl, wl):
            return moe_gmm(xl, wl, device=device, block_m=block_m,
                           block_n=block_n, block_k=block_k, pad=True,
                           interpret=interpret)

        fn = jax.shard_map(_body, mesh=mesh,
                           in_specs=(asn.spec("E", None, None),
                                     asn.spec("E", None, None)),
                           out_specs=asn.spec("E", None, None),
                           check_vma=False)
        return fn(x, w)
    plan, blocks = _resolve("moe_gmm", plan,
                            {"E": E, "C": C, "K": K, "N": N},
                            x.dtype, device,
                            dict(block_m=block_m, block_n=block_n,
                                 block_k=block_k), pad)
    if pad:
        # zero slot rows and zero contraction blocks are exact
        Cp, Kp, Np = (_padded(plan, d, s)
                      for d, s in (("C", C), ("K", K), ("N", N)))
        x = _pad_axis(_pad_axis(x, 1, Cp), 2, Kp)
        w = _pad_axis(_pad_axis(w, 1, Kp), 2, Np)
    out = _gmm.moe_gmm(x, w, **blocks,
                       interpret=compat.resolve_interpret(interpret))
    return out[:, :C, :N] if pad else out
