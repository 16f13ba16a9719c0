"""Flash-decode Pallas kernels: one query token vs a long KV cache.

Two variants share one kernel body:

* :func:`decode_attention` — the contiguous cache.  Grid (B*KV,
  T/block_kv): the KV sequence is the sequential dimension; the G query
  heads of each KV group ride along inside the tile ((G, hd) query
  block), so the kernel's inner product is an MXU-friendly (G, hd) x
  (hd, block_kv) matmul even for G as small as 4-8.  Running (m, l, acc)
  scratch identical to the prefill kernel; ``kv_len`` — a scalar or a
  per-request (B,) vector — masks unwritten cache slots.

* :func:`paged_decode_attention` — the block-paged cache the
  continuous-batching serve engine uses.  K/V live in a shared pool of
  fixed-size blocks ``(P, KV, block_kv, hd)`` — head-major inside a
  block, so one head's page is a contiguous, (8, 128)-tileable
  ``(block_kv, hd)`` slab and a run of heads of one page is one slab
  too; each request names its blocks via a ``(B, NB)`` block table.
  The serve engine stacks the pools of its scanned layers into one
  ``(L, P, KV, block_kv, hd)`` buffer, and the kernel reads that stack
  in place: the table, the per-request lengths and the layer index ride
  in as scalar-prefetch operands (``compat.prefetch_grid_spec``), so the
  K/V BlockSpec index maps gather ``pool[layer, table[b, j]]`` per grid
  step.  One grid step is one request's page over ``kv_step`` KV heads
  (all of them where they fit the fast-memory budget), each head one
  (G, hd) x (hd, block_kv) tile of the shared body.  The table is
  clamped to each request's last live page, so steps past it repeat a
  block index (no copy) and ``pl.when`` skips their compute; the
  ``kv_len`` mask handles the partial last block.  A single
  ``(P, KV, block_kv, hd)`` pool is the stack with a unit layer axis.
  Its latent mode serves multi-head latent attention: a pool of latent
  rows that are keys and values at once, and a pool of the decoupled
  rope keys, each live page read once.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.plan import validate_tiling

__all__ = ["decode_attention", "paged_decode_attention"]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_body(kv_len, ki, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, *, scale: float, n_kv: int, block_kv: int,
                 kt_ref=None):
    """Shared online-softmax step.  Every ref leads with the step's KV
    heads; each head scores its own (G, block_kv) tile against its slice
    of the running (m, l, acc) scratch.  ``kv_len`` masks columns past
    the request's written prefix (the partial last block and, for the
    paged variant, the whole tail of over-allocated table slots).
    Latent mode (``v_ref=None``): the key tile (block_kv, R) is also the
    value tile, and ``kt_ref``'s (rope, block_kv) tile adds the
    decoupled rope key, scored against the query's columns past R."""

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * block_kv < kv_len)
    def _compute():
        for h in range(q_ref.shape[0]):                     # static heads
            q = q_ref[h].astype(jnp.float32) * scale        # (G, hd)
            k = k_ref[h].astype(jnp.float32)                # (bkv, R)
            if kt_ref is None:
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
            else:
                R = k.shape[1]
                s = jax.lax.dot_general(q[:, :R], k,
                                        (((1,), (1,)), ((), ())))
                s = s + jax.lax.dot_general(                # (G, bkv)
                    q[:, R:], kt_ref[h].astype(jnp.float32),
                    (((1,), (0,)), ((), ())))
            col = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(col < kv_len, s, _NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, -1, keepdims=True)
            v = k_ref[h] if v_ref is None else v_ref[h]
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ki == n_kv - 1)
    def _store():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, n_kv: int, block_kv: int,
                   kv_heads: int):
    kv_len = len_ref[pl.program_id(0) // kv_heads]      # per-request length
    _decode_body(kv_len, pl.program_id(1), q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref, scale=scale, n_kv=n_kv,
                 block_kv=block_kv)


def _lens_vector(kv_len, B: int) -> jax.Array:
    """Normalise ``kv_len`` to a (B,) int32 vector (scalars broadcast)."""
    kl = jnp.asarray(kv_len, jnp.int32)
    if kl.ndim == 0:
        return jnp.broadcast_to(kl[None], (B,))
    if kl.shape != (B,):
        raise ValueError(
            f"decode_attention: kv_len must be a scalar or a per-request "
            f"({B},) vector, got shape {kl.shape}")
    return kl


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, *, block_kv: int,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k/v: (B, T, KV, hd); kv_len: int32 scalar or (B,).

    Returns (B, H, hd) attention output over cache positions < kv_len —
    per request when ``kv_len`` is a (B,) vector, so mixed-length batches
    mask correctly.  ``block_kv`` must be an MXU-aligned divisor of the
    cache length T (derive it with ``repro.kernels.plan.plan_for``).
    """
    B, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    validate_tiling("decode_attention", {"T": (T, block_kv)},
                    depth_dims=(), block_names={"T": "block_kv"})

    qf = q.reshape(B, KV, G, hd).reshape(B * KV, G, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, T, hd)
    lens = _lens_vector(kv_len, B)

    grid = (B * KV, T // block_kv)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, n_kv=T // block_kv,
                          block_kv=block_kv, kv_heads=KV),
        grid=grid,
        in_specs=[
            compat.smem_block_spec(),
            pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), q.dtype),
        scratch_shapes=[
            compat.vmem((1, G, 1), jnp.float32),
            compat.vmem((1, G, 1), jnp.float32),
            compat.vmem((1, G, hd), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(B, KV, G, hd).reshape(B, H, hd)


def _paged_decode_kernel(tbl_ref, len_ref, layer_ref, q_ref, k_ref, *refs,
                         scale: float, n_kv: int, block_kv: int,
                         groups: int, latent: bool):
    # tbl_ref/len_ref/layer_ref are the scalar-prefetch operands; the K/V
    # gather already happened in the BlockSpec index maps below.  Latent
    # mode's second pool operand is the transposed rope key, not V.
    del tbl_ref, layer_ref
    v_ref, kt_ref = (None, refs[0]) if latent else (refs[0], None)
    o_ref, m_ref, l_ref, acc_ref = refs[1:]
    kv_len = len_ref[pl.program_id(0) // groups]
    _decode_body(kv_len, pl.program_id(1), q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref, scale=scale, n_kv=n_kv,
                 block_kv=block_kv, kt_ref=kt_ref)


def _live_tables(tables: jax.Array, lens: jax.Array,
                 block_kv: int) -> jax.Array:
    """``tables`` with each request's slots past its last live page
    pointing at that page.  The kernel's grid steps there then repeat
    the previous step's block index, so the TPU pipeline copies nothing
    for them, and ``pl.when`` skips their compute.  Clamped here, once a
    call, and not in the index map, whose arithmetic would run on every
    grid step."""
    last = jnp.maximum((lens + block_kv - 1) // block_kv - 1, 0)
    cols = jnp.arange(tables.shape[1], dtype=jnp.int32)
    return jnp.take_along_axis(
        tables, jnp.minimum(cols[None, :], last[:, None]), axis=1)


@functools.partial(jax.jit, static_argnames=("scale", "kv_step",
                                             "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool, block_tables: jax.Array,
                           kv_len: jax.Array, layer=None, *,
                           k_rope_pool=None, scale: float = 0.0,
                           kv_step: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k_pool/v_pool: a (L, P, KV, block_kv, hd) stack of
    layer pools with ``layer`` the int32 index of the one to read, or a
    single (P, KV, block_kv, hd) pool with ``layer=None``;
    block_tables: (B, NB) int32 physical block ids; kv_len: (B,) int32.
    ``scale`` is the softmax scale (0: 1/sqrt(hd)).  ``kv_step`` is the
    KV heads one grid step reads, a divisor of KV (None: all of them;
    the ``paged_decode_attention`` planner picks the largest whose pages
    fit the fast-memory budget): the grid is (B * KV / kv_step, NB), and
    a step's K and V blocks are ``kv_step`` heads of one page, one
    contiguous slab of the (.., KV, block_kv, hd) layout.

    Latent mode (``v_pool=None``; multi-head latent attention in its
    absorbed form): one head of latent rows, ``k_pool`` (..., 1,
    block_kv, R), which are both the keys' first R columns and the
    values, and ``k_rope_pool`` (..., 1, rope, block_kv), the decoupled
    rope key stored transposed, a page of it one (rope, block_kv) tile;
    q is (B, H, R + rope), the output (B, H, R).  Each page of each pool
    is read once.  (A single (.., R + rope) row pool would be laid out
    with its rows minor on the TPU, since R + rope is no multiple of 128,
    and the kernel's read would copy the whole stack.)

    Each request attends its first ``kv_len[b]`` cache positions, read
    from pool blocks ``block_tables[b, 0..ceil(kv_len/block_kv))`` of
    layer ``layer`` — the page size IS the kv tile, so it must be
    MXU-aligned (the ``paged_decode_attention`` planner chooses it).
    The stack is read in place: only the live pages of one layer move.
    Grid steps past a request's last live page re-read that page's block
    index (no copy) and skip their compute, so table slots past the
    written prefix are never read; they must still hold in-range block
    ids (the serve engine points them at its reserved null block).
    """
    latent = v_pool is None
    second = k_rope_pool if latent else v_pool
    if latent and k_rope_pool is None:
        raise ValueError("paged_decode_attention: latent mode (v_pool=None) "
                         "needs k_rope_pool")
    if layer is None:
        # one pool is the stack with a unit layer axis (a bitcast)
        k_pool, second, layer = k_pool[None], second[None], 0
    B, H, hd = q.shape
    KV, block_kv, dk = k_pool.shape[2], k_pool.shape[3], k_pool.shape[4]
    NB = block_tables.shape[1]
    G = H // KV
    T = NB * block_kv
    kv_step = kv_step or KV
    if KV % kv_step:
        raise ValueError(f"paged_decode_attention: kv_step={kv_step} does "
                         f"not divide the pool's {KV} KV heads")
    groups = KV // kv_step
    scale = scale or 1.0 / math.sqrt(hd)
    validate_tiling("paged_decode_attention", {"T": (T, block_kv)},
                    depth_dims=(), block_names={"T": "block_kv"})

    qg = q.reshape(B, KV, G, hd)
    lens = _lens_vector(kv_len, B)
    tables = _live_tables(jnp.asarray(block_tables, jnp.int32), lens,
                          block_kv)
    layers = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    dv = dk if latent else second.shape[-1]

    def index(i, j, tbl_ref, len_ref, layer_ref):
        # gather: grid step (i, j) reads the (i % groups)-th run of KV
        # heads of page table[b, j] of the layer's pool, b = i // groups
        del len_ref
        return (layer_ref[0], tbl_ref[i // groups, j], i % groups, 0, 0)

    def _head_index(i, j, t, n, y):
        return (i // groups, i % groups, 0, 0)

    grid_spec = compat.prefetch_grid_spec(
        num_scalar_prefetch=3,
        grid=(B * groups, NB),
        in_specs=[pl.BlockSpec((None, kv_step, G, hd), _head_index),
                  pl.BlockSpec((None, None, kv_step, block_kv, dk), index),
                  pl.BlockSpec((None, None, kv_step) + second.shape[3:],
                               index)],
        out_specs=pl.BlockSpec((None, kv_step, G, dv), _head_index),
        scratch_shapes=[
            compat.vmem((kv_step, G, 1), jnp.float32),
            compat.vmem((kv_step, G, 1), jnp.float32),
            compat.vmem((kv_step, G, dv), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, n_kv=NB,
                          block_kv=block_kv, groups=groups, latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dv), q.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lens, layers, qg, k_pool, second)
    return out.reshape(B, H, dv)
