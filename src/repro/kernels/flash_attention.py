"""Blockwise online-softmax (flash) attention Pallas kernel, causal GQA.

VMEM tiling: q tile (block_q, hd), K/V tiles (block_kv, hd), running
(m, l, acc) in f32 VMEM scratch.  Grid (B*KV*G, Sq/block_q, T/block_kv)
with the KV dimension innermost/sequential; fully-masked causal blocks and
blocks past ``kv_len`` are skipped with ``pl.when`` (the XLA reference in
models/attention.py executes them — one of the kernel's perf wins on real
TPUs).

``kv_len`` (an SMEM scalar, default T) masks key positions >= kv_len —
both genuinely short caches and the ragged-tail padding the ops wrapper
applies so non-128-multiple T runs the kernel path.

The contract matches ``repro.kernels.ref.flash_attention_ref`` (and the
model's `_flash_sdpa`): grouped heads, causal, optional kv_len mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.plan import validate_tiling

__all__ = ["flash_attention"]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, causal: bool, n_kv: int,
                  block_q: int, block_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[0]
    # skip blocks entirely past kv_len, and (causal) strictly above the
    # diagonal
    run = ki * block_kv < kv_len
    if causal:
        run = jnp.logical_and(
            run, ki * block_kv <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)
        col = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            s = jnp.where(col <= row, s, _NEG_INF)
        s = jnp.where(col < kv_len, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _store():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int, block_kv: int,
                    kv_len=None, interpret: bool = False) -> jax.Array:
    """q: (B, S, H, hd); k/v: (B, T, KV, hd) with H = KV*G -> (B, S, H, hd).

    ``block_q``/``block_kv`` must be MXU-aligned divisors of S/T (derive
    them with ``repro.kernels.plan.plan_for``; ``ops.flash_attention``
    with ``pad=True`` pads ragged shapes onto this contract).  ``kv_len``
    (scalar int32, default T) masks key positions >= kv_len.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    validate_tiling("flash_attention", {"S": (S, block_q),
                                        "T": (T, block_kv)},
                    depth_dims=(),
                    block_names={"S": "block_q", "T": "block_kv"})
    if kv_len is None:
        kv_len = T
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32)[None], (1,))

    # (B, S, KV, G, hd) -> flat (B*KV*G, S, hd) query-major layout
    qf = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KV * G, S, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1) \
        .reshape(B * KV * G, T, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1) \
        .reshape(B * KV * G, T, hd)

    grid = (B * KV * G, S // block_q, T // block_kv)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          n_kv=T // block_kv, block_q=block_q,
                          block_kv=block_kv),
        grid=grid,
        in_specs=[
            compat.smem_block_spec(),
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV * G, S, hd), q.dtype),
        scratch_shapes=[
            compat.vmem((block_q, 1), jnp.float32),   # running max
            compat.vmem((block_q, 1), jnp.float32),   # running denom
            compat.vmem((block_q, hd), jnp.float32),  # accumulator
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(B, KV, G, S, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(B, S, H, hd)
