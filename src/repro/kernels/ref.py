"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each oracle is written in the most *obviously correct* formulation —
full-softmax attention, per-time-step SSM recurrence — deliberately NOT the
blocked algorithms the kernels use, so the allclose sweeps validate the
algebra, not just the implementation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["mfma_gemm_ref", "flash_attention_ref", "decode_attention_ref",
           "paged_decode_attention_ref", "mamba2_ssd_ref", "moe_gmm_ref"]


def mfma_gemm_ref(a, b, c):
    """D = C + A @ B with f32 accumulation (the MFMA contract)."""
    d = c.astype(jnp.float32) + jnp.dot(a.astype(jnp.float32),
                                        b.astype(jnp.float32))
    return d.astype(c.dtype)


def _grouped_full_attn(q, k, v, *, causal, kv_len=None, scale=None):
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bskgh,btkh->bkgst", qg, k.astype(jnp.float32))
    s = s * (scale or 1.0 / math.sqrt(hd))
    if causal:
        i = jnp.arange(S)[:, None]
        j = jnp.arange(T)[None, :]
        s = jnp.where((j <= i)[None, None, None], s, -jnp.inf)
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        if kl.ndim == 1:                      # per-request (B,) lengths
            kl = kl[:, None, None, None, None]
        s = jnp.where(jnp.arange(T)[None, None, None, None] < kl, s,
                      -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, -1).astype(q.dtype)


def flash_attention_ref(q, k, v, *, causal=True):
    """Full-softmax grouped attention (no blocking)."""
    return _grouped_full_attn(q, k, v, causal=causal)


def decode_attention_ref(q, k, v, kv_len, scale=None):
    """q (B, H, hd) single-token attention vs cache prefix < kv_len
    (an int32 scalar, or a per-request (B,) vector)."""
    o = _grouped_full_attn(q[:, None], k, v, causal=False, kv_len=kv_len,
                           scale=scale)
    return o[:, 0]


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, kv_len, *,
                               k_rope_pool=None, scale=None):
    """Oracle for the paged kernel: gather each request's blocks from the
    (P, KV, bs, hd) pool into a dense (B, NB*bs, KV, hd) cache, then run
    the plain decode oracle with per-request lengths.  Latent mode
    (``v_pool=None``): the keys are the gathered latent rows followed by
    the gathered (transposed back) rope keys, the values the latent rows
    alone."""
    B = q.shape[0]

    def dense(pool):                 # (B, NB, KV, bs, d) -> (B, T, KV, d)
        return pool[block_tables].transpose(0, 1, 3, 2, 4).reshape(
            B, -1, pool.shape[1], pool.shape[3])

    if v_pool is None:
        v = dense(k_pool)
        k = jnp.concatenate([v, dense(jnp.swapaxes(k_rope_pool, -1, -2))],
                            axis=-1)
    else:
        k, v = dense(k_pool), dense(v_pool)
    return decode_attention_ref(q, k, v, kv_len, scale=scale)


def mamba2_ssd_ref(x, dt, A, Bm, Cm):
    """Per-time-step SSM recurrence (sequential oracle; no chunking).

    h_t = exp(dt_t A) h_{t-1} + dt_t * B_t (x) x_t ;  y_t = C_t . h_t
    x (B,S,nh,hd); dt (B,S,nh); A (nh,); Bm/Cm (B,S,G,ds).
    Returns (y (B,S,nh,hd), h_final (B,nh,hd,ds)) — matches mamba2_ssd.
    """
    B, S, nh, hd = x.shape
    G, ds = Bm.shape[2], Bm.shape[3]
    hpg = nh // G

    def step(h, inp):
        xt, dtt, bt, ct = inp                       # (B,nh,hd),(B,nh),(B,G,ds)
        bt = jnp.repeat(bt, hpg, axis=1)
        ct = jnp.repeat(ct, hpg, axis=1)
        da = jnp.exp(dtt * A)                       # (B,nh)
        h = da[..., None, None] * h + jnp.einsum(
            "bhp,bhs->bhps", dtt[..., None] * xt.astype(jnp.float32),
            bt.astype(jnp.float32))
        y = jnp.einsum("bhs,bhps->bhp", ct.astype(jnp.float32), h)
        return h, y

    h0 = jnp.zeros((B, nh, hd, ds), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    h_final, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h_final


def moe_gmm_ref(x, w):
    """(E, C, K) @ (E, K, N) -> (E, C, N), f32 accumulation."""
    y = jnp.einsum("eck,ekn->ecn", x.astype(jnp.float32),
                   w.astype(jnp.float32))
    return y.astype(x.dtype)
