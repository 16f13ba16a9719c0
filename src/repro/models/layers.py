"""Layer primitives: norms, RoPE, MLPs, embeddings.

Numerics policy (recorded in DESIGN.md): parameters and matmul operands in
``cfg.dtype`` (bf16), normalisation statistics / softmax / logits in f32,
matmul accumulation in f32 via ``preferred_element_type``.
"""

from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.parallel.api import shard

__all__ = ["dense", "mm", "norm_apply", "rope", "rope_inv_freq",
           "yarn_mscale", "mlp_apply", "embed_apply",
           "unembed_apply", "DTYPES", "cdtype"]

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def _force_f32_dots() -> bool:
    """XLA:CPU's thunk runtime cannot execute BF16xBF16=F32 dots inside
    while bodies.  For CPU *execution* (tests, examples) we upcast operands
    to f32; the dry-run (lower/compile only) disables this via
    REPRO_CPU_F32_DOTS=0 so the lowered program keeps faithful bf16 dots."""
    env = os.environ.get("REPRO_CPU_F32_DOTS")
    if env is not None:
        return env == "1"
    return jax.default_backend() == "cpu"


def mm(subscripts: str, a: jax.Array, b: jax.Array,
       out_dtype=None) -> jax.Array:
    """Matmul-class einsum with f32 accumulation (bf16 in, f32 acc)."""
    if a.dtype == jnp.bfloat16 and _force_f32_dots():
        y = jnp.einsum(subscripts, a.astype(jnp.float32),
                       b.astype(jnp.float32))
    else:
        y = jnp.einsum(subscripts, a, b,
                       preferred_element_type=jnp.float32)
    return y if out_dtype is None else y.astype(out_dtype)


def cdtype(cfg: ModelConfig):
    return DTYPES[cfg.dtype]


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    """x @ w (+ b): bf16 operands, f32 accumulation, result in x.dtype."""
    y = mm("...k,kn->...n", x, w, out_dtype=x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def norm_apply(cfg: ModelConfig, w, x: jax.Array) -> jax.Array:
    """RMSNorm or LayerNorm in f32, cast back to x.dtype.

    ``w`` is either the scale vector (rms) or {"scale","bias"} (layer).
    """
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layer":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * w["scale"].astype(jnp.float32) + w["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps)
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(hd: int, theta: float, scaling=None) -> jax.Array:
    """(hd // 2,) inverse frequencies, ``theta ** (-2i / hd)``; with a
    :class:`~repro.models.config.YarnSpec` the DeepSeek-V2 YaRN mix: a
    linear ramp over the pair index between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations at the original length
    keeps the fast pairs and slows the others by ``factor``."""
    half = hd // 2
    extra = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (jnp.log(theta) / half))
    if scaling is None:
        return extra

    def corr_dim(rot):
        return hd * math.log(scaling.original_max_position
                             / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(corr_dim(scaling.beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / scaling.factor * ramp + extra * (1.0 - ramp)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling=None) -> jax.Array:
    """Rotary embedding (rotate-half pairs). x: (..., seq, heads,
    head_dim); positions: (seq,) or (batch, seq); ``scaling`` a YaRN
    spec or None."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = rope_inv_freq(hd, theta, scaling)
    ang = positions.astype(jnp.float32)[..., None] * freqs       # (.., S, half)
    # broadcast over the heads axis: (..., S, 1, half)
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.astype(x.dtype)


def mlp_apply(cfg: ModelConfig, w, x: jax.Array) -> jax.Array:
    """SwiGLU (wi/wg/wo) or GELU (wi/wo) feed-forward."""
    if cfg.mlp_type == "gelu":
        h = jax.nn.gelu(dense(x, w["wi"], w.get("bi")))
    else:
        h = jax.nn.silu(dense(x, w["wg"])) * dense(x, w["wi"])
    h = shard(h, "batch", None, "tp")
    return dense(h, w["wo"], w.get("bo"))


def embed_apply(cfg: ModelConfig, w_embed: jax.Array,
                tokens: jax.Array) -> jax.Array:
    """Token embedding lookup; (B, S) int32 -> (B, S, D).

    The wsc on the *weight* shards D — the gather's PASSTHROUGH dim — so
    GSPMD partitions both the lookup and its backward scatter-add natively
    (sharding V instead leaves the (V, D) f32 gradient scatter unsharded:
    the gathered dim can't be partitioned against data-dependent indices).
    Storage stays (vocab, fsdp)-sharded; XLA inserts the reshard.
    """
    w_embed = shard(w_embed, None, "tp")
    h = jnp.take(w_embed, tokens, axis=0).astype(cdtype(cfg))
    return shard(h, "batch", "seq", None)


def unembed_apply(cfg: ModelConfig, w_unembed: jax.Array,
                  h: jax.Array) -> jax.Array:
    """(B, S, D) -> f32 logits (B, S, V).

    Vocab-sharded when V divides the model axis (TP unembed); otherwise
    sequence-sharded — an unsharded (B, S, V) f32 tensor is the single
    largest buffer in training (12+ GiB/device for mamba2/whisper whose
    vocabs are not multiples of 16).
    """
    from repro.parallel.api import current_mesh
    w_unembed = shard(w_unembed, None, "vocab")
    logits = mm("bsd,dv->bsv", h, w_unembed)
    mesh = current_mesh()
    V = w_unembed.shape[-1]
    if mesh is not None and V % mesh.shape.get("model", 1) == 0:
        return shard(logits, "batch", None, "vocab")
    return shard(logits, "batch", "seq", None)
