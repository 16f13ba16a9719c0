"""Attention mixers: GQA self-attention, MLA (DeepSeek-V2), cross-attention.

Each mixer exposes:
  init_*      -> weight tree
  *_train     -> full-sequence causal (or cross) attention
  *_decode    -> single-token step against a KV cache (dynamic_update_slice)

Memory/sharding design (dry-run-validated on the (16,16) production mesh):

* Long sequences use a blockwise online-softmax attention (`_flash_sdpa`,
  a lax.scan over KV blocks) so peak logits memory is O(S x block), never
  O(S x T).  The Pallas `flash_attention` kernel implements the same
  contract for real TPUs; this XLA formulation is the GSPMD-shardable
  reference the dry-run compiles.
* Dual execution path: with ``cfg.use_pallas`` the :func:`attention`
  entry point routes through ``repro.kernels.dispatch`` to the Pallas
  kernels — ``kernels.flash_attention`` for the train/prefill step and
  ``kernels.decode_attention`` for the single-token KV-cache step —
  padding ragged (non-128-multiple) shapes via the ops-layer
  pad/mask/slice path.  Under an active mesh the dispatcher plans
  against the *per-shard* shapes (batch/heads shard via the logical-axis
  rules) and the kernels execute inside ``shard_map``, so
  ``use_pallas=True`` survives ``launch.mesh`` execution.  Anything the
  kernel contract cannot express (MLA's ``v_head_dim != qk_dim``, a
  custom softmax scale, unplannable local shards) falls back to the XLA
  reference below with a logged reason, so the flag is always safe to
  set.
* Query heads are TP-sharded when `n_heads` divides the model axis
  (mistral 32H, internlm2 48H, llama-vision 64H, ...).  When they do not
  (yi 56H, qwen2 28H, whisper 8H), we instead shard the *query sequence*
  over the model axis ("seq_tp") — attention math is position-parallel, so
  this is exact, and it keeps per-device logits bounded.
* Decode KV caches shard batch over "batch" and sequence over "kv_seq"
  (model, then data when free — long_500k with batch 1 gets 256-way
  sequence sharding).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch as kdispatch
from repro.kernels import ops as kops
from repro.models.config import ModelConfig
from repro.models.layers import (cdtype, dense, mm, norm_apply, rope,
                                 yarn_mscale)
from repro.parallel.api import current_mesh, shard

__all__ = ["init_attn", "attn_train", "attn_decode", "attn_decode_paged",
           "attn_prefill_paged", "init_mla", "mla_train", "mla_decode",
           "mla_decode_paged", "mla_prefill_paged", "mla_scale",
           "init_cross", "cross_train", "cross_decode", "init_attn_cache",
           "init_mla_cache", "sdpa", "attention"]

_FLASH_BLOCK = 512
_FLASH_MIN_T = 2048     # plain sdpa below this KV length
_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _heads_divisible(n_heads: int) -> bool:
    mesh = current_mesh()
    if mesh is None:
        return True
    return n_heads % mesh.shape.get("model", 1) == 0


def _shard_q(q: jax.Array) -> jax.Array:
    """(B, S, H, hd): heads-TP when divisible, else sequence-TP."""
    if _heads_divisible(q.shape[2]):
        return shard(q, "batch", None, "heads", None)
    return shard(q, "batch", "seq_tp", None, None)


def _shard_kv(k: jax.Array) -> jax.Array:
    """(B, T, KV, hd) train-time K/V: batch-sharded, heads when divisible."""
    if _heads_divisible(k.shape[2]):
        return shard(k, "batch", None, "heads", None)
    return shard(k, "batch", None, None, None)


def _kv_len_bc(kv_len) -> jax.Array:
    """Normalise ``kv_len`` for (B, H, S, T) logits masks: a scalar
    broadcasts as-is; a per-request (B,) vector gains (1, 1, 1) tails."""
    kl = jnp.asarray(kv_len, jnp.int32)
    return kl[:, None, None, None] if kl.ndim == 1 else kl


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
         scale: float, kv_len: Optional[jax.Array] = None,
         q_offset=0) -> jax.Array:
    """Plain SDPA over full heads.  q: (B,S,H,hd); k/v: (B,T,H,hd).
    ``kv_len`` is an int32 scalar or a per-request (B,) vector;
    ``q_offset`` (global index of q's first row for the causal mask) is
    an int scalar or a per-request (B,) vector — the paged continuation
    prefill decodes chunks sitting at a different offset per request."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    logits = mm("bshd,bthd->bhst", q, k) * scale
    if causal and S > 1:
        j = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
        qo = jnp.asarray(q_offset, jnp.int32)
        if qo.ndim == 1:
            i = (jax.lax.broadcasted_iota(jnp.int32, (S, T), 0)[None, None]
                 + qo[:, None, None, None])
            logits = jnp.where(j[None, None] <= i, logits, _NEG_INF)
        else:
            i = jax.lax.broadcasted_iota(jnp.int32, (S, T), 0) + q_offset
            logits = jnp.where((j <= i)[None, None], logits, _NEG_INF)
    if kv_len is not None:
        t = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, T), 3)
        logits = jnp.where(t < _kv_len_bc(kv_len), logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return mm("bhst,bthd->bshd", probs, v, out_dtype=q.dtype)


def _flash_sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                scale: float, kv_len: Optional[jax.Array] = None,
                q_offset: int = 0, block: int = _FLASH_BLOCK) -> jax.Array:
    """Blockwise online-softmax attention (lax.scan over KV blocks).

    Peak transient is (B,H,S,block) f32 instead of (B,H,S,T).  Exact (same
    contract as sdpa).  k/v may carry KV < H heads: they are expanded to H
    per BLOCK inside the body, so the full K/V tensors are read from HBM at
    KV-head width (§Perf iteration: the pre-expanded form read G x the
    bytes).  ``q_offset``: global row index of q's first position (causal
    triangle splitting).
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    if T % block:
        pad = block - T % block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_len = jnp.asarray(T, jnp.int32) if kv_len is None else kv_len
        T = T + pad
    if kv_len is not None:
        kv_len = _kv_len_bc(kv_len)        # (B,) vectors mask per request
    nb = T // block
    qf = (q.astype(jnp.float32) * scale)

    def body(carry, ib):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, ib * block, block, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, ib * block, block, 1)
        if G > 1:  # expand grouped KV heads per block (fusion-local)
            kb = jnp.repeat(kb, G, axis=2)
            vb = jnp.repeat(vb, G, axis=2)
        s = jnp.einsum("bshd,bthd->bhst", qf, kb.astype(jnp.float32))
        col = (jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, block), 3)
               + ib * block)
        if causal and S > 1:
            row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, S, 1), 2) \
                + q_offset
            s = jnp.where(col <= row, s, _NEG_INF)
        if kv_len is not None:
            s = jnp.where(col < kv_len, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    hd_v = v.shape[-1]
    m0 = jnp.full((B, H, S), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, H, S, hd_v), jnp.float32)
    # checkpoint the block body: scan's backward otherwise stacks the
    # (B,H,S,block) f32 score/prob tensors for every block (tens of GiB at
    # 32k); recomputing them leaves only the O(B*H*S) carries resident.
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                  jnp.arange(nb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # b h s d -> b s h d


def _attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, scale: float,
                      kv_len: Optional[jax.Array],
                      device: Optional[str] = None) -> Optional[jax.Array]:
    """Try the Pallas kernel path; ``None`` means "use the XLA reference".

    Dispatch happens at trace time on static shapes: ``flash_attention``
    for S > 1 (train/prefill), ``decode_attention`` for the S == 1
    KV-cache step.  Ragged shapes run via the ops-layer ``pad=True``
    path (padded keys are ``kv_len``-masked, padded query rows sliced).
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    kernel = "flash_attention" if S > 1 else "decode_attention"
    if v.shape[-1] != hd:
        kdispatch.fallback(
            kernel, f"v head dim {v.shape[-1]} != query head dim {hd} "
                    "(MLA-style asymmetric heads)")
        return None
    if abs(scale * math.sqrt(hd) - 1.0) > 1e-6:
        kdispatch.fallback(
            kernel, f"custom softmax scale {scale:g} != 1/sqrt(hd)")
        return None
    sharded = current_mesh() is not None
    if S > 1:
        dec = kdispatch.decide(
            "flash_attention",
            {"B": B, "S": S, "T": T, "H": H, "KV": KV, "hd": hd},
            dtype=q.dtype, device=device, sharded=sharded)
        if not dec.use_kernel:
            return None
        # a sharded Decision's plan is per-shard: the shard_map body
        # re-resolves it on local shapes, so pass device, not plan
        return kops.flash_attention(q, k, v, causal=causal, kv_len=kv_len,
                                    plan=None if dec.sharded else dec.plan,
                                    device=device, pad=True,
                                    sharded=dec.sharded)
    dec = kdispatch.decide(
        "decode_attention", {"B": B, "T": T, "H": H, "KV": KV, "hd": hd},
        dtype=q.dtype, device=device, sharded=sharded)
    if not dec.use_kernel:
        return None
    kl = jnp.asarray(T, jnp.int32) if kv_len is None else kv_len
    return kops.decode_attention(q[:, 0], k, v, kl,
                                 plan=None if dec.sharded else dec.plan,
                                 device=device, pad=True,
                                 sharded=dec.sharded)[:, None]


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
              scale: Optional[float] = None,
              kv_len: Optional[jax.Array] = None,
              use_pallas: bool = False,
              pallas_device: Optional[str] = None) -> jax.Array:
    """Grouped attention entry point.  q: (B,S,H,hd); k/v: (B,T,KV,hd).

    KV heads are expanded to the full H before the attention math (a
    (KV, G) reshape would break head sharding whenever KV < the model
    axis — yi/jamba/qwen3 all hit that); GQA's memory win lives in the
    KV *cache*, not the transient compute tensors.  With ``use_pallas``
    the Pallas kernels are tried first (``repro.kernels.dispatch`` falls
    back here when they cannot support the op).  The XLA reference
    dispatches to the blockwise path for long KV (training/prefill);
    plain einsum otherwise (short KV, and decode where S == 1 keeps
    logits tiny).
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if use_pallas:
        out = _attention_kernel(q, k, v, causal=causal, scale=scale,
                                kv_len=kv_len, device=pallas_device)
        if out is not None:
            return out
    use_flash = T >= _FLASH_MIN_T and S > 1
    if G > 1 and not use_flash:
        k = jnp.repeat(k, G, axis=2)   # flash expands per block instead
        v = jnp.repeat(v, G, axis=2)
    if S > 1:
        # train/prefill: heads-TP when divisible, else batch-only (the
        # blockwise scan slices T, so T must stay unsharded here)
        if _heads_divisible(k.shape[2]):
            k = shard(k, "batch", None, "heads", None)
            v = shard(v, "batch", None, "heads", None)
        else:
            k = shard(k, "batch", None, None, None)
            v = shard(v, "batch", None, None, None)
    # decode (S == 1): k/v keep the cache's ("batch","kv_seq") sharding —
    # XLA reduces the softmax over the sequence-sharded axis in place
    if use_flash:
        if causal and S == T and kv_len is None and S >= 2 * _FLASH_MIN_T:
            out = _causal_split_flash(q, k, v, scale=scale, depth=2)
        else:
            out = _flash_sdpa(q, k, v, causal=causal, scale=scale,
                              kv_len=kv_len)
    else:
        out = sdpa(q, k, v, causal=causal, scale=scale, kv_len=kv_len)
    return out


def _causal_split_flash(q, k, v, *, scale: float, depth: int,
                        q_offset: int = 0) -> jax.Array:
    """Causal triangle splitting (§Perf): a uniform KV scan executes every
    block, including the ~half that are fully masked.  Splitting q in two —
    the low half attends only the low half of K/V, the high half scans all
    of it — removes 25% of block work per level (31% at depth 2), exactly;
    the Pallas kernel gets the same effect from its pl.when block skip.
    """
    S = q.shape[1]
    if depth == 0 or S < 2 * _FLASH_MIN_T or S % 2:
        return _flash_sdpa(q, k, v, causal=True, scale=scale,
                           q_offset=q_offset)
    h = S // 2
    lo = _causal_split_flash(q[:, :h], k[:, :h], v[:, :h], scale=scale,
                             depth=depth - 1, q_offset=q_offset)
    hi = _flash_sdpa(q[:, h:], k, v, causal=True, scale=scale,
                     q_offset=q_offset + h)
    return jnp.concatenate([lo, hi], axis=1)


# ---------------------------------------------------------------------------
# GQA self-attention
# ---------------------------------------------------------------------------

def init_attn(cfg: ModelConfig, key) -> Dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = cdtype(cfg)
    s = 1.0 / math.sqrt(D)
    w = {
        "wq": jax.random.normal(k1, (D, H * hd), dt) * s,
        "wk": jax.random.normal(k2, (D, KV * hd), dt) * s,
        "wv": jax.random.normal(k3, (D, KV * hd), dt) * s,
        "wo": jax.random.normal(k4, (H * hd, D), dt) * (s / math.sqrt(max(1, cfg.n_layers))),
    }
    if cfg.qkv_bias:
        w["bq"] = jnp.zeros((H * hd,), dt)
        w["bk"] = jnp.zeros((KV * hd,), dt)
        w["bv"] = jnp.zeros((KV * hd,), dt)
    return w


def _qkv(cfg: ModelConfig, w, x, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = dense(x, w["wq"], w.get("bq")).reshape(B, S, H, hd)
    k = dense(x, w["wk"], w.get("bk")).reshape(B, S, KV, hd)
    v = dense(x, w["wv"], w.get("bv")).reshape(B, S, KV, hd)
    if cfg.pos_embed == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return _shard_q(q), _shard_kv(k), _shard_kv(v)


def attn_train(cfg: ModelConfig, w, x: jax.Array,
               positions: jax.Array, *, causal: bool = True) -> jax.Array:
    B, S, D = x.shape
    q, k, v = _qkv(cfg, w, x, positions)
    out = attention(q, k, v, causal=causal, use_pallas=cfg.use_pallas,
                    pallas_device=cfg.pallas_device)
    out = _shard_q(out)
    return dense(out.reshape(B, S, cfg.n_heads * cfg.hd), w["wo"])


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=None) -> Dict:
    dt = dtype or cdtype(cfg)
    shp = (batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}


def _cache_spec():
    return ("batch", "kv_seq", None, None)


def attn_decode(cfg: ModelConfig, w, x: jax.Array, cache: Dict,
                pos: jax.Array) -> Tuple[jax.Array, Dict]:
    """x: (B, 1, D); pos: scalar int32 — index of the new token."""
    B, S, D = x.shape
    positions = jnp.zeros((S,), jnp.int32) + pos
    q, k_new, v_new = _qkv(cfg, w, x, positions)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, pos, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, pos, 0, 0))
    k = shard(k, *_cache_spec())
    v = shard(v, *_cache_spec())
    out = attention(q, k, v, causal=False, kv_len=pos + 1,
                    use_pallas=cfg.use_pallas,
                    pallas_device=cfg.pallas_device)
    y = dense(out.reshape(B, S, cfg.n_heads * cfg.hd), w["wo"])
    return y, {"k": k, "v": v}


def _layer_pools(cache: Dict, layer) -> Tuple[jax.Array, jax.Array,
                                                jax.Array]:
    """(k, v, layer) as a stack of layer pools and the index to use: a
    (L, P, KV, page, hd) stack is used as it is; a single (P, KV, page,
    hd) pool (``layer=None``) is the stack with a unit layer axis."""
    if layer is None:
        return cache["k"][None], cache["v"][None], jnp.int32(0)
    return cache["k"], cache["v"], jnp.asarray(layer, jnp.int32)


def _unstack(k: jax.Array, v: jax.Array, layer) -> Dict:
    """The cache leaves in the caller's shape (see :func:`_layer_pools`)."""
    return {"k": k, "v": v} if layer is not None else {"k": k[0], "v": v[0]}


def _gather_pages(pool: jax.Array, tables: jax.Array,
                  layer: jax.Array) -> jax.Array:
    """(L, P, KV, page, hd) pool stack + (B, NB) tables -> layer
    ``layer``'s dense (B, NB*page, KV, hd) cache holding each request's
    blocks in table order (only the tabled blocks are read)."""
    B, KV, hd = tables.shape[0], pool.shape[2], pool.shape[4]
    # one (page, hd) slab per index: with whole (KV, page, hd) blocks the
    # TPU compiler may split the gather by slicing the whole stack
    head = jnp.arange(KV, dtype=jnp.int32)
    pages = pool[layer, tables[..., None], head]       # (B, NB, KV, page, hd)
    return pages.transpose(0, 1, 3, 2, 4).reshape(B, -1, KV, hd)


def _paged_attention_kernel(q, k_pool, v_pool, tables, kv_len, layer, *,
                            device=None):
    """Try the paged Pallas kernel; ``None`` means "gather + reference"."""
    B, S, H, hd = q.shape
    KV, page = k_pool.shape[-3], k_pool.shape[-2]
    NB = tables.shape[1]
    dec = kdispatch.decide(
        "paged_decode_attention",
        {"B": B, "T": NB * page, "H": H, "KV": KV, "hd": hd, "page": page},
        dtype=q.dtype, device=device, sharded=current_mesh() is not None)
    if not dec.use_kernel:
        return None
    return kops.paged_decode_attention(q[:, 0], k_pool, v_pool, tables,
                                       kv_len, layer, plan=dec.plan)[:, None]


def attn_decode_paged(cfg: ModelConfig, w, x: jax.Array, cache: Dict,
                      block_tables: jax.Array, lens: jax.Array,
                      layer=None) -> Tuple[jax.Array, Dict]:
    """One continuous-batching decode step against the shared KV pool.

    x: (B, 1, D) — each row is a *different* request's pending token;
    cache ``{"k", "v"}``: the (L, P, KV, page, hd) stacks of the scanned
    layers' block pools, of which this layer's is ``layer`` (an int32
    scalar), or with ``layer=None`` one layer's (P, KV, page, hd) pools;
    block_tables: (B, NB) int32 physical block ids (unused tail slots
    must point at the engine's reserved null block 0); lens: (B,) int32
    tokens already in each request's cache — both the new token's write
    position and its RoPE position.  Unlike :func:`attn_decode` there is
    no per-batch ``pos`` scalar: every request sits at its own offset.
    The new rows are written into the stack and attention reads it
    through the layer index, so no layer's pool is sliced out or copied:
    inside a layer scan that carries the stack, the write is in place.
    """
    B, S, D = x.shape
    lens = jnp.asarray(lens, jnp.int32)
    q, k_new, v_new = _qkv(cfg, w, x, lens[:, None])
    k, v, li = _layer_pools(cache, layer)
    page = k.shape[3]
    tables = jnp.asarray(block_tables, jnp.int32)
    # scatter the new K/V row into pool block table[b, lens//page] at
    # row lens%page — requests own disjoint blocks, so rows never collide
    # (idle engine slots all hit the null block, whose content is never
    # attended unmasked).  One index per KV head, so each update is one
    # (hd,) row: with (KV, hd) windows the TPU compiler re-lays the whole
    # stack out around the scatter and back for the kernel
    slot = jnp.take_along_axis(tables, (lens // page)[:, None], axis=1)
    row = (lens % page)[:, None]
    head = jnp.arange(k.shape[2], dtype=jnp.int32)[None, :]
    k = k.at[li, slot, head, row].set(k_new[:, 0])
    v = v.at[li, slot, head, row].set(v_new[:, 0])
    kv_len = lens + 1
    out = None
    if cfg.use_pallas:
        out = _paged_attention_kernel(q, k, v, tables, kv_len, li,
                                      device=cfg.pallas_device)
    if out is None:
        # gather the tables into a dense (B, NB*page, KV, hd) cache and
        # run the plain decode path (which may still pick the contiguous
        # kernel when cfg.use_pallas is set)
        out = attention(q, _gather_pages(k, tables, li),
                        _gather_pages(v, tables, li), causal=False,
                        kv_len=kv_len, use_pallas=cfg.use_pallas,
                        pallas_device=cfg.pallas_device)
    y = dense(out.reshape(B, S, cfg.n_heads * cfg.hd), w["wo"])
    return y, _unstack(k, v, layer)


def attn_prefill_paged(cfg: ModelConfig, w, x: jax.Array, cache: Dict,
                       block_tables: jax.Array, lens: jax.Array,
                       n_valid: jax.Array, layer=None, *,
                       aligned: bool = False) -> Tuple[jax.Array, Dict]:
    """One continuation-prefill chunk against the shared KV pool.

    x: (B, C, D) — a fixed-size chunk of each request's *uncached* prompt
    suffix, right-padded past ``n_valid``; cache ``{"k", "v"}`` and
    ``layer``, block_tables (B, NB) and lens (B,) as in
    :func:`attn_decode_paged` — ``lens`` is the number of tokens already
    in the cache, i.e. the chunk's global start position (both its write
    offset and its RoPE base).  The chunk's K/V rows are written into
    the pool first, then attention reads the whole table back as a dense
    cache — the prefix written by earlier chunks or *shared with other
    requests via the block table* is attended exactly like self-owned
    rows.  The causal mask runs at per-request global offsets, so chunked
    prefill computes the same masked logits full prefill would.

    ``aligned=True`` is a caller promise that B == 1 and every chunk
    lies inside a single block — the engine guarantees this whenever the
    chunk size divides the page, since chunks then start at multiples of
    C past a page boundary.  The write collapses to one contiguous
    ``dynamic_update_slice`` instead of a computed-index row scatter
    (~4.5x cheaper on XLA:CPU), bitwise-identical for every row that is
    ever read: padded rows past ``n_valid`` land just past the valid
    prefix inside the request's own last block (instead of the null
    block), where kv_len masks them this call and decode overwrites
    position ``s`` before any later read reaches it.
    """
    B, C, D = x.shape
    lens = jnp.asarray(lens, jnp.int32)
    nv = jnp.asarray(n_valid, jnp.int32)
    positions = lens[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _qkv(cfg, w, x, positions)
    k, v, li = _layer_pools(cache, layer)
    KV, page = k.shape[2], k.shape[3]
    tables = jnp.asarray(block_tables, jnp.int32)
    if aligned and B == 1 and C <= page:
        # single-block chunk: one contiguous C-row window per head
        start = (li, tables[0, lens[0] // page], 0, lens[0] % page, 0)
        k = jax.lax.dynamic_update_slice(
            k, k_new.transpose(0, 2, 1, 3)[None], start)
        v = jax.lax.dynamic_update_slice(
            v, v_new.transpose(0, 2, 1, 3)[None], start)
    else:
        # scatter the chunk's K/V rows at their global positions; rows
        # past n_valid (chunk padding) are redirected to the null block,
        # whose content is never attended unmasked
        row = jnp.arange(C, dtype=jnp.int32)[None, :]
        valid = row < nv[:, None]
        blk = jnp.where(valid, jnp.take_along_axis(
            tables, positions // page, axis=1), 0)
        r = jnp.where(valid, positions % page, row % page)
        head = jnp.arange(KV, dtype=jnp.int32)
        k = k.at[li, blk[..., None], head, r[..., None]].set(k_new)
        v = v.at[li, blk[..., None], head, r[..., None]].set(v_new)
    # read path: gather the table into a dense (B, NB*page, KV, hd) cache
    # (exactly the decode tick's read) and attend causally at each
    # request's own offset.  kv_len additionally masks rows the causal
    # mask cannot see when C == 1; for valid rows it masks a subset of
    # what causality already does, so the attended logits are unchanged.
    kd = _gather_pages(k, tables, li)
    vd = _gather_pages(v, tables, li)
    G = cfg.n_heads // KV
    if G > 1:
        kd = jnp.repeat(kd, G, axis=2)
        vd = jnp.repeat(vd, G, axis=2)
    out = sdpa(q, kd, vd, causal=True, scale=1.0 / math.sqrt(cfg.hd),
               kv_len=lens + nv, q_offset=lens)
    y = dense(out.reshape(B, C, cfg.n_heads * cfg.hd), w["wo"])
    return y, _unstack(k, v, layer)


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2): the KV cache stores only
# the compressed latent c_kv (+ decoupled RoPE key), up-projected per use.
# ---------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key) -> Dict:
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 5)
    dt = cdtype(cfg)
    s = 1.0 / math.sqrt(D)
    sl = 1.0 / math.sqrt(m.kv_lora_rank)
    return {
        "wq": jax.random.normal(ks[0], (D, H * qk), dt) * s,
        "w_dkv": jax.random.normal(ks[1], (D, m.kv_lora_rank + m.qk_rope_dim), dt) * s,
        "w_uk": jax.random.normal(ks[2], (m.kv_lora_rank, H * m.qk_nope_dim), dt) * sl,
        "w_uv": jax.random.normal(ks[3], (m.kv_lora_rank, H * m.v_head_dim), dt) * sl,
        "wo": jax.random.normal(ks[4], (H * m.v_head_dim, D), dt)
              * (s / math.sqrt(max(1, cfg.n_layers))),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
    }


def mla_scale(cfg: ModelConfig) -> float:
    """MLA's softmax scale: 1/sqrt(qk_nope + qk_rope), times YaRN's
    ``mscale(factor, mscale_all_dim)**2`` when the config scales RoPE."""
    m, y = cfg.mla, cfg.rope_scaling
    s = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if y is not None:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def _mla_latent(cfg: ModelConfig, w, x, positions):
    m = cfg.mla
    dkv = dense(x, w["w_dkv"])
    c_kv, k_pe = dkv[..., :m.kv_lora_rank], dkv[..., m.kv_lora_rank:]
    c_kv = norm_apply(cfg, w["kv_norm"], c_kv)
    k_pe = rope(k_pe[:, :, None, :], positions, cfg.rope_theta,
                cfg.rope_scaling)[:, :, 0, :]
    return c_kv, k_pe


def _mla_query(cfg: ModelConfig, w, x, positions):
    """(q_nope (B, S, H, nope), roped q_rope (B, S, H, rope))."""
    m = cfg.mla
    B, S = x.shape[:2]
    q = dense(x, w["wq"]).reshape(B, S, cfg.n_heads,
                                  m.qk_nope_dim + m.qk_rope_dim)
    q_rope = rope(q[..., m.qk_nope_dim:], positions, cfg.rope_theta,
                  cfg.rope_scaling)
    return q[..., :m.qk_nope_dim], q_rope


def _mla_attend(cfg: ModelConfig, w, x, c_kv, k_rope, positions, *,
                causal, kv_len=None):
    m = cfg.mla
    B, S = x.shape[:2]
    T, H = c_kv.shape[1], cfg.n_heads
    q_nope, q_rope = _mla_query(cfg, w, x, positions)
    k_nope = dense(c_kv, w["w_uk"]).reshape(B, T, H, m.qk_nope_dim)
    v = dense(c_kv, w["w_uv"]).reshape(B, T, H, m.v_head_dim)
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, m.qk_rope_dim))
    q_full = _shard_q(jnp.concatenate([q_nope, q_rope], axis=-1))
    k_full = _shard_kv(jnp.concatenate([k_nope, k_rope_h], axis=-1))
    v = _shard_kv(v)
    out = attention(q_full, k_full, v, causal=causal,
                    scale=mla_scale(cfg), kv_len=kv_len,
                    use_pallas=cfg.use_pallas,
                    pallas_device=cfg.pallas_device)
    return dense(out.reshape(B, S, H * m.v_head_dim), w["wo"])


def mla_train(cfg: ModelConfig, w, x: jax.Array,
              positions: jax.Array) -> jax.Array:
    c_kv, k_rope = _mla_latent(cfg, w, x, positions)
    return _mla_attend(cfg, w, x, c_kv, k_rope, positions, causal=True)


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=None) -> Dict:
    dt = dtype or cdtype(cfg)
    m = cfg.mla
    return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
            "krope": jnp.zeros((batch, max_len, m.qk_rope_dim), dt)}


def mla_decode(cfg: ModelConfig, w, x: jax.Array, cache: Dict,
               pos: jax.Array) -> Tuple[jax.Array, Dict]:
    B, S, D = x.shape
    positions = jnp.zeros((S,), jnp.int32) + pos
    c_new, kr_new = _mla_latent(cfg, w, x, positions)
    ckv = jax.lax.dynamic_update_slice(cache["ckv"], c_new, (0, pos, 0))
    krope = jax.lax.dynamic_update_slice(cache["krope"], kr_new, (0, pos, 0))
    ckv = shard(ckv, "batch", "kv_seq", None)
    krope = shard(krope, "batch", "kv_seq", None)
    y = _mla_attend(cfg, w, x, ckv, krope, positions, causal=False,
                    kv_len=pos + 1)
    return y, {"ckv": ckv, "krope": krope}


def _latent_pools(cache: Dict, layer):
    """(ckv, kpe, index): the (L, P, 1, page, R) latent and (L, P, 1,
    rope, page) transposed rope-key stacks as they are, or one layer's
    pools (``layer=None``) as stacks with a unit layer axis."""
    if layer is None:
        return cache["ckv"][None], cache["kpe"][None], jnp.int32(0)
    return cache["ckv"], cache["kpe"], jnp.asarray(layer, jnp.int32)


def _latent_cache(ckv, kpe, layer) -> Dict:
    return ({"ckv": ckv, "kpe": kpe} if layer is not None
            else {"ckv": ckv[0], "kpe": kpe[0]})


def _gather_latent(ckv, kpe, tables, li):
    """Layer ``li``'s dense (B, T, 1, R + rope) latent keys."""
    B = tables.shape[0]
    c = ckv[li, tables, 0].reshape(B, -1, 1, ckv.shape[-1])
    r = jnp.swapaxes(kpe[li, tables, 0], -1, -2).reshape(B, -1, 1,
                                                         kpe.shape[-2])
    return jnp.concatenate([c, r], axis=-1)


def mla_decode_paged(cfg: ModelConfig, w, x: jax.Array, cache: Dict,
                     block_tables: jax.Array, lens: jax.Array,
                     layer=None) -> Tuple[jax.Array, Dict]:
    """One continuous-batching MLA decode step against the latent pool,
    in the absorbed form.

    x (B, 1, D); cache ``{"ckv", "kpe"}`` and ``layer``, block_tables and
    lens as in :func:`attn_decode_paged`.  Each token writes one latent
    row (c_kv | k_rope) per layer.  The query folds ``w_uk`` in, so it
    attends over the latent rows as ONE key head of width R + rope whose
    values are the rows' first R columns — the paged kernel's latent
    mode; ``w_uv`` is applied to the R-wide result.  No cached row is
    ever up-projected."""
    m = cfg.mla
    B, S, D = x.shape
    H, R = cfg.n_heads, m.kv_lora_rank
    lens = jnp.asarray(lens, jnp.int32)
    c_new, k_new = _mla_latent(cfg, w, x, lens[:, None])
    ckv, kpe, li = _latent_pools(cache, layer)
    page = ckv.shape[3]
    tables = jnp.asarray(block_tables, jnp.int32)
    slot = jnp.take_along_axis(tables, (lens // page)[:, None], axis=1)[:, 0]
    ckv = ckv.at[li, slot, 0, lens % page].set(c_new[:, 0])
    kpe = kpe.at[li, slot, 0, :, lens % page].set(k_new[:, 0])
    q_nope, q_rope = _mla_query(cfg, w, x, lens[:, None])
    q_lat = mm("bshn,rhn->bshr", q_nope,
               w["w_uk"].reshape(R, H, m.qk_nope_dim), out_dtype=x.dtype)
    q = jnp.concatenate([q_lat, q_rope], axis=-1)       # (B, 1, H, R + rope)
    kv_len = lens + 1
    out = None
    if cfg.use_pallas:
        NB = tables.shape[1]
        dec = kdispatch.decide(
            "paged_decode_attention",
            {"B": B, "T": NB * page, "H": H, "KV": 1, "hd": q.shape[-1],
             "page": page},
            dtype=q.dtype, device=cfg.pallas_device,
            sharded=current_mesh() is not None)
        if dec.use_kernel:
            out = kops.paged_decode_attention(
                q[:, 0], ckv, None, tables, kv_len, li, k_rope_pool=kpe,
                scale=mla_scale(cfg), plan=dec.plan)[:, None]
    if out is None:
        lat = _gather_latent(ckv, kpe, tables, li)      # (B, T, 1, R + rope)
        out = attention(q, lat, lat[..., :R], causal=False,
                        scale=mla_scale(cfg), kv_len=kv_len)
    o = mm("bshr,rhv->bshv", out, w["w_uv"].reshape(R, H, m.v_head_dim),
           out_dtype=x.dtype)
    y = dense(o.reshape(B, S, H * m.v_head_dim), w["wo"])
    return y, _latent_cache(ckv, kpe, layer)


def mla_prefill_paged(cfg: ModelConfig, w, x: jax.Array, cache: Dict,
                      block_tables: jax.Array, lens: jax.Array,
                      n_valid: jax.Array, layer=None, *,
                      aligned: bool = False) -> Tuple[jax.Array, Dict]:
    """One continuation-prefill chunk of MLA against the latent pool.

    Arguments as in :func:`attn_prefill_paged`.  The chunk writes its
    latent rows, then attends in the up-projected form: a loop over the
    table's pages up to the chunk's last valid row (later pages hold
    nothing it may see) up-projects each page's latent rows to per-head
    keys and values and folds them into an online softmax, so no
    table-wide K/V or score tensor is formed."""
    m = cfg.mla
    B, C, D = x.shape
    H, R = cfg.n_heads, m.kv_lora_rank
    lens = jnp.asarray(lens, jnp.int32)
    nv = jnp.asarray(n_valid, jnp.int32)
    positions = lens[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    c_new, k_new = _mla_latent(cfg, w, x, positions)    # (B, C, R), rope
    ckv, kpe, li = _latent_pools(cache, layer)
    page = ckv.shape[3]
    tables = jnp.asarray(block_tables, jnp.int32)
    if aligned and B == 1 and C <= page:
        blk, off = tables[0, lens[0] // page], lens[0] % page
        ckv = jax.lax.dynamic_update_slice(ckv, c_new[:, None][None],
                                           (li, blk, 0, off, 0))
        kpe = jax.lax.dynamic_update_slice(
            kpe, jnp.swapaxes(k_new, 1, 2)[:, None][None],
            (li, blk, 0, 0, off))
    else:
        # rows past n_valid go to the null block, never attended unmasked
        row = jnp.arange(C, dtype=jnp.int32)[None, :]
        valid = row < nv[:, None]
        blk = jnp.where(valid, jnp.take_along_axis(
            tables, positions // page, axis=1), 0)
        r = jnp.where(valid, positions % page, row % page)
        ckv = ckv.at[li, blk, 0, r].set(c_new)
        kpe = kpe.at[li, blk, 0, :, r].set(k_new)

    q_nope, q_rope = _mla_query(cfg, w, x, positions)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)      # (B, C, H, qk)
    scale = mla_scale(cfg)
    end = lens + nv                                     # rows in the cache
    w_uk, w_uv = w["w_uk"], w["w_uv"]

    def page_step(j, carry):
        mx, l, acc = carry
        c = ckv[li, tables[:, j], 0]                    # (B, page, R)
        k_pe = jnp.swapaxes(kpe[li, tables[:, j], 0], 1, 2)   # (B, page, rope)
        k_nope = dense(c, w_uk).reshape(B, page, H, m.qk_nope_dim)
        v = dense(c, w_uv).reshape(B, page, H, m.v_head_dim)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_pe[:, :, None], (B, page, H, m.qk_rope_dim))], axis=-1)
        s = mm("bshd,bthd->bhst", q, k) * scale         # (B, H, C, page)
        col = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, page),
                                                  3)
        see = (col <= positions[:, None, :, None]) & (
            col < end[:, None, None, None])
        s = jnp.where(see, s, _NEG_INF)
        m_new = jnp.maximum(mx, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mx - m_new)
        return (m_new, l * corr + jnp.sum(p, axis=-1),
                acc * corr[..., None]
                + mm("bhst,bthd->bhsd", p.astype(v.dtype), v))

    n_pages = (jnp.max(end) + page - 1) // page
    init = (jnp.full((B, H, C), _NEG_INF, jnp.float32),
            jnp.zeros((B, H, C), jnp.float32),
            jnp.zeros((B, H, C, m.v_head_dim), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_pages, page_step, init)
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(x.dtype)
    y = dense(out.transpose(0, 2, 1, 3).reshape(B, C, H * m.v_head_dim),
              w["wo"])
    return y, _latent_cache(ckv, kpe, layer)


# ---------------------------------------------------------------------------
# Cross-attention (VLM media layers; whisper decoder)
# ---------------------------------------------------------------------------

init_cross = init_attn  # same weight structure, no biases used


def cross_kv(cfg: ModelConfig, w, media: jax.Array):
    """Precompute K/V from media/encoder embeddings (B, M, D)."""
    B, M, _ = media.shape
    KV, hd = cfg.n_kv_heads, cfg.hd
    k = dense(media, w["wk"]).reshape(B, M, KV, hd)
    v = dense(media, w["wv"]).reshape(B, M, KV, hd)
    return _shard_kv(k), _shard_kv(v)


def cross_train(cfg: ModelConfig, w, x: jax.Array,
                media: jax.Array) -> jax.Array:
    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q = _shard_q(dense(x, w["wq"]).reshape(B, S, H, hd))
    k, v = cross_kv(cfg, w, media)
    out = attention(q, k, v, causal=False, use_pallas=cfg.use_pallas,
                    pallas_device=cfg.pallas_device)
    return dense(out.reshape(B, S, H * hd), w["wo"])


def cross_decode(cfg: ModelConfig, w, x: jax.Array,
                 kv: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """Decode-time cross-attn against precomputed media K/V."""
    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q = dense(x, w["wq"]).reshape(B, S, H, hd)
    out = attention(q, kv[0], kv[1], causal=False,
                    use_pallas=cfg.use_pallas,
                    pallas_device=cfg.pallas_device)
    return dense(out.reshape(B, S, H * hd), w["wo"])
