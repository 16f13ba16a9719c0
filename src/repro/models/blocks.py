"""Decoder blocks + heterogeneous layer schedules.

A *layer signature* ``(mixer, is_moe)`` classifies every layer:
  mixer ∈ {"attn", "ssm", "cross", "attn_cross"}   (attn_cross = whisper dec)
  is_moe  — MoE FFN instead of dense MLP.

Architectures repeat a fixed *period* of signatures (dense: [attn]*1;
jamba: 8 layers with 1 attn + MoE every other; vlm: 4 self + 1 cross;
deepseek: 1 dense-FFN layer then homogeneous MoE).  ``model.py`` scans over
periods with per-slot weight stacks, so the compiled HLO stays small for
60-100 layer models.

Every block is pre-norm with residuals:  h += mixer(norm(h));
h += ffn(norm(h)); whisper decoder inserts a cross-attention sub-block.
Cross layers carry a learned tanh gate (llama-3.2-vision style).

Mixer execution path: the attention/SSD/MoE calls below read
``cfg.use_pallas`` — when set, each catalog-backed op dispatches to the
``repro.kernels`` Pallas layer (falling back per op, with a logged
reason, whenever the kernel contract cannot express it).  Nothing at the
block level changes: the dual path lives inside the mixers, and the
mesh context threads through ``parallel.api.set_mesh``'s trace-time
thread-local — under an active mesh the mixers plan per-shard and run
their kernels inside ``shard_map``, so blocks stay mesh-agnostic.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import cdtype, mlp_apply, norm_apply
from repro.models.moe import init_moe, moe_apply, moe_serve

__all__ = ["Sig", "layer_sigs", "schedule", "init_layer", "init_layer_cache",
           "apply_layer", "apply_layer_paged", "apply_layer_prefill_paged",
           "init_norm", "init_mlp"]

Sig = Tuple[str, bool]


def layer_sigs(cfg: ModelConfig) -> List[Sig]:
    sigs: List[Sig] = []
    for i in range(cfg.n_layers):
        if cfg.cross_attn and (i + 1) % cfg.cross_attn.period == 0:
            mixer = "cross"
        else:
            mixer = cfg.layer_kind(i)
        sigs.append((mixer, cfg.layer_is_moe(i)))
    return sigs


def schedule(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(first_k, period, n_periods): first_k unstacked layers, then
    n_periods repetitions of a `period`-layer cycle."""
    first_k = cfg.first_k_dense
    sigs = layer_sigs(cfg)[first_k:]
    n = len(sigs)
    for p in range(1, n + 1):
        if n % p == 0 and all(sigs[i] == sigs[i % p] for i in range(n)):
            return first_k, p, n // p
    return first_k, n, 1


def init_norm(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm_type == "layer":
        return {"scale": jnp.ones((d,), cdtype(cfg)),
                "bias": jnp.zeros((d,), cdtype(cfg))}
    return jnp.ones((d,), cdtype(cfg))


def init_mlp(cfg: ModelConfig, key) -> Dict:
    import math
    D, F = cfg.d_model, cfg.d_ff
    dt = cdtype(cfg)
    s = 1.0 / math.sqrt(D)
    so = 1.0 / math.sqrt(F) / math.sqrt(max(1, cfg.n_layers))
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp_type == "gelu":
        return {"wi": jax.random.normal(k1, (D, F), dt) * s,
                "bi": jnp.zeros((F,), dt),
                "wo": jax.random.normal(k2, (F, D), dt) * so,
                "bo": jnp.zeros((D,), dt)}
    return {"wg": jax.random.normal(k1, (D, F), dt) * s,
            "wi": jax.random.normal(k2, (D, F), dt) * s,
            "wo": jax.random.normal(k3, (F, D), dt) * so}


def init_layer(cfg: ModelConfig, key, sig: Sig) -> Dict:
    mixer, is_moe = sig
    ks = jax.random.split(key, 4)
    w: Dict = {"ln1": init_norm(cfg)}
    if is_moe or cfg.d_ff > 0:
        w["ln2"] = init_norm(cfg)
    if mixer in ("attn", "enc_attn"):
        w["mixer"] = (attn.init_mla(cfg, ks[0]) if cfg.mla and mixer == "attn"
                      else attn.init_attn(cfg, ks[0]))
    elif mixer == "ssm":
        w["mixer"] = ssm_mod.init_ssm(cfg, ks[0])
    elif mixer == "cross":
        w["mixer"] = attn.init_cross(cfg, ks[0])
        w["gate"] = jnp.zeros((), jnp.float32)
    elif mixer == "attn_cross":
        w["mixer"] = attn.init_attn(cfg, ks[0])
        w["lnx"] = init_norm(cfg)
        w["cross"] = attn.init_cross(cfg, ks[3])
    else:
        raise ValueError(mixer)
    if is_moe:
        w["ffn"] = init_moe(cfg, ks[1])
    elif cfg.d_ff > 0:
        w["ffn"] = init_mlp(cfg, ks[1])
    return w


def init_layer_cache(cfg: ModelConfig, sig: Sig, batch: int, max_len: int,
                     media_len: int = 0) -> Dict:
    """Zeroed decode cache for one layer (also the dry-run cache spec)."""
    mixer, _ = sig
    dt = cdtype(cfg)
    if mixer == "ssm":
        return ssm_mod.init_ssm_cache(cfg, batch)
    if mixer == "cross":
        shp = (batch, media_len, cfg.n_kv_heads, cfg.hd)
        return {"ck": jnp.zeros(shp, dt), "cv": jnp.zeros(shp, dt)}
    if mixer == "attn_cross":
        c = attn.init_attn_cache(cfg, batch, max_len)
        shp = (batch, media_len, cfg.n_kv_heads, cfg.hd)
        c["ck"] = jnp.zeros(shp, dt)
        c["cv"] = jnp.zeros(shp, dt)
        return c
    if cfg.mla:
        return attn.init_mla_cache(cfg, batch, max_len)
    return attn.init_attn_cache(cfg, batch, max_len)


def _ffn(cfg: ModelConfig, sig: Sig, w, h):
    if sig[1]:
        y, aux = moe_apply(cfg, w["ffn"], h)
    else:
        y, aux = mlp_apply(cfg, w["ffn"], h), jnp.zeros((), jnp.float32)
    return y, aux


def _pad_cache(x: jax.Array, max_len: int) -> jax.Array:
    """Right-pad a (B, S, ...) prefill tensor to cache length."""
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, max_len - x.shape[1])
    return jnp.pad(x, pad)


def apply_layer(cfg: ModelConfig, sig: Sig, w, h: jax.Array, *,
                mode: str, positions=None, media=None, cache=None,
                pos=None, max_len: int = 0):
    """Unified layer application.

    mode="train":   returns (h, aux)
    mode="prefill": returns (h, aux, cache)   — cache padded to max_len
    mode="decode":  returns (h, new_cache)    — h is (B, 1, D)
    """
    mixer, _ = sig
    hin = h
    x = norm_apply(cfg, w["ln1"], h)
    new_cache: Dict = {}

    if mixer == "enc_attn":
        y = attn.attn_train(cfg, w["mixer"], x, positions, causal=False)
    elif mixer == "attn":
        if mode == "decode":
            if cfg.mla:
                y, new_cache = attn.mla_decode(cfg, w["mixer"], x, cache, pos)
            else:
                y, new_cache = attn.attn_decode(cfg, w["mixer"], x, cache, pos)
        else:
            if cfg.mla:
                y = attn.mla_train(cfg, w["mixer"], x, positions)
            else:
                y = attn.attn_train(cfg, w["mixer"], x, positions)
            if mode == "prefill":
                new_cache = _attn_prefill_cache(cfg, w["mixer"], x, positions,
                                                max_len)
    elif mixer == "ssm":
        if mode == "decode":
            y, new_cache = ssm_mod.ssm_decode(cfg, w["mixer"], x, cache, pos)
        else:
            y = ssm_mod.ssm_train(cfg, w["mixer"], x)
            if mode == "prefill":
                new_cache = _ssm_prefill_cache(cfg, w["mixer"], x)
    elif mixer == "cross":
        if mode == "decode":
            y = attn.cross_decode(cfg, w["mixer"], x, (cache["ck"], cache["cv"]))
            new_cache = cache
        else:
            y = attn.cross_train(cfg, w["mixer"], x, media)
            if mode == "prefill":
                ck, cv = attn.cross_kv(cfg, w["mixer"], media)
                new_cache = {"ck": ck, "cv": cv}
        y = (jnp.tanh(w["gate"]) * y.astype(jnp.float32)).astype(y.dtype)
    elif mixer == "attn_cross":
        if mode == "decode":
            y, nc = attn.attn_decode(cfg, w["mixer"], x, cache, pos)
            h1 = hin + y
            xc = norm_apply(cfg, w["lnx"], h1)
            yc = attn.cross_decode(cfg, w["cross"], xc,
                                   (cache["ck"], cache["cv"]))
            nc["ck"], nc["cv"] = cache["ck"], cache["cv"]
            new_cache = nc
            y = y + yc  # combined residual below
        else:
            y = attn.attn_train(cfg, w["mixer"], x, positions)
            if mode == "prefill":
                new_cache = _attn_prefill_cache(cfg, w["mixer"], x, positions,
                                                max_len)
                ck, cv = attn.cross_kv(cfg, w["cross"], media)
                new_cache["ck"], new_cache["cv"] = ck, cv
            h1 = hin + y
            xc = norm_apply(cfg, w["lnx"], h1)
            y = y + attn.cross_train(cfg, w["cross"], xc, media)
    else:
        raise ValueError(mixer)

    h = hin + y
    if "ffn" in w:
        z = norm_apply(cfg, w["ln2"], h)
        f, aux = _ffn(cfg, sig, w, z)
        h = h + f
    else:
        aux = jnp.zeros((), jnp.float32)  # attn-free mamba2: mixer-only block
    if mode == "train":
        return h, aux
    if mode == "prefill":
        return h, aux, new_cache
    return h, new_cache


def _paged_mixer_check(sig: Sig, fn: str) -> None:
    if sig[0] != "attn":
        raise NotImplementedError(
            f"{fn}: only attention layers (GQA, or MLA's latent rows) "
            f"page; SSM state and cross-attention caches do not (got "
            f"mixer={sig[0]!r})")


def _ffn_serve(cfg: ModelConfig, sig: Sig, w, h, valid):
    """The block's FFN half when serving: (h, routed).  A MoE layer runs
    dropless (``moe_serve``) over the rows ``valid`` marks and returns
    the assignments its held experts computed; ``routed`` is None for a
    dense FFN."""
    if "ffn" not in w:
        return h, None
    z = norm_apply(cfg, w["ln2"], h)
    if sig[1]:
        f, routed = moe_serve(cfg, w["ffn"], z, valid)
        return h + f, routed
    return h + mlp_apply(cfg, w["ffn"], z), None


def apply_layer_paged(cfg: ModelConfig, sig: Sig, w, h: jax.Array,
                      cache: Dict, block_tables: jax.Array,
                      lens: jax.Array, layer=None):
    """One layer of a continuous-batching decode tick: like
    ``apply_layer(mode="decode")`` but against the shared block-paged
    pool, with per-request positions (``lens``) instead of a batch-wide
    ``pos`` scalar.  ``cache`` holds the (L, P, KV, page, hd) pool
    stacks — for MLA the latent ``ckv`` and rope-key ``kpe`` stacks — and
    ``layer`` this layer's index in them, or one layer's pools with
    ``layer=None`` (see :func:`attn.attn_decode_paged`).
    Returns (h, new_cache, routed); h is (B, 1, D), ``routed`` the MoE
    assignments computed for the active slots (lens > 0), or None.

    Only attention layers page — the SSM state is O(1) and needs no
    paging, cross-attention caches have other leaves — so heterogeneous
    schedules raise rather than silently mixing cache layouts
    (``PagedKVCache`` rejects such configs up front).
    """
    _paged_mixer_check(sig, "apply_layer_paged")
    hin = h
    x = norm_apply(cfg, w["ln1"], h)
    mixer = attn.mla_decode_paged if cfg.mla else attn.attn_decode_paged
    y, new_cache = mixer(cfg, w["mixer"], x, cache, block_tables, lens,
                         layer)
    valid = (jnp.asarray(lens) > 0)[:, None]
    h, routed = _ffn_serve(cfg, sig, w, hin + y, valid)
    return h, new_cache, routed


def apply_layer_prefill_paged(cfg: ModelConfig, sig: Sig, w, h: jax.Array,
                              cache: Dict, block_tables: jax.Array,
                              lens: jax.Array, n_valid: jax.Array,
                              layer=None, aligned: bool = False):
    """One layer of a continuation-prefill chunk: like
    :func:`apply_layer_paged` but over a (B, C, D) chunk of prompt
    tokens instead of a single pending token — the chunk's K/V (or
    latent) rows are written into the pool and attention reads the
    already-written prefix back through the block table.  ``cache`` and
    ``layer`` as in :func:`apply_layer_paged`.  Returns (h, new_cache,
    routed), ``routed`` counting the chunk's valid rows only.
    ``aligned`` passes through to the attention's single-block fast
    write path.  Same paging restriction: attention layers only.
    """
    _paged_mixer_check(sig, "apply_layer_prefill_paged")
    hin = h
    x = norm_apply(cfg, w["ln1"], h)
    mixer = attn.mla_prefill_paged if cfg.mla else attn.attn_prefill_paged
    y, new_cache = mixer(cfg, w["mixer"], x, cache, block_tables, lens,
                         n_valid, layer, aligned=aligned)
    C = h.shape[1]
    valid = jnp.arange(C)[None, :] < jnp.asarray(n_valid)[:, None]
    h, routed = _ffn_serve(cfg, sig, w, hin + y, valid)
    return h, new_cache, routed


def _attn_prefill_cache(cfg: ModelConfig, w, x, positions, max_len):
    """Recompute K/V (cheap vs attention itself) and pad to cache length."""
    if cfg.mla:
        c_kv, k_rope = attn._mla_latent(cfg, w, x, positions)
        return {"ckv": _pad_cache(c_kv, max_len),
                "krope": _pad_cache(k_rope, max_len)}
    _, k, v = attn._qkv(cfg, w, x, positions)
    return {"k": _pad_cache(k, max_len), "v": _pad_cache(v, max_len)}


def _ssm_prefill_cache(cfg: ModelConfig, w, x):
    """Re-run the SSD scan keeping final state + conv tail."""
    import jax.numpy as jnp
    s = cfg.ssm
    B, S, D = x.shape
    d_in = ssm_mod.d_inner_of(cfg)
    nh = d_in // s.head_dim
    zxbcdt = jnp.einsum("bsd,dk->bsk", x, w["in_proj"]).astype(x.dtype)
    z, xs, Bm, Cm, dtr = ssm_mod._split_proj(cfg, zxbcdt)
    xbc_raw = jnp.concatenate([xs, Bm, Cm], axis=-1)
    xbc = ssm_mod._conv_train(w, xbc_raw, s.d_conv)
    xs2, Bm2, Cm2 = jnp.split(xbc, [d_in, d_in + s.n_groups * s.d_state],
                              axis=-1)
    xh = xs2.reshape(B, S, nh, s.head_dim)
    Bg = Bm2.reshape(B, S, s.n_groups, s.d_state)
    Cg = Cm2.reshape(B, S, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    _, h_final = ssm_mod.ssd_chunked(xh, dt, A, Bg, Cg, s.chunk,
                                     use_pallas=cfg.use_pallas,
                                     pallas_device=cfg.pallas_device)
    return {"conv": xbc_raw[:, S - (s.d_conv - 1):, :],
            "state": h_final}
