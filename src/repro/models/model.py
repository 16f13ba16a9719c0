"""Top-level LM assembly: init / forward / loss / prefill / decode.

The layer stack is executed as ``lax.scan`` over *periods* (see blocks.py)
with per-slot weight stacks, wrapped in ``jax.checkpoint`` per the config's
remat policy.  The same code path serves:

  train_step   forward(mode="train") -> logits + aux -> CE loss
  prefill      forward(mode="prefill") -> logits + full KV/state cache
  decode_step  single token against the cache (the serve_step the
               decode_32k / long_500k shapes lower)

Encoder-decoder (whisper) runs the encoder stack first and feeds its output
as the decoder's cross-attention media.  Modality frontends are STUBS per
the assignment: inputs are precomputed frame/patch embeddings.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.models.blocks import (Sig, apply_layer, apply_layer_paged,
                                 apply_layer_prefill_paged, init_layer,
                                 init_layer_cache, init_norm, layer_sigs,
                                 schedule)
from repro.models.config import ModelConfig
from repro.models.layers import cdtype, embed_apply, norm_apply, unembed_apply
from repro.parallel.api import shard

__all__ = ["init_params", "forward", "loss_fn", "init_cache", "decode_step",
           "paged_decode_step", "paged_prefill_step", "prefill",
           "param_logical_axes", "LEARNED_POS_LEN"]

LEARNED_POS_LEN = 32768  # learned-pos table length (whisper decode_32k)


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(cfg: ModelConfig, key, sig: Sig, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_layer(cfg, k, sig))(keys)


def init_params(cfg: ModelConfig, key) -> Dict:
    dt = cdtype(cfg)
    first_k, period, n_periods = schedule(cfg)
    sigs = layer_sigs(cfg)
    ks = jax.random.split(key, 8 + first_k + period)
    p: Dict = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), dt)
                 * (1.0 / math.sqrt(cfg.d_model)),
        "final_norm": init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = jax.random.normal(
            ks[1], (cfg.d_model, cfg.vocab_size), dt) / math.sqrt(cfg.d_model)
    if cfg.pos_embed == "learned":
        p["pos_embed"] = jax.random.normal(
            ks[2], (LEARNED_POS_LEN, cfg.d_model), dt) * 0.01
    if first_k:
        p["layers0"] = [init_layer(cfg, ks[8 + i], sigs[i])
                        for i in range(first_k)]
    p["layers"] = tuple(
        _stack_init(cfg, ks[8 + first_k + s], sigs[first_k + s], n_periods)
        for s in range(period))
    if cfg.encoder:
        e = cfg.encoder
        enc_sig: Sig = ("enc_attn", False)
        p["encoder"] = {
            "pos": jax.random.normal(ks[3], (e.n_frames, cfg.d_model), dt) * 0.01,
            "layers": (_stack_init(cfg, ks[4], enc_sig, e.n_layers),),
            "norm": init_norm(cfg),
        }
    return p


# ---------------------------------------------------------------------------
# logical axes for sharding (leaf-name -> trailing-dims rule; leading
# stack/slot dims get None)
# ---------------------------------------------------------------------------

_LEAF_RULES = {
    "embed": ("vocab", "fsdp"),
    "unembed": ("fsdp", "vocab"),
    "pos_embed": (None, "fsdp"),
    "pos": (None, "fsdp"),
    "wq": ("fsdp", "tp"), "wk": ("fsdp", "tp"), "wv": ("fsdp", "tp"),
    "wo": ("tp", "fsdp"),
    "wi": ("fsdp", "tp"), "wg": ("fsdp", "tp"),
    "w_dkv": ("fsdp", None), "w_uk": (None, "tp"), "w_uv": (None, "tp"),
    "router": ("fsdp", None),
    "we_g": ("expert", "fsdp", None), "we_i": ("expert", "fsdp", None),
    "we_o": ("expert", None, "fsdp"),
    "in_proj": ("fsdp", "tp"), "out_proj": ("tp", "fsdp"),
    "conv_w": (None, "tp"), "conv_b": ("tp",),
}


def _leaf_name(path) -> str:
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    return ""


def param_axes_rule(path, leaf):
    """Logical axes for one parameter leaf (by leaf name + ndim; leading
    stack/slot dims get None)."""
    name = _leaf_name(path)
    core = _LEAF_RULES.get(name, ())
    nd = len(leaf.shape)
    if len(core) > nd:
        core = core[len(core) - nd:]
    return (None,) * (nd - len(core)) + tuple(core)


def param_logical_axes(params) -> Dict:
    """Pytree of logical-axis tuples matching ``params``' structure."""
    return jax.tree_util.tree_map_with_path(param_axes_rule, params)


# ---------------------------------------------------------------------------
# forward paths
# ---------------------------------------------------------------------------

def _encode(cfg: ModelConfig, params, frames: jax.Array) -> jax.Array:
    """Whisper encoder over precomputed frame embeddings (B, F, D)."""
    enc = params["encoder"]
    h = frames.astype(cdtype(cfg)) + enc["pos"][None, :frames.shape[1]]
    h = shard(h, "batch", None, None)
    positions = jnp.arange(frames.shape[1], dtype=jnp.int32)

    def body(carry, ws):
        hh, = carry
        hh, _ = apply_layer(cfg, ("enc_attn", False), ws, hh, mode="train",
                            positions=positions)
        return (hh,), None

    (h,), _ = jax.lax.scan(_remat(cfg, body), (h,), enc["layers"][0])
    return norm_apply(cfg, enc["norm"], h)


def _embed_in(cfg: ModelConfig, params, tokens, pos0=None):
    h = embed_apply(cfg, params["embed"], tokens)
    if cfg.pos_embed == "learned":
        S = tokens.shape[1]
        if pos0 is None:
            h = h + params["pos_embed"][None, :S]
        else:
            pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos0, S, 0)
            h = h + pe[None]
    return h


def _logits_out(cfg: ModelConfig, params, h):
    h = norm_apply(cfg, params["final_norm"], h)
    w_un = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return unembed_apply(cfg, w_un, h)


def forward(cfg: ModelConfig, params, batch: Dict, *, mode: str = "train",
            max_len: int = 0, with_hidden: bool = False, last_pos=None):
    """Returns (logits, aux) for train; (logits, aux, cache) for prefill.
    ``with_hidden`` additionally returns the final-normed hidden states
    (used by the memory-lean CE loss).  ``last_pos`` (prefill only)
    names the true last prompt position(s) — an int32 scalar or a
    per-request (B,) vector — so right-padded prompts (the paged serve
    engine pads to page multiples) slice their logits at the real last
    token instead of the padding; causal attention makes the padded
    positions inert for every earlier row."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    first_k, period, n_periods = schedule(cfg)
    sigs = layer_sigs(cfg)
    positions = jnp.arange(S, dtype=jnp.int32)
    media = batch.get("media")
    if cfg.encoder:
        media = _encode(cfg, params, batch["frames"])
    h = _embed_in(cfg, params, tokens)

    aux = jnp.zeros((), jnp.float32)
    caches0: List = []
    for i in range(first_k):
        out = apply_layer(cfg, sigs[i], params["layers0"][i], h, mode=mode,
                          positions=positions, media=media, max_len=max_len)
        if mode == "prefill":
            h, a, c = out
            caches0.append(c)
        else:
            h, a = out
        aux = aux + a

    slot_sigs = [sigs[first_k + s] for s in range(period)]

    if mode == "prefill":
        def body(carry, ws):
            hh, ax = carry
            slot_caches = []
            for s in range(period):
                hh, a, c = apply_layer(cfg, slot_sigs[s], ws[s], hh,
                                       mode="prefill", positions=positions,
                                       media=media, max_len=max_len)
                hh = shard(hh, "batch", "seq", None)
                ax = ax + a
                slot_caches.append(c)
            return (hh, ax), tuple(slot_caches)

        (h, aux), layer_caches = jax.lax.scan(body, (h, aux), params["layers"])
        # serving only needs the last position's logits — slice BEFORE the
        # unembed matmul so the (B, S, V) tensor is never formed
        if last_pos is None:
            h_last = h[:, -1:]
        else:
            lp = jnp.asarray(last_pos, jnp.int32)
            if lp.ndim == 0:
                h_last = jax.lax.dynamic_slice_in_dim(h, lp, 1, 1)
            else:
                h_last = jnp.take_along_axis(h, lp[:, None, None], axis=1)
        logits = _logits_out(cfg, params, h_last)
        cache = {"layers0": caches0, "layers": layer_caches}
        return logits, aux, cache

    def body(carry, ws):
        hh, ax = carry
        for s in range(period):
            hh, a = apply_layer(cfg, slot_sigs[s], ws[s], hh, mode="train",
                                positions=positions, media=media)
            hh = shard(hh, "batch", "seq", None)
            ax = ax + a
        return (hh, ax), None

    (h, aux), _ = jax.lax.scan(_remat(cfg, body), (h, aux), params["layers"])
    h = norm_apply(cfg, params["final_norm"], h)
    # constrain h (and thereby its cotangent — wsc transposes to wsc): the
    # unembed backward otherwise materialises an unsharded (B,S,D) f32 grad
    h = shard(h, "batch", "seq", None)
    if with_hidden:
        # loss path: the chunked CE computes its own (batch-sliced) logits;
        # materialising the full (B,S,V) tensor here would defeat it
        return None, aux, h
    w_un = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = unembed_apply(cfg, w_un, h)
    return logits, aux


def loss_fn(cfg: ModelConfig, params, batch: Dict) -> Tuple[jax.Array, Dict]:
    """Next-token cross-entropy (f32) + MoE aux loss.

    CE = mean(logsumexp(logits) - logit[label]).  The correct-class logit
    is a masked sum over the (sharded) logits — compare-select-reduce fuses
    with the unembed dot and stays sharded; a take()/gather formulation
    materialises (D, V)-scale scatter-adds in the backward.
    """
    _, aux, h = forward(cfg, params, batch, mode="train", with_hidden=True)
    labels = batch["labels"]
    w_un = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    ce = _chunked_ce(cfg, h, w_un, labels)
    return ce + aux, {"ce": ce, "aux": aux}


def _chunked_ce(cfg: ModelConfig, h, w_un, labels, n_chunks: int = 4):
    """Batch-chunked CE (§Perf): the (B,S,V) f32 logits chain (logits, exp,
    grads) dominates training byte traffic for large vocabs.  Chunking over
    the BATCH dim keeps sharding uniform across chunks (sequence-chunking
    would idle 15/16 devices per chunk under sequence sharding) and each
    chunk body is checkpointed so its logits are recomputed in the backward
    instead of saved: peak logits bytes drop by n_chunks.
    """
    from repro.models.layers import mm
    from repro.parallel.api import current_mesh as _cm
    B, S, D = h.shape
    V = w_un.shape[-1]
    # chunks must stay divisible by the batch-shard count, else each slice
    # lives on a subset of devices and GSPMD reshards per chunk
    mesh = _cm()
    shard_n = 1
    if mesh is not None:
        shard_n = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    while n_chunks > 1 and (B % n_chunks or (B // n_chunks) % shard_n):
        n_chunks -= 1
    bc = B // n_chunks
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, V), 2)

    @jax.checkpoint
    def chunk_ce(h_c, lab_c):
        logits = mm("bsd,dv->bsv", h_c, w_un)                 # (bc, S, V) f32
        from repro.parallel.api import current_mesh
        mesh = current_mesh()
        if mesh is not None and V % mesh.shape.get("model", 1) == 0:
            logits = shard(logits, "batch", None, "vocab")
        else:
            logits = shard(logits, "batch", "seq", None)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        eq = lab_c[..., None] == vocab_iota
        correct = jnp.sum(jnp.where(eq, logits, 0.0), axis=-1)
        return jnp.sum(lse - correct)

    total = jnp.zeros((), jnp.float32)
    for i in range(n_chunks):
        total = total + chunk_ce(h[i * bc:(i + 1) * bc],
                                 labels[i * bc:(i + 1) * bc])
    return total / (B * S)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               media_len: int = 0) -> Dict:
    if cfg.encoder and media_len == 0:
        media_len = cfg.encoder.n_frames
    if cfg.cross_attn and media_len == 0:
        media_len = cfg.cross_attn.n_media_tokens
    first_k, period, n_periods = schedule(cfg)
    sigs = layer_sigs(cfg)
    c: Dict = {"layers0": [init_layer_cache(cfg, sigs[i], batch, max_len,
                                            media_len)
                           for i in range(first_k)]}
    stacked = []
    for s in range(period):
        one = init_layer_cache(cfg, sigs[first_k + s], batch, max_len,
                               media_len)
        stacked.append(jax.tree.map(
            lambda a: jnp.zeros((n_periods,) + a.shape, a.dtype), one))
    c["layers"] = tuple(stacked)
    return c


def cache_axes_rule(path, leaf):
    """Logical axes for one decode-cache leaf."""
    name = _leaf_name(path)
    nd = len(leaf.shape)
    if name in ("k", "v", "ck", "cv"):
        core = ("batch", "kv_seq", None, None)
    elif name in ("ckv", "krope"):
        core = ("batch", "kv_seq", None)
    elif name == "conv":
        core = ("batch", None, "tp")
    elif name == "state":
        core = ("batch", "heads", None, None)
    else:
        core = ()
    if len(core) > nd:
        core = core[len(core) - nd:]
    return (None,) * (nd - len(core)) + tuple(core)


def cache_logical_axes(cfg: ModelConfig, cache) -> Dict:
    """Logical axes for the decode cache (dry-run in_shardings)."""
    return jax.tree_util.tree_map_with_path(cache_axes_rule, cache)


def decode_step(cfg: ModelConfig, params, cache: Dict, tokens: jax.Array,
                pos: jax.Array) -> Tuple[jax.Array, Dict]:
    """One decode step.  tokens (B, 1) int32; pos scalar int32 (current
    write index = number of tokens already in the cache)."""
    first_k, period, n_periods = schedule(cfg)
    sigs = layer_sigs(cfg)
    h = _embed_in(cfg, params, tokens, pos0=pos)

    new0: List = []
    for i in range(first_k):
        h, nc = apply_layer(cfg, sigs[i], params["layers0"][i], h,
                            mode="decode", cache=cache["layers0"][i], pos=pos)
        new0.append(nc)

    slot_sigs = [sigs[first_k + s] for s in range(period)]

    def body(h, x):
        ws, cs = x
        new_cs = []
        for s in range(period):
            h, nc = apply_layer(cfg, slot_sigs[s], ws[s], h, mode="decode",
                                cache=cs[s], pos=pos)
            new_cs.append(nc)
        return h, tuple(new_cs)

    h, new_layers = jax.lax.scan(body, h, (params["layers"], cache["layers"]))
    logits = _logits_out(cfg, params, h)
    return logits, {"layers0": new0, "layers": new_layers}


def prefill(cfg: ModelConfig, params, batch: Dict, max_len: int,
            last_pos=None) -> Tuple[jax.Array, Dict]:
    """Process a prompt, returning (last-position logits, filled cache).
    ``last_pos`` slices right-padded prompts at their true last token
    (see :func:`forward`)."""
    logits, _, cache = forward(cfg, params, batch, mode="prefill",
                               max_len=max_len, last_pos=last_pos)
    return logits[:, -1:], cache


def _paged_layers(cfg: ModelConfig, params, cache: Dict, h, apply):
    """Run the paged layers: the ``first_k`` leading ones one by one, then
    the scan over periods that carries the pool stacks (each layer writes
    its rows into the stack and reads it through its index, so no step
    slices a layer's pool out or builds a second stack).  ``apply(sig, w,
    h, pools, layer)`` is one layer -> (h, new_pools, routed).  Returns
    (h, new_cache, counters): ``counters`` holds ``routed_rows``, the MoE
    assignments computed summed over the layers, for a MoE config only."""
    first_k, period, n_periods = schedule(cfg)
    sigs = layer_sigs(cfg)
    rr = jnp.zeros((), jnp.int32) if cfg.moe else None

    def add(rr, routed):
        return rr if routed is None else rr + routed

    new0: List = []
    for i in range(first_k):
        h, nc, routed = apply(sigs[i], params["layers0"][i], h,
                              cache["layers0"][i], None)
        rr = add(rr, routed)
        new0.append(nc)

    slot_sigs = [sigs[first_k + s] for s in range(period)]

    def body(carry, x):
        h, pools, rr = carry
        ws, i = x
        new_pools = []
        for s in range(period):
            h, nc, routed = apply(slot_sigs[s], ws[s], h, pools[s], i)
            rr = add(rr, routed)
            new_pools.append(nc)
        return (h, tuple(new_pools), rr), None

    (h, new_layers, rr), _ = jax.lax.scan(
        body, (h, cache["layers"], rr),
        (params["layers"], jnp.arange(n_periods, dtype=jnp.int32)))
    counters = {} if rr is None else {"routed_rows": rr}
    return h, {"layers0": new0, "layers": new_layers}, counters


def paged_decode_step(cfg: ModelConfig, params, cache: Dict,
                      tokens: jax.Array, block_tables: jax.Array,
                      lens: jax.Array) -> Tuple[jax.Array, Dict, Dict]:
    """One continuous-batching decode tick over the block-paged cache.

    tokens (B, 1) int32 — each engine slot's pending token; block_tables
    (B, NB) int32 logical->physical pool block maps (shared across
    layers: every layer's pool is indexed by the same table); lens (B,)
    int32 per-request cache lengths (write index AND RoPE position; 0 for
    a slot that is not decoding).
    The cache pytree mirrors :func:`init_cache`'s structure: each
    ``layers0`` leaf is one layer's (P, KV, page, hd) pool and each
    ``layers`` leaf the (n_periods, P, KV, page, hd) stack of a period
    slot's pools (MLA: latent rows, KV = 1) — build it with
    ``repro.serve.PagedKVCache``.  Unlike :func:`decode_step` there is
    no batch-wide ``pos``: slots decode at independent offsets, which is
    what lets one compiled step serve ragged in-flight requests.

    The layer scan carries the pool stacks and hands each layer its
    index in them (:func:`_paged_layers`).  Donated by the caller, the
    stacks are updated in place.  Returns (logits, new cache, counters):
    a MoE config's ``counters["routed_rows"]`` counts the assignments
    its held experts computed; other configs' are empty.
    """
    if cfg.pos_embed != "rope":
        raise NotImplementedError(
            f"paged_decode_step: per-request positions need rope "
            f"(cfg.pos_embed={cfg.pos_embed!r})")
    h = _embed_in(cfg, params, tokens)
    h, new_cache, counters = _paged_layers(
        cfg, params, cache, h,
        lambda sig, w, h, pools, i: apply_layer_paged(
            cfg, sig, w, h, pools, block_tables, lens, i))
    return _logits_out(cfg, params, h), new_cache, counters


def paged_prefill_step(cfg: ModelConfig, params, cache: Dict,
                       tokens: jax.Array, block_tables: jax.Array,
                       lens: jax.Array, n_valid: jax.Array, *,
                       aligned: bool = False) -> Tuple[jax.Array, Dict, Dict]:
    """One continuation-prefill chunk over the block-paged cache.

    tokens (B, C) int32 — a fixed-size chunk of each request's uncached
    prompt suffix, right-padded past ``n_valid``; block_tables (B, NB)
    and lens (B,) as in :func:`paged_decode_step` (``lens`` = tokens
    already cached = the chunk's global start position), and so are the
    cache pytree, the layer scan that carries its stacks and the
    counters (of the chunk's valid rows).  Each layer writes the chunk's
    K/V (or latent) rows into the pool and attends back through the
    block table, so a chunk sees both earlier chunks of its own prompt
    AND any prefix blocks *shared* with other requests.  Returns the
    logits at each request's last valid chunk row (B, 1, V) — only
    meaningful for the final chunk, where that row is the last prompt
    token — plus the updated pool pytree and the counters.  Chunking the
    prompt this way is the incremental-admission path: one fixed
    compiled shape serves any prompt length, and long prompts interleave
    with decode ticks instead of stalling them.  ``aligned`` forwards
    the single-block fast-write promise (B == 1, chunk size divides the
    page) to the attention layers.
    """
    if cfg.pos_embed != "rope":
        raise NotImplementedError(
            f"paged_prefill_step: per-request positions need rope "
            f"(cfg.pos_embed={cfg.pos_embed!r})")
    nv = jnp.asarray(n_valid, jnp.int32)
    h = _embed_in(cfg, params, tokens)
    h, new_cache, counters = _paged_layers(
        cfg, params, cache, h,
        lambda sig, w, h, pools, i: apply_layer_prefill_paged(
            cfg, sig, w, h, pools, block_tables, lens, nv, i,
            aligned=aligned))
    # logits only at the last valid row — sliced before the unembed, like
    # prefill's last_pos path, so the (B, C, V) tensor is never formed
    h_last = jnp.take_along_axis(h, (nv - 1)[:, None, None], axis=1)
    return _logits_out(cfg, params, h_last), new_cache, counters
