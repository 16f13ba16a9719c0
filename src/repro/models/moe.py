"""Mixture-of-Experts FFN: top-k router + capacity-based gather dispatch.

Dispatch design (matters for the roofline): the classic one-hot-einsum
dispatch charges O(tokens x E x C x D) *fake* matmul FLOPs to HLO, polluting
the compute roofline term by >10x on qwen3 (128 experts, top-8).  We instead
build integer slot maps from the router output (cumsum over one-hot int32 —
cheap) and move tokens with gathers:

  dispatch:  xbuf[g, e, c, :]  = x[g, src[g, e, c], :]     (take_along_axis)
  experts:   ybuf = swiglu(xbuf @ We_in) @ We_out          (E-sharded einsum)
  combine:   y[g, t]          = sum_k gate * ybuf[g, e(t,k), p(t,k), :]

Expert weights and the (G, E, C, D) buffers shard E over the "expert"
logical axis (model); the combine gather crossing the expert axis is where
GSPMD inserts the all-to-all-class collective — the EP communication the
paper's scoreboard would attribute to the interconnect, and a hillclimb
target.  Training (:func:`moe_apply`) drops past a capacity, Switch
semantics (first-come within the group, position >= C dropped); serving
(:func:`moe_serve`) is dropless.  A device may hold a share of the
experts (``MoESpec.n_held`` / ``held_offset``, expert parallelism): the
router still scores all of them, and only the held experts' part of the
layer is computed — the other devices' part is theirs to add.

Dual execution path: with ``cfg.use_pallas`` the three expert matmuls
(gate/up/down projections over the (E, C, D) slot buffers) route through
``repro.kernels.dispatch`` to the ``kernels.moe_gmm`` grouped-GEMM Pallas
kernel — the batch groups fold into the per-expert row dim, and
capacity-trimmed (non-128-multiple) C plus ragged D/F pad via the
ops-layer zero-pad/slice path, which is exact for a GEMM.  On a mesh the
GMM runs under ``shard_map`` with E sharded over the "expert" axis —
the dispatch/combine gathers (the EP collectives) stay in the
surrounding XLA program.  Unplannable (local) shapes fall back to the
einsum with a logged reason.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch as kdispatch
from repro.kernels import ops as kops
from repro.models.config import ModelConfig
from repro.models.layers import cdtype, dense, mm
from repro.parallel.api import current_mesh, shard

__all__ = ["init_moe", "moe_apply", "moe_serve", "router_topk", "capacity"]


def capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    m = cfg.moe
    c = int(math.ceil(tokens_per_group * m.top_k * m.capacity_factor
                      / m.n_experts))
    return max(4, ((c + 3) // 4) * 4)  # pad to a multiple of 4


def init_moe(cfg: ModelConfig, key) -> Dict:
    m = cfg.moe
    D, E, F = cfg.d_model, m.held, m.d_ff_expert
    ks = jax.random.split(key, 6)
    dt = cdtype(cfg)
    s = 1.0 / math.sqrt(D)
    w = {
        "router": jax.random.normal(ks[0], (D, m.n_experts), jnp.float32) * s,
        "we_g": jax.random.normal(ks[1], (E, D, F), dt) * s,
        "we_i": jax.random.normal(ks[2], (E, D, F), dt) * s,
        "we_o": jax.random.normal(ks[3], (E, F, D), dt)
                * (1.0 / math.sqrt(F) / math.sqrt(max(1, cfg.n_layers))),
    }
    if m.n_shared:
        Fs = m.d_ff_shared or m.n_shared * F
        w["shared"] = {
            "wg": jax.random.normal(ks[4], (D, Fs), dt) * s,
            "wi": jax.random.normal(ks[4], (D, Fs), dt) * s,
            "wo": jax.random.normal(ks[5], (Fs, D), dt) * (1.0 / math.sqrt(Fs)),
        }
    return w


def router_topk(cfg: ModelConfig, w_router, x, precision=None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router: f32 softmax over all ``n_experts``, top-k, gates
    renormalised to sum to 1 when ``moe.norm_topk`` (else the softmax
    scores themselves).  ``precision`` is the logits matmul's.

    x: (G, S, D) -> gates (G, S, K) f32, idx (G, S, K) i32 (global expert
    ids), aux_loss scalar.
    """
    m = cfg.moe
    logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32),
                        w_router.astype(jnp.float32), precision=precision)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss.
    me = jnp.mean(probs, axis=(0, 1))                                # (E,)
    ce = jnp.mean(jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32),
                  axis=(0, 1, 2))                                    # (E,)
    aux = m.n_experts * jnp.sum(me * ce)
    return gates, idx, aux


def _slot_maps(idx: jax.Array, E: int, C: int):
    """Integer slot maps from expert assignments.

    idx: (G, A) ids among the ``E`` held experts (A = S*K assignments in
    token order); an id outside [0, E) names an expert held elsewhere.
    Returns:
      pos   (G, A)   position of each assignment within its expert (i32)
      keep  (G, A)   held and pos < C
      src   (G, E*C) assignment index feeding each expert slot (0 if empty)
      used  (G, E*C) slot occupancy mask
    """
    G, A = idx.shape
    held = (idx >= 0) & (idx < E)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)    # (G,A,E); 0 if not held
    onehot = shard(onehot, "batch", None, "expert")
    pos_all = jnp.cumsum(onehot, axis=1) - 1                          # (G,A,E)
    pos_all = shard(pos_all, "batch", None, "expert")
    pos = jnp.take_along_axis(pos_all, jnp.clip(idx, 0, E - 1)[..., None],
                              axis=-1)[..., 0]
    keep = held & (pos < C)
    # dropped and absent assignments scatter out of bounds -> mode="drop"
    slot = jnp.where(keep, idx * C + pos, E * C)
    src = jnp.zeros((G, E * C), jnp.int32)
    arange = jnp.broadcast_to(jnp.arange(A, dtype=jnp.int32)[None], (G, A))
    src = src.at[jnp.arange(G)[:, None], slot].set(arange, mode="drop")
    used = jnp.zeros((G, E * C), jnp.bool_)
    used = used.at[jnp.arange(G)[:, None], slot].set(True, mode="drop")
    return pos, keep, shard(src, "batch", "expert"), \
        shard(used, "batch", "expert")


def _expert_mm(x4: jax.Array, w3: jax.Array, *, use_pallas: bool,
               device=None, out_dtype=None) -> jax.Array:
    """Per-expert batched matmul (B, E, C, K) @ (E, K, N) -> (B, E, C, N).

    f32 accumulation either way.  With ``use_pallas`` the batch groups
    fold into the per-expert row dim and the op dispatches to the
    ``moe_gmm`` grouped-GEMM kernel (ragged C/K/N zero-pad exactly);
    otherwise (or on fallback) the E-sharded einsum runs.
    """
    if use_pallas:
        B, E, C, K = x4.shape
        N = w3.shape[2]
        dec = kdispatch.decide(
            "moe_gmm", {"E": E, "C": B * C, "K": K, "N": N},
            dtype=x4.dtype, device=device,
            sharded=current_mesh() is not None)
        if dec.use_kernel:
            xe = x4.transpose(1, 0, 2, 3).reshape(E, B * C, K)
            y = kops.moe_gmm(xe, w3,
                             plan=None if dec.sharded else dec.plan,
                             device=device, pad=True, sharded=dec.sharded)
            y = y.reshape(E, B, C, N).transpose(1, 0, 2, 3)
            # the kernel accumulates in f32 but stores in x4.dtype, so
            # (unlike mm's true-f32 output) the bf16 path takes one extra
            # rounding here before the f32 gate math — covered by the
            # bf16 parity tolerance
            return y.astype(jnp.float32 if out_dtype is None else out_dtype)
    return mm("beck,ekn->becn", x4, w3, out_dtype=out_dtype)


def _experts(cfg: ModelConfig, w, x: jax.Array, gates: jax.Array,
             idx: jax.Array, C: int, valid=None) -> Tuple[jax.Array, jax.Array]:
    """Dispatch, the held experts' SwiGLU, combine, plus the shared
    experts.  x (G, S, D); gates/idx (G, S, K) from :func:`router_topk`;
    ``valid`` (G, S) marks the rows whose assignments count (the others
    reach no expert).  Returns (y (G, S, D), keep (G, S*K): the
    assignments computed)."""
    m = cfg.moe
    G, S, D = x.shape
    E, K = m.held, m.top_k
    idx_flat = idx.reshape(G, S * K) - m.held_offset   # (t, k) order, local
    if valid is not None:
        idx_flat = jnp.where(jnp.repeat(valid, K, axis=1), idx_flat, E)
    pos, keep, src, used = _slot_maps(idx_flat, E, C)

    # token index of each assignment; gather tokens into expert slot buffers
    tok_of_src = src // K                                             # (G, E*C)
    xbuf = jnp.take_along_axis(x, tok_of_src[..., None], axis=1)      # (G,E*C,D)
    xbuf = xbuf * used[..., None].astype(x.dtype)
    xbuf = xbuf.reshape(G, E, C, D)
    xbuf = shard(xbuf, "batch", "expert", None, None)

    # expert FFN (E-sharded batched einsum, or the moe_gmm grouped-GEMM
    # kernel under cfg.use_pallas; f32 accumulation either way)
    h = jax.nn.silu(_expert_mm(xbuf, w["we_g"], use_pallas=cfg.use_pallas,
                               device=cfg.pallas_device)) \
        * _expert_mm(xbuf, w["we_i"], use_pallas=cfg.use_pallas,
                     device=cfg.pallas_device)
    h = h.astype(x.dtype)
    h = shard(h, "batch", "expert", None, None)
    ybuf = _expert_mm(h, w["we_o"], use_pallas=cfg.use_pallas,
                      device=cfg.pallas_device, out_dtype=x.dtype)
    # §Perf: reshard E@model -> D@model here (an all-to-all: each device
    # keeps 1/|model| of ybuf) so the combine gather below is LOCAL in its
    # passthrough dim.  Leaving ybuf expert-sharded makes GSPMD all-gather
    # the full (B,E,C,D) buffer to every device — measured ~1.2 TB/device
    # of all-gather wire on qwen3 train_4k vs ~E/(E-1) x local bytes here.
    ybuf = shard(ybuf, "batch", None, None, "tp")

    # combine: gather each kept assignment's slot output, weight, sum over k
    slot = jnp.where(keep, idx_flat * C + pos, 0)                     # (G,S*K)
    y_k = jnp.take_along_axis(ybuf.reshape(G, E * C, D), slot[..., None],
                              axis=1)                                 # (G,S*K,D)
    y_k = shard(y_k, "batch", None, "tp")
    gk = (gates.reshape(G, S * K) * keep.astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("bad,ba->bad", y_k, gk).reshape(G, S, K, D).sum(axis=2)
    y = shard(y, "batch", "seq", None)

    if m.n_shared:
        ws = w["shared"]
        hs = jax.nn.silu(dense(x, ws["wg"])) * dense(x, ws["wi"])
        y = y + dense(hs, ws["wo"])
    return y, keep


def moe_apply(cfg: ModelConfig, w, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Training: x (B, S, D) -> (y, aux_loss).  Groups = batch rows, each
    expert takes at most ``capacity`` rows of a group (Switch drops)."""
    # pin x's sharding with D on the model axis: D is the PASSTHROUGH dim
    # of the dispatch/combine gathers, so GSPMD partitions them AND their
    # backward scatter-adds (S-sharding would leave unsharded (B,S,D) f32
    # gradient scatters — the gathered dim can't partition vs indices)
    x = shard(x, "batch", None, "tp")
    gates, idx, aux = router_topk(cfg, w["router"], x)
    y, _ = _experts(cfg, w, x, gates, idx, capacity(cfg, x.shape[1]))
    return y, aux * cfg.moe.router_aux_weight


def moe_serve(cfg: ModelConfig, w, x: jax.Array,
              valid=None) -> Tuple[jax.Array, jax.Array]:
    """Serving: x (B, S, D) -> (y, routed_rows), dropless.

    The router scores every expert; only assignments to the held experts
    are computed, and none is dropped: all B*S tokens form one group, so
    an expert's buffer holds every row that can reach it (C = B*S; a
    token picks an expert at most once).  ``valid`` (B, S) leaves the
    other rows (chunk padding, idle slots) out of every expert.
    ``routed_rows`` (int32) counts the assignments computed, the work
    the padded buffers carry."""
    B, S, D = x.shape
    xt = x.reshape(1, B * S, D)
    # full f32 logits, as the published gate computes them: the top-k
    # choice at near ties decides which experts a token reaches
    gates, idx, _ = router_topk(cfg, w["router"], xt,
                                precision=jax.lax.Precision.HIGHEST)
    v = None if valid is None else jnp.broadcast_to(valid, (B, S)).reshape(
        1, B * S)
    y, keep = _experts(cfg, w, xt, gates, idx, B * S, v)
    return y.reshape(B, S, D), jnp.sum(keep, dtype=jnp.int32)
