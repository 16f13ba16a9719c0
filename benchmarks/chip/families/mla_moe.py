"""Latent attention with routed experts: DeepSeek-V2-Lite and its kin.

Everything the benchmark needs to know about the family, written from
the published description (DeepSeek-V2, arXiv:2405.04434, and the
model's ``config.json`` / ``modeling_deepseek.py``) and importing
nothing of the program:

* ``dims`` reads a configuration file (Hugging Face ``config.json``
  keys); ``n_routed_experts`` there is the experts this chip holds, and
  the ``reduced`` entry for it gives the router's published width and
  the held experts' offset;
* ``program_config`` maps it onto the program's ``ModelConfig`` fields
  (plain dicts for the nested groups);
* ``program_params`` / ``layer_weights`` make seeded random weights in
  the layout the program reads, on the device, in one jitted call; the
  reference makes the same values again, layer by layer;
* ``reference_logits`` is the plain float32 forward computed one layer
  at a time at ``highest`` matmul precision, attention in blocks of
  query rows: RMSNorm; multi-head latent attention in its textbook,
  un-absorbed form (the latent ``c_kv`` normed and up-projected to
  per-head keys and values, the decoupled roped key shared by the
  heads); YaRN RoPE; the dense SwiGLU of the leading layers; the
  softmax router over every expert with greedy top-k, gates
  renormalised only if ``norm_topk_prob``; the held experts' SwiGLU for
  the tokens routed to them, weighted by their gates; the shared
  experts' SwiGLU; the untied head.  The absent experts' part is left
  out, as the program leaves it out.  ``quant="fp8"`` computes every
  matmul (the router's too) from float8_e4m3 operands, the control that
  has to fail the comparison;
* ``token_flops`` / ``chunk_flops`` / ``decode_attention_work`` /
  ``moe_gmm_work`` count the model's work from its shapes.

Departures from the published code, none of which changes what is
computed on random weights:
* HF's ``apply_rotary_pos_emb`` first de-interleaves the rope columns
  (pairs (0, 1), (2, 3), ... become halves) and then rotates halves;
  with random weights that is rotate-half under a fixed permutation of
  the rope columns of ``q_proj`` and ``kv_a_proj_with_mqa``, so both
  sides here rotate halves directly.
* ``kv_b_proj`` (per head: 128 key rows, then 128 value rows) is held as
  two matrices, ``w_uk`` and ``w_uv``, each (kv_lora_rank, heads x 128).
* Expert e's weights are drawn from the layer key and e itself, so the
  uncut model and every chip's share draw the same experts.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Leaf order of one layer's weights; a leaf's key is fold_in(layer key,
# its index here), so adding a leaf never changes the others' values.
_LAYER_LEAVES = ("ln1", "ln2", "wq", "w_dkv", "kv_norm", "w_uk", "w_uv",
                 "wo", "wg", "wi", "wd", "router", "we_g", "we_i", "we_o",
                 "sh_g", "sh_i", "sh_o")
_GLOBAL_LEAVES = ("embed", "unembed", "final_norm")
NORM_JITTER = 0.1     # norm weights ~ 1 + N(0, 0.1): trained norms are not 1
# The router and the experts' down projections, in units of
# initializer_range.  A router at the source's 0.02 scores the 64 experts
# almost alike (top-6 mass ~0.3), and the held experts' part of a layer
# then sits under the served bf16's noise, so no fault in it shows.  At
# 3.5x the top-6 carry ~0.9 of the softmax mass (top-1 ~0.45), a
# decisive router; at 2.5x an expert's output is such that the uncut
# layer's routed part is about as large as its shared experts' part.
ROUTER_INIT = 3.5
EXPERT_OUT_INIT = 2.5
QBLOCK = 512          # reference attention: query rows per block
FP8_MAX = 448.0       # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Dims:
    L: int                # layers
    D: int                # hidden size
    H: int                # attention heads
    KV: int               # key/value heads (MLA: one per query head)
    hd: int               # query/key head width: qk_nope + qk_rope
    F: int                # dense FFN width (the leading layers)
    V: int                # vocabulary
    qkv_bias: bool
    R: int                # kv_lora_rank: the latent width
    nope: int             # qk_nope_head_dim
    rope: int             # qk_rope_head_dim
    vd: int               # v_head_dim
    first_dense: int      # first_k_dense_replace
    E: int                # experts the router scores (published)
    held: int             # experts this chip holds
    offset: int           # the first held expert's id
    topk: int
    Fe: int               # expert width
    Fs: int               # shared experts' width, run as one SwiGLU
    norm_topk: bool
    theta: float
    yarn: Tuple[float, int, float, float, float, float]
    eps: float
    init: float
    dtype: str

    @property
    def moe_layers(self) -> int:
        return self.L - self.first_dense

    @property
    def row(self) -> int:
        """Width of the latent row a token keeps per layer."""
        return self.R + self.rope


def dims(conf: Dict) -> Dims:
    if conf.get("tie_word_embeddings"):
        raise ValueError("mla_moe: tied embeddings are not modelled")
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"mla_moe: hidden_act {conf['hidden_act']!r}")
    if conf.get("q_lora_rank"):
        raise ValueError("mla_moe: query compression is not modelled")
    if (conf["scoring_func"], conf["topk_method"]) != ("softmax", "greedy"):
        raise ValueError("mla_moe: only softmax scores with greedy top-k")
    if float(conf["routed_scaling_factor"]) != 1.0 or conf["moe_layer_freq"] != 1:
        raise ValueError("mla_moe: routed_scaling_factor 1 and an expert "
                         "layer after every leading dense one only")
    rs = conf["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError(f"mla_moe: rope_scaling {rs.get('type')!r}")
    share = conf.get("reduced", {}).get("n_routed_experts", {})
    held = conf["n_routed_experts"]
    H = conf["num_attention_heads"]
    return Dims(
        L=conf["num_hidden_layers"], D=conf["hidden_size"], H=H,
        KV=conf["num_key_value_heads"],
        hd=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        F=conf["intermediate_size"], V=conf["vocab_size"],
        qkv_bias=bool(conf["attention_bias"]),
        R=conf["kv_lora_rank"], nope=conf["qk_nope_head_dim"],
        rope=conf["qk_rope_head_dim"], vd=conf["v_head_dim"],
        first_dense=conf["first_k_dense_replace"],
        E=int(share.get("published", held)), held=held,
        offset=int(share.get("offset", 0)),
        topk=conf["num_experts_per_tok"], Fe=conf["moe_intermediate_size"],
        Fs=conf["n_shared_experts"] * conf["moe_intermediate_size"],
        norm_topk=bool(conf["norm_topk_prob"]),
        theta=float(conf["rope_theta"]),
        yarn=(float(rs["factor"]), int(rs["original_max_position_embeddings"]),
              float(rs["beta_fast"]), float(rs["beta_slow"]),
              float(rs["mscale"]), float(rs["mscale_all_dim"])),
        eps=float(conf["rms_norm_eps"]),
        init=float(conf["initializer_range"]), dtype=conf["torch_dtype"])


def program_config(conf: Dict) -> Dict:
    """Keyword arguments of the program's ``ModelConfig``."""
    d = dims(conf)
    if d.qkv_bias:
        raise ValueError("mla_moe: attention_bias is not modelled")
    factor, orig, fast, slow, ms, ms_all = d.yarn
    return dict(
        family="moe", n_layers=d.L, d_model=d.D, n_heads=d.H,
        n_kv_heads=d.KV, d_ff=d.F, vocab_size=d.V, rope_theta=d.theta,
        norm_eps=d.eps, norm_type="rms", mlp_type="swiglu",
        pos_embed="rope", tie_embeddings=False, dtype=d.dtype,
        first_k_dense=d.first_dense,
        mla=dict(kv_lora_rank=d.R, q_lora_rank=0, qk_nope_dim=d.nope,
                 qk_rope_dim=d.rope, v_head_dim=d.vd),
        moe=dict(n_experts=d.E, top_k=d.topk, d_ff_expert=d.Fe,
                 n_shared=d.Fs // d.Fe, d_ff_shared=d.Fs,
                 norm_topk=d.norm_topk, n_held=d.held,
                 held_offset=d.offset),
        rope_scaling=dict(factor=factor, original_max_position=orig,
                          beta_fast=fast, beta_slow=slow, mscale=ms,
                          mscale_all_dim=ms_all))


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, np.uint32(word & 0xFFFFFFFF))
    return key


def _normal(key, shape, std: float, dt):
    # drawn and scaled in float32, rounded once to the served type: the
    # same values whichever program makes them
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)


def _norm_w(key, n: int, dt):
    return (1.0 + _normal(key, (n,), NORM_JITTER, jnp.float32)).astype(dt)


def layer_weights(d: Dims, key, i, moe: bool, dt=jnp.bfloat16) -> Dict:
    """Layer ``i``'s weights in the program's layout (``i`` may be
    traced); ``moe`` says whether it is an expert layer."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    ks = {n: jax.random.fold_in(k, j) for j, n in enumerate(_LAYER_LEAVES)}
    D, H, R, s = d.D, d.H, d.R, d.init
    mixer = {"wq": _normal(ks["wq"], (D, H * d.hd), s, dt),
             "w_dkv": _normal(ks["w_dkv"], (D, d.row), s, dt),
             "kv_norm": _norm_w(ks["kv_norm"], R, dt),
             "w_uk": _normal(ks["w_uk"], (R, H * d.nope), s, dt),
             "w_uv": _normal(ks["w_uv"], (R, H * d.vd), s, dt),
             "wo": _normal(ks["wo"], (H * d.vd, D), s, dt)}
    out = {"ln1": _norm_w(ks["ln1"], D, dt), "ln2": _norm_w(ks["ln2"], D, dt),
           "mixer": mixer}
    if not moe:
        out["ffn"] = {"wg": _normal(ks["wg"], (D, d.F), s, dt),
                      "wi": _normal(ks["wi"], (D, d.F), s, dt),
                      "wo": _normal(ks["wd"], (d.F, D), s, dt)}
        return out
    ids = d.offset + jnp.arange(d.held)

    def experts(name, shape, std):
        return jax.vmap(lambda e: _normal(jax.random.fold_in(ks[name], e),
                                          shape, std, dt))(ids)

    out["ffn"] = {"router": _normal(ks["router"], (D, d.E),
                                    ROUTER_INIT * s, dt),
                  "we_g": experts("we_g", (D, d.Fe), s),
                  "we_i": experts("we_i", (D, d.Fe), s),
                  "we_o": experts("we_o", (d.Fe, D), EXPERT_OUT_INIT * s),
                  "shared": {"wg": _normal(ks["sh_g"], (D, d.Fs), s, dt),
                             "wi": _normal(ks["sh_i"], (D, d.Fs), s, dt),
                             "wo": _normal(ks["sh_o"], (d.Fs, D), s, dt)}}
    return out


def global_weights(d: Dims, key, name: str, dt=jnp.bfloat16):
    k = jax.random.fold_in(jax.random.fold_in(key, 0),
                           _GLOBAL_LEAVES.index(name))
    if name == "embed":
        return _normal(k, (d.V, d.D), d.init, dt)
    if name == "unembed":
        return _normal(k, (d.D, d.V), d.init, dt)
    return _norm_w(k, d.D, dt)


def program_params(d: Dims, key) -> Dict:
    """The program's parameter pytree in the served bf16: the leading
    dense layers one by one, the expert layers stacked on a leading axis
    (one scan period).  Jit this: one device call."""
    return {"embed": global_weights(d, key, "embed"),
            "unembed": global_weights(d, key, "unembed"),
            "final_norm": global_weights(d, key, "final_norm"),
            "layers0": [layer_weights(d, key, i, False)
                        for i in range(d.first_dense)],
            "layers": (jax.vmap(lambda i: layer_weights(d, key, i, True))(
                jnp.arange(d.first_dense, d.L)),)}


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------

def _q8(x, axis):
    """Round ``x`` to float8_e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant):
    """a (..., k) @ b (k, n) in float32, or from fp8 operands."""
    if quant == "fp8":
        a, b = _q8(a, -1), _q8(b, None)
    return a @ b


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(d: Dims) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies for the rope columns:
    ``theta ** (-2i / rope)`` for the pairs that turn fast (below the
    correction dim of ``beta_fast`` rotations over the original length),
    the same divided by ``factor`` above that of ``beta_slow``, a linear
    ramp between."""
    factor, orig, fast, slow, _, _ = d.yarn
    dim, half = d.rope, d.rope // 2

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(d.theta))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)),
                                                    dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / d.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (extra / factor) * ramp + extra * (1.0 - ramp)


def softmax_scale(d: Dims) -> float:
    """1 / sqrt(qk head width) times YaRN's mscale(factor, mscale_all_dim)
    squared."""
    factor, _, _, _, _, ms_all = d.yarn
    return _mscale(factor, ms_all) ** 2 / math.sqrt(d.hd)


def _rope(x, pos, d: Dims):
    """Rotate halves of x (S, heads, rope) at YaRN frequencies; cos and
    sin scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    factor, _, _, _, ms, ms_all = d.yarn
    half = d.rope // 2
    inv = jnp.asarray(yarn_inv_freq(d), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv                # (S, half)
    m = _mscale(factor, ms) / _mscale(factor, ms_all)
    cos, sin = jnp.cos(ang)[:, None] * m, jnp.sin(ang)[:, None] * m
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(d: Dims, q, k, v, seg, quant):
    """Causal attention over one packed row: q, k (S, H, qk); v (S, H,
    vd); a token sees the earlier tokens of its own segment."""
    S = q.shape[0]
    if quant == "fp8":
        k, v = _q8(k, -1), _q8(v, -1)
    cols = jnp.arange(S)
    scale = softmax_scale(d)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, 0)
        sq = jax.lax.dynamic_slice_in_dim(seg, i * QBLOCK, QBLOCK, 0)
        if quant == "fp8":
            qb = _q8(qb, -1)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = i * QBLOCK + jnp.arange(QBLOCK)
        see = (cols[None, :] <= rows[:, None]) & (seg[None, :] == sq[:, None])
        s = jnp.where(see[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(S // QBLOCK))
    return out.reshape(S, d.H, d.vd)


def _swiglu(x, wg, wi, wo, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wi, quant), wo, quant)


def routing(d: Dims, x, w_router, quant=None):
    """(gates (S, topk), expert ids (S, topk)): softmax over all ``E``
    experts, greedy top-k, renormalised only if ``norm_topk``."""
    probs = jax.nn.softmax(_mm(x, w_router, quant), axis=-1)
    gates, idx = jax.lax.top_k(probs, d.topk)
    if d.norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    return gates, idx


def _experts(d: Dims, f, x, quant):
    """The held experts' part of an expert layer, plus the shared
    experts: each held expert's SwiGLU over every row, weighted by the
    gate with which the row chose it (0 if it did not).  Also returns
    each row's number of assignments to held experts."""
    gates, idx = routing(d, x, f["router"], quant)
    held = jnp.sum((idx >= d.offset) & (idx < d.offset + d.held), -1)

    def one(y, e):
        g = jnp.sum(jnp.where(idx == d.offset + e, gates, 0.0), -1)
        ye = _swiglu(x, f["we_g"][e], f["we_i"][e], f["we_o"][e], quant)
        return y + g[:, None] * ye, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(d.held))
    sh = f["shared"]
    return y + _swiglu(x, sh["wg"], sh["wi"], sh["wo"], quant), held


def _layer(d: Dims, quant, moe: bool, w, h, seg, pos):
    """One pre-norm block over one packed row h (S, D), float32: the new
    h, and the row's assignments to held experts (0 in a dense layer),
    padding left out."""
    S = h.shape[0]
    m = w["mixer"]
    x = _rms(h, w["ln1"], d.eps)
    q = _mm(x, m["wq"], quant).reshape(S, d.H, d.hd)
    q = jnp.concatenate([q[..., :d.nope], _rope(q[..., d.nope:], pos, d)], -1)
    kv = _mm(x, m["w_dkv"], quant)                              # (S, R+rope)
    c = _rms(kv[:, :d.R], m["kv_norm"], d.eps)
    k_pe = _rope(kv[:, None, d.R:], pos, d)                     # (S, 1, rope)
    k_nope = _mm(c, m["w_uk"], quant).reshape(S, d.H, d.nope)
    v = _mm(c, m["w_uv"], quant).reshape(S, d.H, d.vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, d.H, d.rope))],
                        -1)
    o = _attend(d, q, k, v, seg, quant)
    h = h + _mm(o.reshape(S, d.H * d.vd), m["wo"], quant)
    x = _rms(h, w["ln2"], d.eps)
    f = w["ffn"]
    if moe:
        y, held = _experts(d, f, x, quant)
        return h + y, jnp.sum(jnp.where(seg >= 0, held, 0))
    return h + _swiglu(x, f["wg"], f["wi"], f["wo"], quant), jnp.int32(0)


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _layer_f32(d: Dims, quant, moe, w, h, seg, pos):
    return _layer(d, quant, moe, _f32(w), h, seg, pos)


@functools.lru_cache(maxsize=None)
def _programs(d: Dims, quant: Optional[str]):
    # weights leave their own program in the served bf16 and are widened
    # inside the next one, so they hold exactly the values served
    layer_w = jax.jit(lambda key, i, moe: layer_weights(d, key, i, moe),
                      static_argnums=2)
    glob_w = jax.jit(lambda key, n: global_weights(d, key, n),
                     static_argnums=1)
    layer = jax.jit(functools.partial(_layer_f32, d, quant),
                    static_argnums=0)
    embed = jax.jit(lambda e, toks: e[toks].astype(jnp.float32))

    def _head(norm, un, h, idx):
        return _mm(_rms(h[idx], norm.astype(jnp.float32), d.eps),
                   un.astype(jnp.float32), quant)
    return layer_w, glob_w, layer, embed, jax.jit(_head)


def pack(lengths: Sequence[int], row_len: int):
    """First-fit-decreasing packing of sequences into rows of
    ``row_len``: [(row, offset)] per sequence, and the number of rows."""
    free: List[int] = []
    where = [None] * len(lengths)
    for i in sorted(range(len(lengths)), key=lambda i: -lengths[i]):
        if lengths[i] > row_len:
            raise ValueError(f"sequence of {lengths[i]} > row {row_len}")
        r = next((r for r, f in enumerate(free) if f >= lengths[i]), None)
        if r is None:
            free.append(row_len)
            r = len(free) - 1
        where[i] = (r, row_len - free[r])
        free[r] -= lengths[i]
    return where, len(free)


def _forward(d: Dims, key, seqs, row_len: int, quant):
    """The packed rows' hidden states after the last layer, where each
    sequence sits, and the assignments to held experts of every valid
    position over the layers."""
    layer_w, glob_w, layer, embed, _ = _programs(d, quant)
    where, n_rows = pack([len(s) for s in seqs], row_len)
    toks = np.zeros((n_rows, row_len), np.int32)
    seg = np.full((n_rows, row_len), -1, np.int32)
    pos = np.zeros((n_rows, row_len), np.int32)
    for i, (s, (r, o)) in enumerate(zip(seqs, where)):
        toks[r, o:o + len(s)] = s
        seg[r, o:o + len(s)] = i
        pos[r, o:o + len(s)] = np.arange(len(s))
    routed = 0
    e = glob_w(key, "embed")
    hs = [embed(e, jnp.asarray(t)) for t in toks]
    del e
    for i in range(d.L):
        moe = i >= d.first_dense
        w = layer_w(key, i, moe)
        out = [layer(moe, w, h, jnp.asarray(sg), jnp.asarray(p))
               for h, sg, p in zip(hs, seg, pos)]
        hs = [h for h, _ in out]
        routed += sum(int(n) for _, n in out)
    return hs, where, routed


def reference_logits(d: Dims, key, seqs: Sequence[np.ndarray],
                     rows: Sequence[np.ndarray], *, row_len: int,
                     quant: Optional[str] = None) -> List[np.ndarray]:
    """Float32 logits of each sequence at its ``rows`` (positions whose
    next token is compared).  Weights come again from ``key``, one layer
    at a time.  Sequences are packed into rows of ``row_len`` tokens
    (one compiled shape whatever the sample), each attending only within
    itself."""
    _, glob_w, _, _, head = _programs(d, quant)
    with jax.default_matmul_precision("highest"):
        hs, where, _ = _forward(d, key, seqs, row_len, quant)
        norm, un = glob_w(key, "final_norm"), glob_w(key, "unembed")
        out = []
        for (r, o), rw in zip(where, rows):
            # gather a fixed bucket of rows so the head compiles once
            idx = np.zeros(-(-len(rw) // QBLOCK) * QBLOCK, np.int32)
            idx[:len(rw)] = o + np.asarray(rw)
            out.append(np.asarray(head(norm, un, hs[r], idx))[:len(rw)])
        return out


def held_routed_rows(d: Dims, key, seqs: Sequence[np.ndarray], *,
                     row_len: int) -> int:
    """Assignments to the held experts the reference makes over every
    position of ``seqs`` and every expert layer: what a program that runs
    those positions through its expert layers computes."""
    with jax.default_matmul_precision("highest"):
        return _forward(d, key, seqs, row_len, None)[2]


# ---------------------------------------------------------------------------
# work counted from shapes
# ---------------------------------------------------------------------------

def attn_params(d: Dims) -> int:
    """Matmul weights of one layer's attention (norms left out)."""
    return (d.D * d.H * d.hd + d.D * d.row + d.R * d.H * (d.nope + d.vd)
            + d.H * d.vd * d.D)


def ffn_params(d: Dims, moe: bool) -> int:
    """Matmul weights of one layer's FFN this chip holds: the dense SwiGLU,
    or the router, the held experts and the shared experts."""
    if not moe:
        return 3 * d.D * d.F
    return d.D * d.E + 3 * d.D * (d.held * d.Fe + d.Fs)


def n_params(d: Dims) -> int:
    """Every parameter the chip holds: layers, norms, embedding, head."""
    norms = 2 * d.D + d.R
    return (d.L * (attn_params(d) + norms)
            + d.first_dense * ffn_params(d, False)
            + d.moe_layers * ffn_params(d, True) + 2 * d.V * d.D + d.D)


def active_params(d: Dims) -> float:
    """Matmul weights one token runs through on this chip, its routed
    experts counted at their expected share here (topk x held / E of an
    expert layer's routed work)."""
    routed = d.topk * d.held / d.E * 3 * d.D * d.Fe
    moe = d.D * d.E + 3 * d.D * d.Fs + routed
    return (d.L * attn_params(d) + d.first_dense * 3 * d.D * d.F
            + d.moe_layers * moe)


def token_flops(d: Dims, ctx, head: bool):
    """Model FLOPs of one token that attends ``ctx`` positions (itself
    included): 2 per weight it runs through (``active_params``), and, in
    every layer, attention in its up-projected form at that context
    (QK^T over the qk head width, PV over the v head width, each head),
    and the head where logits are produced.  Works on numbers and numpy
    arrays."""
    per = 2 * active_params(d) + 2 * d.L * d.H * (d.hd + d.vd) * ctx
    return per + (2 * d.D * d.V if head else 0)


def chunk_flops(d: Dims, start, n):
    """A prefill chunk of ``n`` tokens at positions start..start+n-1,
    logits for its last row only."""
    n = np.asarray(n, np.int64)
    ctx_sum = n * np.asarray(start, np.int64) + n * (n + 1) // 2
    return (2 * active_params(d) * n
            + 2 * d.L * d.H * (d.hd + d.vd) * ctx_sum + 2 * d.D * d.V)


def kv_bytes_per_token(d: Dims) -> int:
    """Bytes of latent rows one token keeps over every layer (bf16)."""
    return d.L * d.row * 2


def decode_attention_work(d: Dims, kv_len):
    """(flops, bytes) of ONE ``paged_decode_attention`` call (one layer,
    latent mode) over slots with ``kv_len`` cached rows each: every query
    head scores each latent row over its whole width and takes its first
    R columns as the value, 2 H kv (row + R) FLOPs; each slot's rows are
    read once, plus q (H x row) and the output (H x R), all bf16."""
    kv = np.asarray(kv_len, np.int64)
    flops = int(np.sum(2 * d.H * kv * (d.row + d.R)))
    rows = int(np.sum(kv * d.row)) * 2
    qo = kv.size * d.H * (d.row + d.R) * 2
    return flops, rows + qo


def moe_gmm_work(d: Dims, routed_rows):
    """(flops, bytes) of one call's ``moe_gmm`` kernels (gate, up and down
    of every expert layer) given the assignments its held experts took,
    summed over the layers: 6 D Fe FLOPs per routed row; the held
    experts' three matrices read once a layer, and each routed row's
    inputs and outputs (D in, Fe out twice; Fe in, D out), bf16.  Rows
    the buffers pad with are not work."""
    rows = np.asarray(routed_rows, np.int64)
    flops = rows * 6 * d.D * d.Fe
    weights = d.moe_layers * 3 * d.held * d.D * d.Fe * 2
    return flops, weights + rows * 3 * (d.D + d.Fe) * 2
