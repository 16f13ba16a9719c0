"""Dense GQA decoder family: Qwen2, Mistral-Nemo and their kin.

Everything the benchmark needs to know about the family, written from
the published description and importing nothing of the program:

* ``dims`` reads a configuration file (Hugging Face ``config.json`` keys);
* ``program_config`` maps it onto the program's ``ModelConfig`` fields;
* ``program_params`` / ``layer_weights`` make seeded random weights in
  the layout the program reads, on the device, in one jitted call; the
  reference makes the same values again, layer by layer;
* ``reference_logits`` is the plain float32 forward (RMSNorm, RoPE with
  rotate-half, optional QKV bias, GQA causal attention, SwiGLU, untied
  head) computed one layer at a time at ``highest`` matmul precision;
  ``quant="fp8"`` computes every matmul from float8_e4m3 operands, the
  control that has to fail the comparison;
* ``token_flops`` / ``decode_attention_work`` count the model's work
  from its shapes.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Leaf order of one layer's weights; a leaf's key is fold_in(layer key,
# its index here), so adding a leaf never changes the others' values.
_LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
                 "wg", "wi", "wd")
_GLOBAL_LEAVES = ("embed", "unembed", "final_norm")
NORM_JITTER = 0.1     # norm weights ~ 1 + N(0, 0.1): trained norms are not 1
BIAS_STD = 0.5        # q/k/v biases ~ N(0, 0.5): trained Qwen2 biases are not 0
QBLOCK = 512          # reference attention: query rows per block
FP8_MAX = 448.0       # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Dims:
    L: int
    D: int
    H: int
    KV: int
    hd: int
    F: int
    V: int
    qkv_bias: bool
    theta: float
    eps: float
    init: float
    dtype: str


def dims(conf: Dict) -> Dims:
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    if conf.get("tie_word_embeddings"):
        raise ValueError("dense_gqa: tied embeddings are not modelled")
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"dense_gqa: hidden_act {conf['hidden_act']!r}")
    return Dims(L=conf["num_hidden_layers"], D=D, H=H,
                KV=conf["num_key_value_heads"],
                hd=conf.get("head_dim") or D // H,
                F=conf["intermediate_size"], V=conf["vocab_size"],
                qkv_bias=bool(conf["qkv_bias"]),
                theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]),
                init=float(conf["initializer_range"]),
                dtype=conf["torch_dtype"])


def program_config(conf: Dict) -> Dict:
    """Keyword arguments of the program's ``ModelConfig``."""
    d = dims(conf)
    return dict(family="dense", n_layers=d.L, d_model=d.D, n_heads=d.H,
                n_kv_heads=d.KV, d_ff=d.F, vocab_size=d.V, head_dim=d.hd,
                qkv_bias=d.qkv_bias, rope_theta=d.theta, norm_eps=d.eps,
                norm_type="rms", mlp_type="swiglu", pos_embed="rope",
                tie_embeddings=False, dtype=d.dtype)


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


def layer_weights(d: Dims, key, i, dt=jnp.bfloat16) -> Dict:
    """Layer ``i``'s weights in the program's layout (``i`` may be traced)."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    ks = {n: jax.random.fold_in(k, j) for j, n in enumerate(_LAYER_LEAVES)}
    D, H, KV, hd, F, s = d.D, d.H, d.KV, d.hd, d.F, d.init
    mixer = {"wq": _normal(ks["wq"], (D, H * hd), s, dt),
             "wk": _normal(ks["wk"], (D, KV * hd), s, dt),
             "wv": _normal(ks["wv"], (D, KV * hd), s, dt),
             "wo": _normal(ks["wo"], (H * hd, D), s, dt)}
    if d.qkv_bias:
        mixer.update(bq=_normal(ks["bq"], (H * hd,), BIAS_STD, dt),
                     bk=_normal(ks["bk"], (KV * hd,), BIAS_STD, dt),
                     bv=_normal(ks["bv"], (KV * hd,), BIAS_STD, dt))
    return {"ln1": _norm_w(ks["ln1"], D, dt), "ln2": _norm_w(ks["ln2"], D, dt),
            "mixer": mixer,
            "ffn": {"wg": _normal(ks["wg"], (D, F), s, dt),
                    "wi": _normal(ks["wi"], (D, F), s, dt),
                    "wo": _normal(ks["wd"], (F, D), s, dt)}}


def global_weights(d: Dims, key, name: str, dt=jnp.bfloat16):
    k = jax.random.fold_in(jax.random.fold_in(key, 0),
                           _GLOBAL_LEAVES.index(name))
    if name == "embed":
        return _normal(k, (d.V, d.D), d.init, dt)
    if name == "unembed":
        return _normal(k, (d.D, d.V), d.init, dt)
    return _norm_w(k, d.D, dt)


def program_params(d: Dims, key) -> Dict:
    """The program's parameter pytree (layers stacked on a leading axis,
    one scan period), in the served bf16.  Jit this: one device call."""
    return {"embed": global_weights(d, key, "embed"),
            "unembed": global_weights(d, key, "unembed"),
            "final_norm": global_weights(d, key, "final_norm"),
            "layers": (jax.vmap(lambda i: layer_weights(d, key, i))(
                jnp.arange(d.L)),)}


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


def _rope(x, pos, theta):
    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv                # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]     # (S, 1, half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, seg, quant):
    """Causal GQA attention over one packed row: q (S, H, hd); k, v
    (S, KV, hd); a token sees the earlier tokens of its own segment."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    if quant == "fp8":
        k, v = _q8(k, -1), _q8(v, -1)
    cols = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, 0)
        sq = jax.lax.dynamic_slice_in_dim(seg, i * QBLOCK, QBLOCK, 0)
        if quant == "fp8":
            qb = _q8(qb, -1)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(hd)
        rows = i * QBLOCK + jnp.arange(QBLOCK)
        see = (cols[None, :] <= rows[:, None]) & (seg[None, :] == sq[:, None])
        s = jnp.where(see[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(S // QBLOCK))
    return out.reshape(S, H, hd)


def _layer(d: Dims, quant, w, h, seg, pos):
    """One pre-norm block over one packed row h (S, D), float32."""
    S = h.shape[0]
    m = w["mixer"]
    x = _rms(h, w["ln1"], d.eps)
    q, k, v = (_mm(x, m[n], quant) for n in ("wq", "wk", "wv"))
    if d.qkv_bias:
        q, k, v = q + m["bq"], k + m["bk"], v + m["bv"]
    q = _rope(q.reshape(S, d.H, d.hd), pos, d.theta)
    k = _rope(k.reshape(S, d.KV, d.hd), pos, d.theta)
    o = _attend(q, k, v.reshape(S, d.KV, d.hd), seg, quant)
    h = h + _mm(o.reshape(S, d.H * d.hd), m["wo"], quant)
    x = _rms(h, w["ln2"], d.eps)
    f = w["ffn"]
    g = jax.nn.silu(_mm(x, f["wg"], quant)) * _mm(x, f["wi"], quant)
    return h + _mm(g, f["wo"], quant)


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _layer_f32(d: Dims, quant, w, h, seg, pos):
    return _layer(d, quant, _f32(w), h, seg, pos)


@functools.lru_cache(maxsize=None)
def _programs(d: Dims, quant: Optional[str]):
    # weights leave their own program in the served bf16 and are widened
    # inside the next one, so they hold exactly the values served
    layer_w = jax.jit(lambda key, i: layer_weights(d, key, i))
    glob_w = jax.jit(lambda key, n: global_weights(d, key, n),
                     static_argnums=1)
    layer = jax.jit(functools.partial(_layer_f32, d, quant))
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


def reference_logits(d: Dims, key, seqs: Sequence[np.ndarray],
                     rows: Sequence[np.ndarray], *, row_len: int,
                     quant: Optional[str] = None) -> List[np.ndarray]:
    """Float32 logits of each sequence at its ``rows`` (positions whose
    next token is compared).  Weights come again from ``key``, one layer
    at a time.  Sequences are packed into rows of ``row_len`` tokens
    (one compiled shape whatever the sample), each attending only within
    itself."""
    layer_w, glob_w, layer, embed, head = _programs(d, quant)
    where, n_rows = pack([len(s) for s in seqs], row_len)
    toks = np.zeros((n_rows, row_len), np.int32)
    seg = np.full((n_rows, row_len), -1, np.int32)
    pos = np.zeros((n_rows, row_len), np.int32)
    for i, (s, (r, o)) in enumerate(zip(seqs, where)):
        toks[r, o:o + len(s)] = s
        seg[r, o:o + len(s)] = i
        pos[r, o:o + len(s)] = np.arange(len(s))
    with jax.default_matmul_precision("highest"):
        e = glob_w(key, "embed")
        hs = [embed(e, jnp.asarray(t)) for t in toks]
        del e
        for i in range(d.L):
            w = layer_w(key, i)
            hs = [layer(w, h, jnp.asarray(sg), jnp.asarray(p))
                  for h, sg, p in zip(hs, seg, pos)]
        del w
        norm, un = glob_w(key, "final_norm"), glob_w(key, "unembed")
        out = []
        for (r, o), rw in zip(where, rows):
            # gather a fixed bucket of rows so the head compiles once
            idx = np.zeros(-(-len(rw) // QBLOCK) * QBLOCK, np.int32)
            idx[:len(rw)] = o + np.asarray(rw)
            out.append(np.asarray(head(norm, un, hs[r], idx))[:len(rw)])
        return out


# ---------------------------------------------------------------------------
# work counted from shapes
# ---------------------------------------------------------------------------

def layer_params(d: Dims) -> int:
    """Matmul weights of one layer (biases and norms left out)."""
    return (d.D * (d.H + 2 * d.KV) * d.hd + d.H * d.hd * d.D
            + 3 * d.D * d.F)


def n_params(d: Dims) -> int:
    """Every parameter the chip holds: layers, norms, embedding, head."""
    per_layer = layer_params(d) + 2 * d.D + (
        (d.H + 2 * d.KV) * d.hd if d.qkv_bias else 0)
    return d.L * per_layer + 2 * d.V * d.D + d.D


def token_flops(d: Dims, ctx, head: bool):
    """Model FLOPs of one token that attends ``ctx`` positions (itself
    included): 2 per weight of every layer's matmuls, QK^T and PV at its
    context, and the head where logits are produced.  Works on numbers
    and on numpy arrays."""
    per = 2 * d.L * layer_params(d) + 4 * d.L * d.H * d.hd * ctx
    return per + (2 * d.D * d.V if head else 0)


def chunk_flops(d: Dims, start, n):
    """A prefill chunk of ``n`` tokens at positions start..start+n-1,
    logits for its last row only."""
    n = np.asarray(n, np.int64)
    ctx_sum = n * np.asarray(start, np.int64) + n * (n + 1) // 2
    return (2 * d.L * layer_params(d) * n + 4 * d.L * d.H * d.hd * ctx_sum
            + 2 * d.D * d.V)


def kv_bytes_per_token(d: Dims) -> int:
    """Bytes of K and V one token keeps in every layer's cache (bf16)."""
    return 2 * d.L * d.KV * d.hd * 2


def decode_attention_work(d: Dims, kv_len):
    """(flops, bytes) of ONE ``paged_decode_attention`` call (one layer)
    over slots with ``kv_len`` cached rows each: QK^T and PV for every
    query head, and the K/V rows of each slot's context read once, plus
    q and the output, all bf16."""
    kv = np.asarray(kv_len, np.int64)
    flops = int(np.sum(4 * d.H * d.hd * kv))
    rows = int(np.sum(2 * d.KV * d.hd * kv)) * 2
    qo = 2 * kv.size * d.H * d.hd * 2
    return flops, rows + qo
