"""Chunked SSD (Mamba2) Pallas kernel.

Grid (B, nh, S/chunk) with the chunk dimension sequential; the inter-chunk
SSM state (hd, ds) lives in f32 VMEM scratch across chunk steps (reset at
chunk 0).  All intra-chunk work is expressed as (Q x Q) / (Q x hd) / (Q x ds)
matmuls — MXU-shaped, which is precisely the "state-space duality" insight:
the quadratic-attention form of the SSM inside a chunk, the linear
recurrence across chunks.  Cumulative sums are masked (Q x Q) reductions
rather than a serial scan.

Operands are passed head-major ((B, nh, S, hd), dt as a (B, nh, S, 1)
column), so every block's last two dims are a (chunk, width) slab the
TPU's (8, 128) tiling accepts; the per-head decay ``A`` sits whole in
SMEM.  B/C group tensors are indexed per-head via the BlockSpec index map
(h -> h // heads_per_group), so grouped B/C are never materialised per head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compat
from repro.kernels.plan import SUBLANE, validate_tiling

__all__ = ["mamba2_ssd"]


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, hout_ref,
                state_ref, *, n_chunks: int, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    A = a_ref[pl.program_id(1)]                         # scalar f32 (SMEM)
    x = x_ref[...].astype(jnp.float32)                  # (Q, hd)
    dt = dt_ref[...].astype(jnp.float32)                # (Q, 1) column
    Bm = b_ref[...].astype(jnp.float32)                 # (Q, ds)
    Cm = c_ref[...].astype(jnp.float32)                 # (Q, ds)

    # per-step vectors stay 2-D ((Q, 1) columns, (1, Q) rows), the
    # shapes Mosaic lays out on vregs; the cumsums are masked reductions
    dA = dt * A                                         # (Q, 1) log-decay <= 0
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dA_row = jnp.sum(jnp.where(row == col, dA, 0.0), axis=0,
                     keepdims=True)                     # (1, Q)
    causal = col <= row                                 # inclusive lower-tri
    cum = jnp.sum(jnp.where(causal, dA_row, 0.0), axis=1,
                  keepdims=True)                        # (Q, 1) cumsum
    cum_row = jnp.sum(jnp.where(row <= col, dA, 0.0), axis=0,
                      keepdims=True)                    # (1, Q) cumsum
    total = jnp.sum(dA, axis=0, keepdims=True)          # (1, 1)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q, Q)
    # mask inside the exp: anti-causal entries are positive log-decays
    # whose exp overflows (inf * 0 = NaN)
    L = jnp.exp(jnp.where(causal, cum - cum_row, -1e30))
    y_intra = jax.lax.dot_general(scores * L, x * dt,
                                  (((1,), (0,)), ((), ())))         # (Q, hd)

    h_prev = state_ref[...]                              # (hd, ds)
    y_inter = jax.lax.dot_general(Cm, h_prev,
                                  (((1,), (1,)), ((), ())))         # (Q, hd)
    y_inter = y_inter * jnp.exp(cum)

    decay = jnp.exp(total - cum) * dt                    # (Q, 1)
    state_ref[...] = jnp.exp(total) * h_prev + jax.lax.dot_general(
        x * decay, Bm, (((0,), (0,)), ((), ())))                    # (hd, ds)

    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hout_ref[...] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_ssd(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
               Cm: jax.Array, *, chunk: int,
               interpret: bool = False):
    """x (B,S,nh,hd); dt (B,S,nh) f32 post-softplus; A (nh,) f32 negative;
    Bm/Cm (B,S,G,ds).  Returns (y (B,S,nh,hd), state (B,nh,hd,ds) f32).

    ``chunk`` must be a sublane-aligned divisor of S (the chunked SSD
    algebra is exact at any chunk; derive one with
    ``repro.kernels.plan.plan_for``)."""
    B, S, nh, hd = x.shape
    G, ds = Bm.shape[2], Bm.shape[3]
    hpg = nh // G
    validate_tiling("mamba2_ssd", {"S": (S, chunk)}, depth_dims=(),
                    block_names={"S": "chunk"}, quantum=SUBLANE)
    n_chunks = S // chunk
    grid = (B, nh, n_chunks)

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=n_chunks, chunk=chunk),
        grid=grid,
        in_specs=[
            compat.smem_block_spec(),
            pl.BlockSpec((None, None, chunk, hd),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, chunk, 1),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, chunk, ds),
                         lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((None, None, chunk, ds),
                         lambda b, h, c: (b, h // hpg, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, hd),
                         lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, hd, ds), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, S, hd), x.dtype),
            jax.ShapeDtypeStruct((B, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[compat.vmem((hd, ds), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), x.transpose(0, 2, 1, 3),
      dt.transpose(0, 2, 1)[..., None], Bm.transpose(0, 2, 1, 3),
      Cm.transpose(0, 2, 1, 3))
    return y.transpose(0, 2, 1, 3), state
