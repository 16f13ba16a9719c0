"""Scan-based pipeline parallelism (GPipe schedule) over a mesh axis.

Each rank of the ``pp`` axis owns one contiguous stage of layers
(``stage_params`` stacked on a leading n_stages dim, sharded over the
axis).  The schedule runs ``n_micro + n_stages - 1`` ticks; at each tick
every rank applies its stage and the activation ring advances one hop via
``collective_permute`` — compute and communication overlap across ranks,
bubble fraction = (n_stages - 1) / (n_micro + n_stages - 1).

This is the opt-in alternative to pure FSDP for the multi-pod mesh: map
``pp`` onto the "pod" axis so only stage-boundary activations cross the
slow DCN link (vs. per-layer weight gathers under cross-pod ZeRO-3).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def gpipe_apply(stage_fn: Callable, stage_params, x: jax.Array, *,
                mesh: Mesh, axis: str = "pod") -> jax.Array:
    """Run ``x`` through the pipeline.

    stage_fn(params_slice, h) -> h          (one stage, shapes preserved)
    stage_params: pytree, leaves (n_stages, ...) — sharded over ``axis``
    x: (n_micro, mb, ...) microbatched input (replicated)
    Returns (n_micro, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    n_ticks = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def ranked(params_loc, x_all):
        params_loc = jax.tree.map(lambda a: a[0], params_loc)  # (1,...) -> (...)
        rank = jax.lax.axis_index(axis)
        mb_shape = x_all.shape[1:]

        def tick(carry, t):
            buf = carry
            # rank 0 ingests microbatch t (zeros once the stream dries up)
            x_t = jax.lax.dynamic_index_in_dim(
                x_all, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            buf = jnp.where(rank == 0,
                            jnp.where(t < n_micro, x_t, jnp.zeros(mb_shape,
                                                                  x_all.dtype)),
                            buf)
            y = stage_fn(params_loc, buf)
            # the last rank emits microbatch t - (n_stages - 1)
            emit = y * (rank == n_stages - 1).astype(y.dtype)
            # advance the ring
            buf_next = jax.lax.ppermute(y, axis, perm)
            return buf_next, emit

        buf0 = jnp.zeros(mb_shape, x_all.dtype)
        _, emits = jax.lax.scan(tick, buf0, jnp.arange(n_ticks))
        # emits[t] is valid for microbatch t-(n_stages-1); all-reduce picks
        # the last rank's values (all other ranks contributed zeros)
        out = jax.lax.psum(emits[n_stages - 1:], axis)
        return out

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    return jax.shard_map(ranked, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)(stage_params, x)
