"""The ONE place that touches ``jax.experimental.pallas.tpu``.

Every kernel in ``repro.kernels`` builds its TPU-specific pieces through
this module, spelled for the one supported JAX (``requirements.txt``):

* :func:`tpu_compiler_params` — dimension-semantics compiler params;
* :func:`vmem` / :func:`smem_block_spec` — VMEM scratch shapes and
  SMEM-resident block specs;
* :func:`prefetch_grid_spec` — the scalar-prefetch grid the paged
  decode kernel gathers through;
* :func:`default_interpret` / :func:`resolve_interpret` — interpret mode
  wherever the backend is not a TPU (CPU tests); on a TPU the same call
  sites compile to Mosaic.

Nothing outside this file may import ``jax.experimental.pallas.tpu``.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "tpu_compiler_params",
    "vmem",
    "smem_block_spec",
    "prefetch_grid_spec",
    "default_interpret",
    "resolve_interpret",
]


def tpu_compiler_params(*, dimension_semantics: Sequence[str]):
    """Compiler params carrying ``dimension_semantics`` for a grid.

    Each entry is ``"parallel"`` (grid dimension may be executed in any
    order / in parallel) or ``"arbitrary"`` (sequential — carries VMEM
    scratch state across steps, e.g. a K loop's accumulator).
    """
    return pltpu.CompilerParams(dimension_semantics=tuple(dimension_semantics))


def vmem(shape: Tuple[int, ...], dtype):
    """A VMEM scratch buffer spec (``scratch_shapes=`` entry)."""
    return pltpu.VMEM(shape, dtype)


def smem_block_spec() -> pl.BlockSpec:
    """A BlockSpec placing the whole operand in SMEM (scalars / tiny
    tables the kernel indexes by grid position)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def prefetch_grid_spec(*, num_scalar_prefetch: int, grid, in_specs,
                       out_specs, scratch_shapes=()):
    """A grid spec whose first ``num_scalar_prefetch`` operands are SMEM
    scalars available *before* the kernel body runs — index maps receive
    them as trailing refs, so block indices can be data-dependent (the
    paged-attention block-table gather)."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=tuple(grid),
        in_specs=list(in_specs), out_specs=out_specs,
        scratch_shapes=list(scratch_shapes))


def default_interpret() -> bool:
    """True when there is no TPU backend: run kernels in interpret mode
    (the kernel body executes in Python per grid step — correctness-exact,
    not performance-shaped)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> auto-detect; an explicit bool wins."""
    if interpret is None:
        return default_interpret()
    return interpret
