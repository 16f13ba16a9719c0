"""Spec-driven tile planning: ``TilePlan`` / ``plan_for`` / the catalog.

Every Pallas kernel in this package tiles its operands into fast-memory
blocks.  Those block sizes used to be five sets of hard-coded defaults
(``block_m=256`` here, ``block_kv=512`` there) with a silent
``min(block, dim)`` clamp that happily produced non-MXU-aligned tiles for
small dims.  This module replaces all of that with one planner that
derives tiles from the :class:`repro.arch.DeviceSpec` the same way the
cost engines derive their peaks:

* the **alignment quantum** comes from the compute topology —
  ``mxu_dim`` (the 128x128 systolic array) on TPUs; on MFMA cycle-table
  GPUs the same 128 width, which an MCE assembles as an 8x8 grid of
  16x16 micro-tiles, so one plan serves both device families;
* the **working-set budget** is ``DeviceSpec.vmem_bytes`` (VMEM per TPU
  core, an L2 staging slice on GPUs), with half reserved for the
  double-buffered prefetch pipeline;
* tiles are chosen as the largest aligned divisors of the problem dims
  under per-kernel caps, then shrunk greedily until the working set fits.

:func:`plan_for` is the entry point; :class:`KernelEntry` catalog rows
make kernels enumerable by name (op + oracle + planner), which the parity
test suite and the perf pipeline both iterate.  :func:`validate_tiling`
is the shared alignment contract the kernels themselves enforce — a
sub-128 or non-dividing block now raises ``ValueError`` naming the
offending dim instead of silently clamping.

This module is deliberately JAX-free: the perf engines call it for
representative tiles without touching the compute stack.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from typing import (Callable, Dict, Mapping, Optional, Sequence, Tuple,
                    Union)

from repro.arch.registry import get_device
from repro.arch.spec import DeviceSpec

__all__ = [
    "TilePlan",
    "KernelEntry",
    "UnknownKernelError",
    "UnknownDtypeError",
    "plan_for",
    "register_kernel",
    "get_kernel",
    "list_kernels",
    "tile_align",
    "vmem_budget",
    "validate_tiling",
    "DEFAULT_PLAN_DEVICE",
    "SUBLANE",
]

#: Planning device when the caller names none (CPU containers have no
#: backend to introspect; the base TPU is the canonical Pallas target).
DEFAULT_PLAN_DEVICE = "tpu_v5e"

#: Fallback working-set budget for specs predating ``vmem_bytes``.
_DEFAULT_VMEM_BYTES = 16 << 20

#: Quantum for sequence-chunked (non-GEMM-tiled) dims: the VPU's 8-row
#: sublane granularity, not the MXU width.
SUBLANE = 8

#: numpy/JAX-style spellings -> the canonical HLO names of
#: ``repro.perf.hlo_ir.BYTES_PER_ELEM`` (the ONE byte table).
_DTYPE_ALIASES = {
    "float64": "f64", "fp64": "f64",
    "float32": "f32", "fp32": "f32",
    "float16": "f16", "fp16": "f16",
    "bfloat16": "bf16",
    "int64": "s64", "uint64": "u64",
    "int32": "s32", "i32": "s32", "uint32": "u32",
    "int16": "s16", "uint16": "u16",
    "int8": "s8", "i8": "s8", "uint8": "u8",
    "int4": "s4", "uint4": "u4",
    "float8_e4m3fn": "f8e4m3fn", "fp8": "f8e4m3fn",
    "float8_e5m2": "f8e5m2",
    "bool": "pred",
}


def _itemsize(dtype) -> int:
    """Bytes per element for a numpy/jax dtype object or an HLO name."""
    # lazy: hlo_ir is stdlib-only, but importing it at module scope would
    # pull the whole perf package under this deliberately light module
    from repro.perf.hlo_ir import BYTES_PER_ELEM
    name = str(dtype).lower()
    size = BYTES_PER_ELEM.get(_DTYPE_ALIASES.get(name, name))
    if size is not None:
        return size
    itemsize = getattr(dtype, "itemsize", None) or getattr(
        getattr(dtype, "dtype", None), "itemsize", None)
    if itemsize:
        return int(itemsize)
    raise UnknownDtypeError(
        f"unknown dtype {dtype!r}: cannot size tiles "
        f"(known: {sorted(BYTES_PER_ELEM)} and aliases)")


class UnknownKernelError(KeyError):
    """Raised for a kernel name not in the catalog."""


class UnknownDtypeError(ValueError):
    """Raised when a dtype cannot be sized for tile planning.

    Distinct from the plain ``ValueError`` contract violations
    (misalignment, budget overflow) so callers with a fallback dtype —
    ``repro.perf.engines.plan_for_dot`` — can retry on exactly this
    failure without masking real planning errors."""


def tile_align(spec: DeviceSpec) -> int:
    """The matrix-unit alignment quantum for GEMM-tiled dims on ``spec``."""
    return spec.mxu_dim if spec.mxu_count else 128


def vmem_budget(spec: DeviceSpec) -> int:
    """Plannable working-set bytes: half the fast-memory budget (the
    other half is the double-buffered prefetch pipeline)."""
    return (spec.vmem_bytes or _DEFAULT_VMEM_BYTES) // 2


# ---------------------------------------------------------------------------
# The alignment contract (shared with the kernels themselves)
# ---------------------------------------------------------------------------

def validate_tiling(kernel: str,
                    dims: Mapping[str, Tuple[int, int]], *,
                    align: int = 128,
                    depth_dims: Sequence[str] = ("K",),
                    block_names: Optional[Mapping[str, str]] = None,
                    quantum: Optional[int] = None) -> None:
    """Enforce the matrix-unit tiling contract.

    ``dims`` maps dim name -> ``(dim, block)``; ``block_names`` maps dim
    name -> the kernel's keyword for it (default ``block_<dim>``), used
    in error messages.  Every block must divide its dim and be a multiple
    of ``align``; dims listed in ``depth_dims`` (the contraction) may
    alternatively use one full-depth step (``block == dim``), which
    streams the whole reduction in a single grid iteration and so has no
    unaligned tile boundary.  ``quantum`` overrides ``align`` for dims
    that are sublane- rather than MXU-quantised (the SSD chunk).

    Raises ``ValueError`` naming the offending dim — the silent
    ``min(block, dim)`` clamp this replaces let e.g. M=64 run with a
    64-wide, non-MXU tile.
    """
    q = quantum or align
    names = block_names or {}
    for dim_name, (dim, block) in dims.items():
        block_name = names.get(dim_name, f"block_{dim_name.lower()}")
        if block < 1:
            raise ValueError(f"{kernel}: {block_name}={block} must be >= 1")
        if dim % block:
            raise ValueError(
                f"{kernel}: {dim_name}={dim} is not divisible by "
                f"{block_name}={block}; pad {dim_name} or pick a divisor "
                "(the XLA reference path handles ragged shapes)")
        if block % q and not (dim_name in depth_dims and block == dim):
            depth_hint = (" (a single full-depth step block == "
                          f"{dim_name} is also legal)"
                          if dim_name in depth_dims else "")
            raise ValueError(
                f"{kernel}: {block_name}={block} on {dim_name}={dim} is "
                f"not a multiple of the {q}-wide matrix-unit "
                f"tile{depth_hint}; pad {dim_name} to a multiple of {q} "
                "or use the XLA reference path for small shapes")


# ---------------------------------------------------------------------------
# TilePlan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TilePlan:
    """One kernel's chosen tiling on one device.

    ``blocks`` holds exactly the keyword arguments the ops-layer wrapper
    forwards to the kernel (``block_m``/``block_n``/``block_k``,
    ``block_q``/``block_kv``, ``chunk``, ``kv_step``); the perf engines
    record the same mapping in ``Report.plan`` so predicted and executed
    tiles can be cross-checked.
    """

    kernel: str
    device: str
    dtype: str
    blocks: Mapping[str, int]
    grid: Tuple[int, ...]
    vmem_bytes: int              # estimated per-core working set
    vmem_budget: int             # the budget it was sized against
    align: int
    padded: bool = False         # dims were rounded up (pad=True planning)
    #: dim name -> the (possibly padded) size the blocks tile.  With
    #: ``pad=True`` these are the quantum-rounded sizes the ops-layer
    #: wrappers pad inputs to (and slice outputs back from); with
    #: ``pad=False`` they equal the problem dims.
    dims: Mapping[str, int] = dataclasses.field(default_factory=dict)

    def kwargs(self) -> Dict[str, int]:
        """The block keyword arguments for the ops-layer call."""
        return dict(self.blocks)

    def as_dict(self) -> Dict[str, object]:
        d: Dict[str, object] = {"kernel": self.kernel, "device": self.device,
                                "dtype": self.dtype, "align": self.align,
                                "vmem_bytes": self.vmem_bytes}
        d.update(self.blocks)
        return d

    def describe(self) -> str:
        blk = " ".join(f"{k.replace('block_', 'b')}={v}"
                       for k, v in self.blocks.items())
        return (f"{self.kernel}@{self.device} {blk} "
                f"(vmem {self.vmem_bytes / 2**20:.2f}/"
                f"{self.vmem_budget / 2**20:.0f} MiB)")


# ---------------------------------------------------------------------------
# Planner internals
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Dim:
    """One plannable dim: kernel keyword, dim name, size, quantum class."""

    block_name: str
    dim_name: str
    size: int
    sublane: bool = False        # sublane- instead of MXU-quantised
    depth: bool = False          # contraction dim (full-depth step legal)
    heads: bool = False          # a head count: any divisor is a block


def _pad_to(dim: int, quantum: int) -> int:
    return quantum * math.ceil(dim / quantum)


def _candidates(kernel: str, d: _Dim, *, align: int,
                pad: bool) -> Tuple[int, Sequence[int]]:
    """(possibly padded size, descending quantum-aligned divisor blocks)."""
    q = 1 if d.heads else SUBLANE if d.sublane else align
    size = _pad_to(d.size, q) if pad else d.size
    if size % q:
        if d.depth:
            # a single full-depth step streams the whole reduction in one
            # grid iteration: no unaligned tile boundary to misalign
            return size, [size]
        raise ValueError(
            f"{kernel}: {d.dim_name}={size} is not a multiple of the "
            f"{q}-wide tile quantum; pad {d.dim_name} (plan with pad=True "
            "to model padded execution) or use the XLA reference path")
    units = size // q
    return size, [u * q for u in range(units, 0, -1) if units % u == 0]


def _plan(kernel: str, spec: DeviceSpec, dtype, *,
          dims: Sequence[_Dim],
          caps: Mapping[str, int],
          footprint: Callable[[Mapping[str, int], int], int],
          grid: Callable[[Mapping[str, int], Mapping[str, int]], Tuple[int, ...]],
          overrides: Mapping[str, Optional[int]],
          pad: bool) -> TilePlan:
    """Shared planner body: choose quantum-aligned divisor blocks under
    the caps, shrink to the VMEM budget, validate, emit the plan."""
    align = tile_align(spec)
    budget = vmem_budget(spec)
    dsz = _itemsize(dtype)

    sizes: Dict[str, int] = {}           # dim name -> (padded) size
    cands: Dict[str, Sequence[int]] = {} # block name -> descending choices
    chosen: Dict[str, int] = {}
    for d in dims:
        size, c = _candidates(kernel, d, align=align, pad=pad)
        sizes[d.dim_name] = size
        cands[d.block_name] = c
        ov = overrides.get(d.block_name)
        if ov is not None:
            chosen[d.block_name] = ov
        else:
            cap = caps[d.block_name]
            chosen[d.block_name] = next((x for x in c if x <= cap), c[-1])

    # shrink the largest free block until the working set fits
    while footprint(chosen, dsz) > budget:
        shrinkable = [(v, k) for k, v in chosen.items()
                      if overrides.get(k) is None
                      and any(x < v for x in cands[k])]
        if not shrinkable:
            if any(v is not None for v in overrides.values()):
                break                      # caller pinned blocks: honour them
            raise ValueError(
                f"{kernel}: no tiling fits the {budget}-byte working-set "
                f"budget on {spec.name} (minimum aligned tiles need "
                f"{footprint(chosen, dsz)} bytes); raise the device's "
                "vmem_bytes or shrink the problem")
        _, k = max(shrinkable)
        chosen[k] = next(x for x in cands[k] if x < chosen[k])

    validate_tiling(
        kernel,
        {d.dim_name: (sizes[d.dim_name], chosen[d.block_name])
         for d in dims if not (d.sublane or d.heads)},
        align=align,
        depth_dims=tuple(d.dim_name for d in dims if d.depth),
        block_names={d.dim_name: d.block_name for d in dims})
    for quantum, pick in ((SUBLANE, "sublane"), (1, "heads")):
        validate_tiling(
            kernel,
            {d.dim_name: (sizes[d.dim_name], chosen[d.block_name])
             for d in dims if getattr(d, pick)},
            align=align, depth_dims=(), quantum=quantum,
            block_names={d.dim_name: d.block_name for d in dims})

    return TilePlan(kernel=kernel, device=spec.name, dtype=str(dtype),
                    blocks=dict(chosen), grid=grid(sizes, chosen),
                    vmem_bytes=footprint(chosen, dsz), vmem_budget=budget,
                    align=align, padded=pad, dims=dict(sizes))


# ---------------------------------------------------------------------------
# Per-kernel planners
# ---------------------------------------------------------------------------

def _plan_mfma_gemm(shapes, dtype, spec, overrides, pad):
    M, N, K = shapes["M"], shapes["N"], shapes["K"]
    return _plan(
        "mfma_gemm", spec, dtype,
        dims=(_Dim("block_m", "M", M), _Dim("block_n", "N", N),
              _Dim("block_k", "K", K, depth=True)),
        caps={"block_m": 256, "block_n": 256, "block_k": 512},
        # A + B tiles in the operand dtype; C tile + f32 accumulator.
        footprint=lambda b, dsz: (b["block_m"] * b["block_k"] * dsz
                                  + b["block_k"] * b["block_n"] * dsz
                                  + 2 * b["block_m"] * b["block_n"] * 4),
        grid=lambda s, b: (s["M"] // b["block_m"], s["N"] // b["block_n"],
                           s["K"] // b["block_k"]),
        overrides=overrides, pad=pad)


def _plan_moe_gmm(shapes, dtype, spec, overrides, pad):
    E, C, K, N = shapes["E"], shapes["C"], shapes["K"], shapes["N"]
    return _plan(
        "moe_gmm", spec, dtype,
        dims=(_Dim("block_m", "C", C), _Dim("block_n", "N", N),
              _Dim("block_k", "K", K, depth=True)),
        caps={"block_m": 128, "block_n": 128, "block_k": 512},
        footprint=lambda b, dsz: (b["block_m"] * b["block_k"] * dsz
                                  + b["block_k"] * b["block_n"] * dsz
                                  + b["block_m"] * b["block_n"] * (dsz + 4)),
        grid=lambda s, b: (E, s["C"] // b["block_m"], s["N"] // b["block_n"],
                           s["K"] // b["block_k"]),
        overrides=overrides, pad=pad)


def _plan_flash_attention(shapes, dtype, spec, overrides, pad):
    B, S, T = shapes["B"], shapes["S"], shapes["T"]
    H, KV, hd = shapes["H"], shapes["KV"], shapes["hd"]
    return _plan(
        "flash_attention", spec, dtype,
        dims=(_Dim("block_q", "S", S), _Dim("block_kv", "T", T)),
        caps={"block_q": 512, "block_kv": 512},
        # q/o tiles + K and V tiles + f32 (acc, m, l) scratch.
        footprint=lambda b, dsz: (2 * b["block_q"] * hd * dsz
                                  + 2 * b["block_kv"] * hd * dsz
                                  + b["block_q"] * (hd + 2) * 4),
        grid=lambda s, b: (B * KV * (H // KV), s["S"] // b["block_q"],
                           s["T"] // b["block_kv"]),
        overrides=overrides, pad=pad)


def _plan_decode_attention(shapes, dtype, spec, overrides, pad,
                           kernel="decode_attention"):
    B, T = shapes["B"], shapes["T"]
    H, KV, hd = shapes["H"], shapes["KV"], shapes["hd"]
    G = H // KV
    return _plan(
        kernel, spec, dtype,
        dims=(_Dim("block_kv", "T", T),),
        caps={"block_kv": 512},
        footprint=lambda b, dsz: (2 * G * hd * dsz
                                  + 2 * b["block_kv"] * hd * dsz
                                  + G * (hd + 2) * 4),
        grid=lambda s, b: (B * KV, s["T"] // b["block_kv"]),
        overrides=overrides, pad=pad)


def _plan_paged_decode_attention(shapes, dtype, spec, overrides, pad):
    """Two tile dims.  ``block_kv`` doubles as the KV-pool page size and
    is chosen first, as ``decode_attention`` chooses it for one head (a
    (G, block_kv) score tile, one K and one V page), so the page never
    depends on the heads.  An optional
    ``shapes["page"]`` pins it to an existing pool's page so plans always
    match pool geometry on every device; omit it (the
    :class:`~repro.serve.PagedKVCache` constructor does) to let the
    planner choose the page size.  Then ``kv_step``, the KV heads one
    grid step reads: the largest divisor of KV whose step — q/o tiles,
    the K and V pages double-buffered, f32 (m, l, acc) scratch — fits
    the budget."""
    B, T = shapes["B"], shapes["T"]
    H, KV, hd = shapes["H"], shapes["KV"], shapes["hd"]
    G = H // KV
    page = shapes.get("page")
    if page is not None and overrides.get("block_kv") is None:
        overrides = dict(overrides, block_kv=int(page))
    if overrides.get("block_kv") is None:
        one_head = _plan_decode_attention(shapes, dtype, spec, {}, pad,
                                          kernel="paged_decode_attention")
        overrides = dict(overrides, block_kv=one_head.blocks["block_kv"])
    return _plan(
        "paged_decode_attention", spec, dtype,
        dims=(_Dim("block_kv", "T", T),
              _Dim("kv_step", "KV", KV, heads=True)),
        caps={"block_kv": 512, "kv_step": KV},
        footprint=lambda b, dsz: b["kv_step"] * (
            2 * G * hd * dsz + 4 * b["block_kv"] * hd * dsz
            + G * (hd + 2) * 4),
        grid=lambda s, b: (B * KV // b["kv_step"],
                           s["T"] // b["block_kv"]),
        overrides=overrides, pad=pad)


def _plan_mamba2_ssd(shapes, dtype, spec, overrides, pad):
    B, S, nh = shapes["B"], shapes["S"], shapes["nh"]
    hd, ds = shapes["hd"], shapes["ds"]
    return _plan(
        "mamba2_ssd", spec, dtype,
        # the chunk feeds (Q x Q) intra-chunk matmuls; chunked SSD stays
        # exact at any chunk, so it is sublane- rather than MXU-quantised
        dims=(_Dim("chunk", "S", S, sublane=True),),
        caps={"chunk": 256},
        footprint=lambda b, dsz: (2 * b["chunk"] * hd * dsz
                                  + 2 * b["chunk"] * ds * dsz
                                  + b["chunk"] * (dsz + 4)
                                  + 3 * b["chunk"] * b["chunk"] * 4
                                  + hd * ds * 4),
        grid=lambda s, b: (B, nh, s["S"] // b["chunk"]),
        overrides=overrides, pad=pad)


# ---------------------------------------------------------------------------
# Catalog
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One enumerable kernel: op entry point, oracle, planner, blocks."""

    name: str
    op: str                      # "module:attr" of the ops-layer wrapper
    ref: str                     # "module:attr" of the jnp oracle
    planner: Callable
    block_names: Tuple[str, ...]
    doc: str = ""
    #: Mesh-eligibility contract: problem dim -> logical axis name
    #: (``parallel.api`` rules).  Dims sharing a logical axis co-shard;
    #: dims absent here stay replicated under ``shard_map``.  ``None``
    #: means the kernel has no sharded execution path and dispatch keeps
    #: the legacy whole-op fallback on a mesh.  Plain strings only — the
    #: catalog stays importable without JAX.
    logical: Optional[Mapping[str, str]] = None

    def _resolve(self, target: str):
        mod, attr = target.split(":")
        return getattr(importlib.import_module(mod), attr)

    @property
    def op_fn(self):
        return self._resolve(self.op)

    @property
    def ref_fn(self):
        return self._resolve(self.ref)


_CATALOG: Dict[str, KernelEntry] = {}


def register_kernel(entry: KernelEntry, *,
                    replace: bool = False) -> KernelEntry:
    if entry.name in _CATALOG and not replace:
        raise ValueError(f"kernel {entry.name!r} is already registered")
    _CATALOG[entry.name] = entry
    return entry


def get_kernel(name: str) -> KernelEntry:
    try:
        return _CATALOG[name]
    except KeyError:
        raise UnknownKernelError(
            f"unknown kernel {name!r}; registered: {sorted(_CATALOG)}"
        ) from None


def list_kernels() -> Sequence[str]:
    return sorted(_CATALOG)


# ---------------------------------------------------------------------------
# plan_for
# ---------------------------------------------------------------------------

def _as_spec(device) -> DeviceSpec:
    if device is None:
        return get_device(DEFAULT_PLAN_DEVICE)
    if isinstance(device, DeviceSpec):
        return device
    spec = getattr(device, "spec", None)      # MachineModel duck-type
    if isinstance(spec, DeviceSpec):
        return spec
    return get_device(str(device))


def plan_for(kernel: str, shapes: Mapping[str, int], *,
             dtype="bfloat16",
             device: Union[None, str, DeviceSpec, object] = None,
             pad: bool = False,
             **overrides: Optional[int]) -> TilePlan:
    """Derive the tile plan for ``kernel`` on ``device``.

    ``shapes`` names the kernel's problem dims (``mfma_gemm`` wants
    M/N/K, ``moe_gmm`` E/C/K/N, ``flash_attention`` B/S/T/H/KV/hd,
    ``decode_attention`` B/T/H/KV/hd, ``mamba2_ssd`` B/S/nh/hd/ds).
    ``device`` is a registry name, a :class:`DeviceSpec`, or anything
    with a ``.spec`` (a ``MachineModel``); ``None`` plans for
    ``DEFAULT_PLAN_DEVICE``.  ``pad=True`` rounds dims up to the
    alignment quantum first — the perf engines use this to model padded
    execution of arbitrary HLO dots; the execution path leaves it off so
    misaligned shapes raise.  Keyword overrides (``block_m=...``) pin
    individual blocks, which are then validated rather than chosen.
    """
    entry = get_kernel(kernel)
    spec = _as_spec(device)
    overrides = {k: v for k, v in overrides.items() if v is not None}
    unknown = set(overrides) - set(entry.block_names)
    if unknown:
        raise ValueError(f"{kernel}: unknown block override(s) "
                         f"{sorted(unknown)}; expected {entry.block_names}")
    return entry.planner(dict(shapes), dtype, spec, overrides, pad)


for _entry in (
    KernelEntry(
        name="mfma_gemm", op="repro.kernels.ops:mfma_gemm",
        ref="repro.kernels.ref:mfma_gemm_ref", planner=_plan_mfma_gemm,
        block_names=("block_m", "block_n", "block_k"),
        doc="MXU-tiled accumulate-GEMM D = C + A @ B (the MFMA contract)"),
    KernelEntry(
        name="moe_gmm", op="repro.kernels.ops:moe_gmm",
        ref="repro.kernels.ref:moe_gmm_ref", planner=_plan_moe_gmm,
        block_names=("block_m", "block_n", "block_k"),
        doc="grouped per-expert matmul (E, C, K) @ (E, K, N)",
        logical={"E": "expert"}),
    KernelEntry(
        name="flash_attention", op="repro.kernels.ops:flash_attention",
        ref="repro.kernels.ref:flash_attention_ref",
        planner=_plan_flash_attention,
        block_names=("block_q", "block_kv"),
        doc="blockwise online-softmax causal GQA attention",
        logical={"B": "batch", "H": "heads", "KV": "heads"}),
    KernelEntry(
        name="decode_attention", op="repro.kernels.ops:decode_attention",
        ref="repro.kernels.ref:decode_attention_ref",
        planner=_plan_decode_attention,
        block_names=("block_kv",),
        doc="flash-decode: one query token vs a long KV cache",
        logical={"B": "batch", "H": "heads", "KV": "heads"}),
    KernelEntry(
        name="paged_decode_attention",
        op="repro.kernels.ops:paged_decode_attention",
        ref="repro.kernels.ref:paged_decode_attention_ref",
        planner=_plan_paged_decode_attention,
        block_names=("block_kv", "kv_step"),
        doc="flash-decode over a block-paged KV pool via a block table"),
    KernelEntry(
        name="mamba2_ssd", op="repro.kernels.ops:mamba2_ssd",
        ref="repro.kernels.ref:mamba2_ssd_ref", planner=_plan_mamba2_ssd,
        block_names=("chunk",),
        doc="chunked SSD (Mamba2): quadratic intra-chunk, linear across",
        logical={"B": "batch", "nh": "heads", "G": "heads"}),
):
    register_kernel(_entry)
