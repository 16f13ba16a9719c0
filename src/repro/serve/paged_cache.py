"""Block-paged KV cache: a shared, reference-counted pool of KV blocks.

Cache layout
------------
Every GQA attention layer owns two pools ``k``/``v`` of shape
``(P, KV, page, hd)`` (an MLA layer a ``ckv`` pool of latent rows,
``(P, 1, page, kv_lora_rank)``, and a ``kpe`` pool of rope keys stored
transposed, ``(P, 1, qk_rope_dim, page)``; read as one key head whose
leading columns are the values): ``P`` physical blocks of ``page`` token rows,
head-major inside a block so each head's page is one contiguous
``(page, hd)`` slab the TPU kernel can DMA as a tile; the pools of
the layers the model scans are stacked, ``(n_periods, P, KV, page,
hd)``, and each step reads and writes them in place.  A
request's cache is the *logical* concatenation of the blocks its row of
the (B, NB) block table names — the table is shared across layers, so
one allocation covers the whole model.  ``page`` is the MXU-aligned
``block_kv`` the ``paged_decode_attention`` planner derives from the
target :class:`~repro.arch.DeviceSpec` (the pool's gather granularity
IS the kernel's kv tile), overridable for tests.

Physical block 0 is the reserved **null block**: it is never allocated,
idle engine slots point their whole table at it, and their masked
scatter-writes land there harmlessly — so one compiled decode step can
run over a fixed-size slot array with any subset active.

The host half — refcounts, free lists, the prefix index and its
copy-on-write rules — is :class:`~repro.serve.blocks.BlockAllocator`,
which holds no device state; :class:`PagedKVCache` is that allocator
plus the pools and :meth:`~PagedKVCache.fork`.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels.plan import plan_for
from repro.models.blocks import layer_sigs, schedule
from repro.models.config import ModelConfig
from repro.models.layers import cdtype
from repro.serve.blocks import BlockAllocator, prefix_digests

__all__ = ["BlockAllocator", "PagedKVCache", "default_page_size",
           "init_pools", "pool_heads", "prefix_digests"]

#: T the page-size probe plans for: the planner cap, so the chosen page
#: is the largest aligned block the device's VMEM budget admits.
_PROBE_T = 512


def pool_heads(cfg: ModelConfig):
    """(KV heads, row width) of one layer's pool: GQA's K and V heads, or
    MLA's latent rows (c_kv | roped key) as one head."""
    if cfg.mla:
        return 1, cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
    return cfg.n_kv_heads, cfg.hd


def default_page_size(cfg: ModelConfig, device=None, *,
                      cap: Optional[int] = None) -> int:
    """The page size the planner picks for ``cfg``'s heads on ``device``.

    ``cap`` bounds the probe length (an engine passes its ``max_len``):
    without it the planner returns its largest VMEM-admissible block,
    and a pool paged coarser than the requests it serves makes every
    decode tick gather and attend over rows that can never hold data.
    """
    probe_t = _PROBE_T if cap is None else min(_PROBE_T, max(1, cap))
    kv, width = pool_heads(cfg)
    plan = plan_for("paged_decode_attention",
                    {"B": 1, "T": probe_t, "H": cfg.n_heads,
                     "KV": kv, "hd": width},
                    dtype=cfg.dtype, device=device)
    return plan.blocks["block_kv"]


def init_pools(cfg: ModelConfig, n_blocks: int, page: int) -> Dict:
    """The zeroed pool pytree: ``layers0`` one pool per leading layer,
    ``layers`` one (n_periods, ...) stack per period slot.  A GQA pool is
    ``{"k", "v"}`` of (P, KV, page, hd); an MLA pool ``{"ckv", "kpe"}``:
    each token's normed latent c_kv, (P, 1, page, kv_lora_rank), and its
    roped key stored transposed, (P, 1, qk_rope_dim, page), so neither
    pool pads its minor dim to the TPU's 128 lanes (at DeepSeek-V2 widths
    576 bf16 values a token a layer, 31,104 B over 27 layers)."""
    dt = cdtype(cfg)
    kv, width = pool_heads(cfg)
    first_k, period, n_periods = schedule(cfg)

    def pool():
        if cfg.mla:
            m = cfg.mla
            return {"ckv": jnp.zeros((n_blocks, 1, page, m.kv_lora_rank), dt),
                    "kpe": jnp.zeros((n_blocks, 1, m.qk_rope_dim, page), dt)}
        shp = (n_blocks, kv, page, width)
        return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}

    return {
        "layers0": [pool() for _ in range(first_k)],
        "layers": tuple(
            jax.tree.map(lambda a: jnp.zeros((n_periods,) + a.shape,
                                             a.dtype), pool())
            for _ in range(period)),
    }


class PagedKVCache(BlockAllocator):
    """Pool pytree + the :class:`BlockAllocator` over its blocks.

    ``page=None`` asks the planner (:func:`default_page_size`); an
    explicit page is validated against the same tiling contract (it must
    be MXU-aligned, or the paged kernel could never run on it).
    """

    def __init__(self, cfg: ModelConfig, *, n_blocks: int,
                 page: Optional[int] = None, device=None):
        sigs = layer_sigs(cfg)
        bad = [f"layer {i}: {s[0]}" for i, s in enumerate(sigs)
               if s[0] != "attn"]
        if bad:
            raise NotImplementedError(
                "PagedKVCache: only attention layers page (GQA K/V or "
                "MLA latent rows); SSM state and cross-attention caches "
                f"do not (config {cfg.name!r} has {', '.join(bad)})")
        if page is None:
            page = default_page_size(cfg, device)
        else:
            # pinning block_kv re-runs the tiling contract: a misaligned
            # page raises here, not inside the first decode step
            kv, width = pool_heads(cfg)
            plan_for("paged_decode_attention",
                     {"B": 1, "T": page, "H": cfg.n_heads,
                      "KV": kv, "hd": width, "page": page},
                     dtype=cfg.dtype, device=device)
        super().__init__(n_blocks, page)
        self.cfg = cfg
        self.pools = init_pools(cfg, self.n_blocks, self.page)

    def fork(self, b: int) -> int:
        """Copy-on-write: give the caller a private copy of shared block
        ``b``, moving one of its references onto the copy.  Returns the
        new block id (unregistered — the forker is about to overwrite
        it).  The copy is an on-device row copy across every layer pool;
        the other holders' view of ``b`` is untouched."""
        self._check_range(b, "fork")
        if self._refs[b] == 0:
            raise ValueError(f"fork: block {b} has no references")
        got = self.alloc(1)
        if got is None:
            raise ValueError("fork: pool exhausted (no block for the copy)")
        dst = got[0]

        def cp(pool):
            if pool.ndim == 5:          # (n_periods, P, KV, page, hd)
                return pool.at[:, dst].set(pool[:, b])
            return pool.at[dst].set(pool[b])

        self.pools = jax.tree.map(cp, self.pools)
        self.free([b])
        return dst
