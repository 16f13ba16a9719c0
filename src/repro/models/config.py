"""Architecture configuration dataclasses.

One ``ModelConfig`` describes every assigned architecture family:
dense GQA decoders, MLA, MoE, Mamba2 SSD, hybrid (jamba), enc-dec (whisper)
and VLM (cross-attention) backbones.  ``reduced()`` derives the smoke-test
variant required by the assignment (small layers/width/experts, same family).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

__all__ = ["ModelConfig", "MoESpec", "MLASpec", "YarnSpec", "SSMSpec",
           "CrossAttnSpec", "EncoderSpec"]


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int               # the router's width: every expert of the layer
    top_k: int
    d_ff_expert: int
    n_shared: int = 0            # always-on shared experts (deepseek)
    d_ff_shared: int = 0
    period: int = 1              # MoE every `period` layers (jamba: 2)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.001
    norm_topk: bool = True       # renormalise the top-k gates to sum to 1
    # expert-parallel share: this device holds experts [held_offset,
    # held_offset + n_held) of the n_experts the router scores, computes
    # their part of the layer, and leaves out the others' (0 = all held)
    n_held: int = 0
    held_offset: int = 0

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class MLASpec:
    """Multi-head Latent Attention (DeepSeek-V2)."""
    kv_lora_rank: int = 512
    q_lora_rank: int = 0         # 0 => no query compression (V2-Lite)
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class YarnSpec:
    """YaRN RoPE scaling as DeepSeek-V2 publishes it (``rope_scaling``
    with ``type: yarn``): inverse frequencies ramp from the original ones
    to ones ``factor`` times slower between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations at
    ``original_max_position``; the softmax scale gains ``mscale(factor,
    mscale_all_dim)**2``.  Cos and sin would be scaled by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, which
    is 1 with the two equal, as every published YaRN config has them."""
    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0

    def __post_init__(self):
        if self.mscale != self.mscale_all_dim:
            raise ValueError(
                f"YarnSpec: mscale {self.mscale} != mscale_all_dim "
                f"{self.mscale_all_dim} (a cos/sin scale) is not modelled")


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """Mamba2 / SSD (state-space duality) mixer."""
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256


@dataclasses.dataclass(frozen=True)
class CrossAttnSpec:
    """VLM: every `period`-th layer cross-attends to media embeddings."""
    period: int = 5
    n_media_tokens: int = 4100   # precomputed patch embeddings (stub frontend)


@dataclasses.dataclass(frozen=True)
class EncoderSpec:
    """Whisper-style encoder; the conv frontend is a STUB (precomputed
    frame embeddings of shape (batch, n_frames, d_model))."""
    n_layers: int = 6
    n_frames: int = 1500


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | vlm | ssm | audio | moe | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 => d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    norm_type: str = "rms"       # rms | layer
    mlp_type: str = "swiglu"     # swiglu | gelu
    pos_embed: str = "rope"      # rope | learned | none
    rope_scaling: Optional[YarnSpec] = None
    tie_embeddings: bool = False
    moe: Optional[MoESpec] = None
    mla: Optional[MLASpec] = None
    ssm: Optional[SSMSpec] = None
    cross_attn: Optional[CrossAttnSpec] = None
    encoder: Optional[EncoderSpec] = None
    # hybrid (jamba): one attention layer per `attn_period` layers at
    # `attn_offset` within the period; all other mixers are SSM.
    attn_period: int = 0
    attn_offset: int = 0
    first_k_dense: int = 0       # deepseek: first k layers use dense FFN
    dtype: str = "bfloat16"
    sub_quadratic: bool = False  # can lower long_500k (SSM/hybrid)
    remat: str = "full"          # full | dots | none  (activation ckpt policy)
    scan_layers: bool = True
    microbatches: int = 1        # train-step gradient-accumulation factor
    # route catalog-backed mixer ops (attention train+decode, SSD, MoE
    # expert matmuls) through the repro.kernels Pallas layer instead of
    # the XLA reference formulations.  Dispatch is per-op via
    # repro.kernels.dispatch: anything the kernel path cannot support
    # (mesh-sharded execution, unplannable shapes, MLA's asymmetric head
    # dims) falls back to the reference with a logged reason.
    use_pallas: bool = False
    # repro.arch registry name the kernel tile plans are derived for
    # (mxu_dim alignment + vmem_bytes budget).  None -> the planner's
    # default TPU; set this to the executing device's registry entry so
    # tiles are sized against its actual VMEM.
    pallas_device: Optional[str] = None
    # gradient-accumulation dtype: f32 default; bf16 halves the accumulator
    # buffer AND the cross-device gradient reduction wire bytes at ~3 bits
    # of accumulated-mantissa cost (used by the largest MoE config)
    grad_accum_dtype: str = "float32"

    def __post_init__(self):
        # nested specs may come as plain dicts (a configuration file's
        # groups): build the dataclasses from them
        for name, cls in (("moe", MoESpec), ("mla", MLASpec),
                          ("rope_scaling", YarnSpec)):
            v = getattr(self, name)
            if isinstance(v, dict):
                object.__setattr__(self, name, cls(**v))

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_attn_free(self) -> bool:
        return self.family == "ssm"

    def layer_kind(self, idx: int) -> str:
        """'attn' or 'ssm' mixer for global layer index `idx` (hybrid)."""
        if self.family == "ssm":
            return "ssm"
        if self.attn_period:
            return "attn" if idx % self.attn_period == self.attn_offset else "ssm"
        return "attn"

    def layer_is_moe(self, idx: int) -> bool:
        if self.moe is None or idx < self.first_k_dense:
            return False
        return (idx - self.first_k_dense) % self.moe.period == 0 \
            if self.moe.period > 1 else idx >= self.first_k_dense

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: same family/wiring, tiny dims."""
        kw = dict(
            microbatches=1,
            n_layers=max(2, min(4, self.n_layers)),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            d_ff=256,
            vocab_size=512,
            head_dim=32,
        )
        if self.moe:
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=2, d_ff_expert=64,
                d_ff_shared=64 if self.moe.n_shared else 0, n_held=0,
                held_offset=0)
        if self.mla:
            kw["mla"] = MLASpec(kv_lora_rank=32, q_lora_rank=0,
                                qk_nope_dim=16, qk_rope_dim=16, v_head_dim=16)
            kw["head_dim"] = 0
        if self.ssm:
            kw["ssm"] = dataclasses.replace(self.ssm, d_state=16, head_dim=16,
                                            chunk=32)
        if self.cross_attn:
            kw["cross_attn"] = dataclasses.replace(self.cross_attn, period=2,
                                                   n_media_tokens=16)
            kw["n_layers"] = 4
        if self.encoder:
            kw["encoder"] = EncoderSpec(n_layers=2, n_frames=32)
        if self.attn_period:
            kw["attn_period"] = min(self.attn_period, 4)
            kw["attn_offset"] = min(self.attn_offset, 3)
            kw["n_layers"] = 2 * min(self.attn_period, 4)
        if self.first_k_dense:
            kw["first_k_dense"] = 1
        return dataclasses.replace(self, name=self.name + "-smoke", **kw)
