"""Unified model configuration for the architecture zoo.

One ``ModelConfig`` describes every assigned architecture: dense / MoE GQA
transformers, Mamba-2 SSM, the Jamba hybrid interleave, encoder-decoder
(seamless-m4t) and modality-stub VLM/audio variants.  The CoCa semantic-cache
integration is first-class: ``tap_layers`` marks the blocks after which pooled
semantic vectors are exposed to the cache.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

Family = Literal["dense", "moe", "ssm", "hybrid", "encdec", "vlm"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    # d_ff of each expert (may differ from the dense d_ff)
    d_expert: int
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01   # load-balance loss weight
    moe_every: int = 1                # apply MoE FFN every k-th layer (jamba: 2)
    d_shared: int = 0                 # shared SwiGLU expert width (0 = none)
    # (first, count) of the experts this device holds: the router still
    # scores all ``num_experts``, and dispatch is dropless over the held
    # ones (expert parallelism's local part).  None = every expert, with
    # capacity-based dispatch.
    held: tuple[int, int] | None = None

    def __post_init__(self):
        if self.held is not None:
            lo, n = (int(v) for v in self.held)
            if lo < 0 or n <= 0 or lo + n > self.num_experts:
                raise ValueError(f"held experts {self.held} outside "
                                 f"0..{self.num_experts}")
            object.__setattr__(self, "held", (lo, n))

    @property
    def num_held(self) -> int:
        return self.num_experts if self.held is None else self.held[1]


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 128                  # SSD chunk length
    conv_bias: bool = False           # bias on the depthwise conv (granite)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    num_layers: int
    d_model: int
    num_heads: int
    kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None       # defaults to d_model // num_heads
    # --- encoder-decoder -----------------------------------------------------
    enc_layers: int = 0               # >0 => encoder-decoder
    # --- hybrid (jamba-style) -------------------------------------------------
    attn_every: int = 0               # 0 = all-attention; 8 = 1 attn per 8 layers
    attn_offset: int = 4              # index of the attention layer in a period
    # --- variants -------------------------------------------------------------
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    qkv_bias: bool = False            # qwen1.5
    parallel_block: bool = False      # command-r: attn & FFN in parallel
    partial_rotary: float = 1.0       # glm4: 0.5 — RoPE on half the head dim
    rope_theta: float = 10_000.0
    position: Literal["rope", "nope"] = "rope"   # nope: no rotary (granite)
    attn_scale: float | None = None   # score scale; None = head_dim ** -0.5
    embedding_multiplier: float = 1.0  # input embeddings x this (granite 12)
    residual_multiplier: float = 1.0  # each residual branch x this (0.22)
    norm: Literal["rmsnorm", "layernorm"] = "rmsnorm"
    act: Literal["swiglu", "gelu"] = "swiglu"
    tie_embeddings: bool = False
    # --- modality stubs --------------------------------------------------------
    # "none": token ids only.  "audio"/"vision": input_specs additionally
    # provides precomputed frontend embeddings (B, frontend_len, d_model).
    frontend: Literal["none", "audio", "vision"] = "none"
    frontend_len: int = 0
    # --- CoCa semantic-cache integration ---------------------------------------
    tap_every: int = 0                # 0 = taps disabled; k = tap after every k blocks
    sem_dim: int = 256                # pooled-vector projection width
    num_classes: int = 0              # stream-task label space (0 = generative only)
    # --- numerics / scale ------------------------------------------------------
    dtype: str = "bfloat16"
    max_seq_len: int = 8192
    remat: bool = False               # activation checkpointing per layer group
    scan_layers: bool = True          # lax.scan over layer groups (compile-time
    #                                   friendly). False = unrolled python loop:
    #                                   needed when XLA cost_analysis must see
    #                                   every layer (roofline), since a while
    #                                   loop body is costed once, not ×G.

    def __post_init__(self):
        # nested groups given as dicts (a configuration read from JSON)
        for name, kind in (("moe", MoEConfig), ("ssm", SSMConfig)):
            v = getattr(self, name)
            if isinstance(v, dict):
                object.__setattr__(self, name, kind(**v))

    # long-context capability flag: quadratic-attention archs must skip
    # the 500k decode shape (DESIGN.md §4).
    @property
    def subquadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    def layer_kind(self, i: int) -> str:
        """'attn' or 'ssm' for decoder layer i (hybrid interleave)."""
        if self.family == "ssm":
            return "ssm"
        if self.attn_every > 0:
            return "attn" if i % self.attn_every == self.attn_offset else "ssm"
        return "attn"

    def layer_is_moe(self, i: int) -> bool:
        return self.moe is not None and (i % self.moe.moe_every
                                         == self.moe.moe_every - 1)

    def tap_layers(self) -> tuple[int, ...]:
        if self.tap_every <= 0:
            return ()
        return tuple(range(self.tap_every - 1, self.num_layers, self.tap_every))

    def param_count(self) -> int:
        """Approximate total parameters (embedding + blocks), for roofline."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.resolved_head_dim
        qkv = d * hd * (self.num_heads + 2 * self.kv_heads) + self.num_heads * hd * d
        mlp_mult = 3 if self.act == "swiglu" else 2
        dense_ff = mlp_mult * d * ff
        n = 0
        for i in range(self.num_layers):
            kind = self.layer_kind(i)
            if kind == "attn":
                n += qkv
            else:
                s = self.ssm or SSMConfig()
                d_in = s.expand * d
                n += 2 * d * d_in + d_in * d + d_in * (2 * s.d_state + 2)
            if self.layer_is_moe(i):
                n += self.moe.num_experts * mlp_mult * d * self.moe.d_expert
            elif ff > 0:
                n += dense_ff
        n += v * d * (1 if self.tie_embeddings else 2)
        if self.is_encdec:
            n += self.enc_layers * (qkv * 2 + dense_ff)   # self+cross attn
        return n

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top-k experts only)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        mlp_mult = 3 if self.act == "swiglu" else 2
        per_layer_moe = self.moe.num_experts * mlp_mult * d * self.moe.d_expert
        active_moe = self.moe.top_k * mlp_mult * d * self.moe.d_expert
        n_moe_layers = sum(self.layer_is_moe(i) for i in range(self.num_layers))
        return self.param_count() - n_moe_layers * (per_layer_moe - active_moe)
