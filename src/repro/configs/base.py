"""ModelConfig: one dataclass covering all ten assigned architecture families."""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax.numpy as jnp

# Layer kinds usable in ``layer_pattern`` (the repeating period of the stack):
#   "attn"  : global self-attention block
#   "local" : sliding-window self-attention block
#   "cross" : cross-attention block (VLM; attends to vision tokens)
#   "mamba" : Mamba2 SSD block (attention-free)
LAYER_KINDS = ("attn", "local", "cross", "mamba")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # Repeating per-period layer pattern; num_layers % len(pattern) == 0.
    layer_pattern: tuple[str, ...] = ("attn",)

    mlp_kind: str = "swiglu"  # swiglu | geglu | gelu
    norm_eps: float = 1e-5
    rope_theta: float = 500_000.0
    sliding_window: int = 4096
    attn_softcap: float | None = None    # gemma2: 50.0
    final_softcap: float | None = None   # gemma2: 30.0, grok: 30.0
    tie_embeddings: bool = False
    scale_embed: bool = False            # gemma: h *= sqrt(d_model)
    use_rope: bool = True                # False: no positional encoding (NoPE)

    # Granite's scalars; the defaults are the plain decoder's.
    embedding_multiplier: float = 1.0    # h = embed(tokens) * m
    attention_multiplier: float | None = None  # score scale; None -> 1/sqrt(head_dim)
    residual_multiplier: float = 1.0     # h += m * branch, on both residual branches
    logits_scaling: float = 1.0          # logits = head(h) / s

    # MoE
    num_experts: int = 0
    experts_per_tok: int = 0
    moe_every: int = 1  # MoE replaces the dense MLP in every k-th layer
    moe_capacity_factor: float = 1.25  # >= num_experts/experts_per_tok -> dropless
    # Dropless routing (models/moe.py apply_dropless): the tokens routed to
    # the held experts are sorted by expert and run as grouped products; no
    # capacity, so no token is dropped. The capacity path above otherwise.
    moe_dropless: bool = False
    shared_d_ff: int = 0               # >0: a shared SwiGLU expert beside the routed ones
    # The contiguous block of experts this chip holds (expert parallelism):
    # the router scores all num_experts, the layer computes only the held
    # experts' part of the result. 0 held -> all of them.
    expert_start: int = 0
    experts_held: int = 0

    # SSM (Mamba2 SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_conv: int = 4

    # Modality
    is_encoder: bool = False          # encoder-only: no decode step
    vision_tokens: int = 0            # >0: cross-attn layers attend to a vision stub
    frontend_stub_dim: int = 0        # >0: inputs are precomputed frame/patch embeds

    # dtypes & perf knobs (hillclimbing operates on these)
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    sharding_preset: str = "tp"       # tp | fsdp
    moe_mode: str = "tp"              # tp | ep  (expert-parallel hillclimb option)
    # FSDP: constrain each scan iteration's weight slice to the gathered (TP)
    # view INSIDE the loop body. Without this XLA hoists one giant all-gather
    # of the whole stacked parameter array out of the loop — full-model-bytes
    # per device (catastrophic; see EXPERIMENTS.md §Perf iteration 1).
    fsdp_gather_per_layer: bool = True
    remat: str = "full"               # none | dots | full
    attn_chunk: int = 1024            # blockwise-attention KV chunk (prefill memory)
    scan_layers: bool = True
    optimizer_dtype: Any = jnp.float32  # moments dtype (bf16 = beyond-paper memory opt)

    def __post_init__(self) -> None:
        assert self.num_layers % len(self.layer_pattern) == 0, (
            f"{self.name}: {self.num_layers} layers not divisible by pattern "
            f"period {len(self.layer_pattern)}"
        )
        for k in self.layer_pattern:
            assert k in LAYER_KINDS, k
        if self.experts_held or self.shared_d_ff:
            assert self.moe_dropless, f"{self.name}: a held block or shared expert needs moe_dropless"
        assert 0 <= self.expert_start and self.expert_start + self.n_experts_held <= self.num_experts, (
            f"{self.name}: held experts [{self.expert_start}, +{self.n_experts_held}) "
            f"outside {self.num_experts}"
        )

    # ------------------------------------------------------------------ #
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def padded_vocab(self) -> int:
        """Embedding rows padded to a multiple of 256 so the vocab dim shards
        evenly on any production mesh axis (MaxText-style). Logits beyond
        ``vocab_size`` are masked to -inf."""
        return -(-self.vocab_size // 256) * 256

    @property
    def period(self) -> int:
        return len(self.layer_pattern)

    @property
    def num_periods(self) -> int:
        return self.num_layers // self.period

    @property
    def attention_free(self) -> bool:
        return all(k == "mamba" for k in self.layer_pattern)

    @property
    def subquadratic(self) -> bool:
        """True if prefill/decode memory does not grow quadratically in seq_len.

        SSM and hybrid (mostly-SSM) stacks qualify; pure-attention stacks don't.
        Used by the long_500k applicability rule.
        """
        n_attn = sum(k in ("attn", "local", "cross") for k in self.layer_pattern)
        return n_attn == 0 or (self.family in ("ssm", "hybrid"))

    @property
    def has_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def n_experts_held(self) -> int:
        """Experts whose weights this chip holds (all of them unless a block is set)."""
        return self.experts_held or self.num_experts

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim if self.ssm_state else 0

    def with_(self, **kw: Any) -> "ModelConfig":
        return replace(self, **kw)

    # ------------------------------------------------------------------ #
    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests (per the assignment)."""
        period = len(self.layer_pattern)
        n_exp = min(self.num_experts, 4)
        # The held block keeps its place and share among the fewer experts.
        held = max(1, self.experts_held * n_exp // self.num_experts) if self.experts_held else 0
        start = min(self.expert_start * n_exp // self.num_experts, n_exp - held) if held else 0
        return replace(
            self,
            name=f"{self.name}-reduced",
            num_layers=period,  # one full period
            d_model=64,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 2),
            head_dim=16,
            d_ff=128,
            vocab_size=256,
            num_experts=n_exp,
            experts_per_tok=min(self.experts_per_tok, n_exp),
            shared_d_ff=128 if self.shared_d_ff else 0,
            expert_start=start,
            experts_held=held,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_headdim=16 if self.ssm_state else self.ssm_headdim,
            vision_tokens=16 if self.vision_tokens else 0,
            frontend_stub_dim=32 if self.frontend_stub_dim else 0,
            sliding_window=32,
            attn_chunk=32,
            param_dtype=jnp.float32,
            compute_dtype=jnp.float32,
            sharding_preset="tp",
            remat="none",
        )
