"""Model configuration for the repro model zoo.

One ``ModelConfig`` describes every architecture family in the assigned pool:
dense decoder-only transformers (GQA / RoPE / SwiGLU, local:global attention
patterns, logit soft-capping), MoE variants (top-k routing), Mamba2 SSD,
Zamba2-style hybrids, encoder-decoder (audio) backbones and early-fusion
multimodal backbones, and DeepSeek-V2-style multi-head latent attention
(MLA) with YaRN rope, leading dense layers and an expert layer that holds a
share of its routed experts.  Modality frontends are stubs per the
assignment: the backbone consumes precomputed frame/patch embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # identity
    name: str = "model"
    family: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio
    citation: str = ""

    # trunk
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0  # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024
    activation: str = "swiglu"  # swiglu | geglu | gelu
    tie_embeddings: bool = True
    norm_eps: float = 1e-6

    # attention details
    rope_theta: float = 10_000.0
    attn_pattern: Tuple[str, ...] = ("global",)  # cycled over layers
    sliding_window: int = 0  # tokens, for 'local' layers (0 = disabled)
    attn_logit_softcap: float = 0.0  # 0 = disabled (gemma2: 50.0)
    final_logit_softcap: float = 0.0  # 0 = disabled (gemma2: 30.0)
    qk_norm: bool = False  # gemma3-style

    # multi-head latent attention (DeepSeek-V2): keys and values are
    # expanded per head from a normed latent of kv_lora_rank; q/k heads
    # are qk_nope_head_dim + qk_rope_head_dim wide (the rope part of k is
    # one vector shared by all heads), v heads v_head_dim
    kv_lora_rank: int = 0  # 0 = grouped-query attention
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # YaRN rope scaling (DeepseekV2YarnRotaryEmbedding); factor 0 = plain rope
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # MoE
    num_experts: int = 0  # 0 = dense FFN
    experts_per_token: int = 0
    moe_d_ff: int = 0  # 0 -> d_ff
    moe_period: int = 1  # MoE FFN every k-th layer (llama4: 2 — interleaved)
    moe_shared_expert: bool = False  # dense shared expert on MoE layers
    shared_expert_d_ff: int = 0  # 0 -> d_ff
    first_dense_layers: int = 0  # dense layers before the MoE stack
    router_aux_coef: float = 0.01
    moe_aux_per_sequence: bool = False  # DeepSeek's sequence-wise balance loss
    moe_renormalize: bool = True  # top-k weights renormalized to sum to 1
    moe_capacity_factor: float = 1.25  # 0 = dropless
    experts_held: int = 0  # this device's share: the first experts_held of
    #                        num_experts (0 = all); the router keeps all

    # SSM (Mamba2 / SSD)
    ssm_state_size: int = 0  # 0 = no ssm layers
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 256
    ssm_ngroups: int = 1

    # hybrid (zamba2-style): period at which the shared attention block fires
    hybrid_attn_period: int = 0  # 0 = no shared attention block

    # encoder-decoder (seamless-style)
    num_encoder_layers: int = 0  # 0 = decoder-only

    # multimodal stub frontend: backbone consumes precomputed embeddings
    frontend: str = "none"  # none | vision | audio
    frontend_tokens: int = 0  # number of prefix embedding positions

    # ---- derived ----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(1, self.num_heads))

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim

    @property
    def resolved_moe_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def resolved_shared_d_ff(self) -> int:
        return self.shared_expert_d_ff or self.d_ff

    @property
    def resolved_experts_held(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def moe_grouped(self) -> bool:
        """The expert layer of ``moe.moe_apply_grouped``: dropless or a held
        share.  Otherwise the capacity dispatch of ``moe.moe_apply``."""
        return bool(self.num_experts) and (
            self.moe_capacity_factor == 0
            or self.resolved_experts_held != self.num_experts)

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def mla_qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def attn_params_per_layer(self) -> int:
        d = self.d_model
        if self.is_mla:
            H, r = self.num_heads, self.kv_lora_rank
            return (d * H * self.mla_qk_head_dim  # wq
                    + d * (r + self.qk_rope_head_dim) + r  # wkv_a, kv_norm
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * d)  # wo
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """True if the arch can serve long_500k (sub-quadratic story)."""
        if self.family in ("ssm", "hybrid"):
            return True
        # dense archs qualify only with a native sliding-window variant
        return self.sliding_window > 0

    def layer_kind(self, i: int) -> str:
        """Attention kind for layer i (dense trunk): 'local' or 'global'."""
        return self.attn_pattern[i % len(self.attn_pattern)]

    def num_params(self) -> int:
        """Approximate parameter count (used for roofline MODEL_FLOPS)."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        n = V * d  # embeddings
        if not self.tie_embeddings:
            n += V * d
        per_layer = 0
        if self.family == "ssm":
            di = self.ssm_d_inner
            ng, ss = self.ssm_ngroups, self.ssm_state_size
            nh = self.ssm_nheads
            # in_proj: d -> 2*di + 2*ng*ss + nh ; out_proj: di -> d
            per_layer = d * (2 * di + 2 * ng * ss + nh) + di * d
            per_layer += self.ssm_conv_width * (di + 2 * ng * ss)
            per_layer += 2 * nh + di  # A_log, D, norm
            n += L * per_layer
        else:
            attn = self.attn_params_per_layer()
            if self.num_experts:
                n_moe = self.num_moe_layers
                n_dense = L - n_moe
                ff_moe = (3 * d * self.resolved_moe_d_ff
                          * self.resolved_experts_held)
                ff_moe += d * self.num_experts  # router
                if self.moe_shared_expert:
                    ff_moe += 3 * d * self.resolved_shared_d_ff
                ff = (n_moe * ff_moe + n_dense * 3 * d * self.d_ff) / max(1, L)
            else:
                ff = 3 * d * self.d_ff
            per_layer = attn + ff + 2 * d
            if self.family == "hybrid":
                # mamba trunk + one shared attention block
                di = self.ssm_d_inner
                ng, ss = self.ssm_ngroups, self.ssm_state_size
                nh = self.ssm_nheads
                mamba = d * (2 * di + 2 * ng * ss + nh) + di * d
                n += L * mamba + (attn + 3 * d * self.d_ff)
            else:
                enc_dec_mult = 1
                if self.num_encoder_layers:
                    # decoder layers additionally carry cross-attention
                    n += self.num_encoder_layers * per_layer
                    n += L * (2 * d * self.kv_dim + d * self.q_dim + self.q_dim * d)
                n += L * per_layer * enc_dec_mult
        return int(n)

    @property
    def num_moe_layers(self) -> int:
        """Layers with an expert FFN: after ``first_dense_layers``, every
        ``moe_period``-th."""
        return (self.num_layers - self.first_dense_layers) // self.moe_period

    def num_active_params(self) -> int:
        """Active params per token (MoE: only routed experts count; of a
        held share, the expected k * held / E experts a token meets
        here)."""
        if not self.num_experts:
            return self.num_params()
        total = self.num_params()
        expert = 3 * self.d_model * self.resolved_moe_d_ff
        held = self.resolved_experts_held
        active = max(1, self.experts_per_token) * held / self.num_experts
        return int(total - self.num_moe_layers * expert * (held - active))


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """A smoke-test-sized variant of the same family (<=2 layers, small dims)."""
    base = dict(
        num_layers=2,
        d_model=128,
        num_heads=4,
        num_kv_heads=max(1, min(cfg.num_kv_heads, 2)),
        head_dim=32,
        d_ff=256,
        vocab_size=512,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        frontend_tokens=min(cfg.frontend_tokens, 8) if cfg.frontend_tokens else 0,
    )
    if cfg.num_experts:
        base.update(num_experts=min(4, cfg.num_experts), moe_d_ff=256,
                    experts_per_token=min(cfg.experts_per_token, 2))
        if cfg.shared_expert_d_ff:
            base.update(shared_expert_d_ff=512)
        if cfg.experts_held:
            base.update(experts_held=min(cfg.experts_held, 2))
    if cfg.kv_lora_rank:
        base.update(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16)
    if cfg.ssm_state_size:
        base.update(ssm_state_size=16, ssm_head_dim=32, ssm_chunk=32)
    if cfg.num_encoder_layers:
        base.update(num_encoder_layers=2)
    if cfg.hybrid_attn_period:
        base.update(hybrid_attn_period=2)
    base.update(overrides)
    return dataclasses.replace(cfg, **base)
