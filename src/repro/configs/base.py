"""Architecture configuration system.

Every assigned architecture is a frozen ``ArchConfig``; reduced smoke variants
are derived with ``cfg.reduced()``. Configs are registered by id in
``repro.configs.registry`` and selectable via ``--arch <id>`` in the launchers.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

# Block kinds a layer stack can be made of.
BLOCK_ATTN = "attn"      # transformer block (attention + MLP/MoE)
BLOCK_MAMBA2 = "mamba2"  # Mamba2 SSD block
BLOCK_RWKV6 = "rwkv6"    # RWKV-6 (Finch) block

FRONTEND_NONE = "none"
FRONTEND_AUDIO = "audio"    # stub: precomputed EnCodec frame embeddings
FRONTEND_VISION = "vision"  # stub: precomputed ViT patch embeddings


@dataclass(frozen=True)
class ArchConfig:
    """Complete architecture description (backbone only for audio/vlm)."""

    name: str
    family: str                  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    # --- attention ---
    n_heads: int = 0             # 0 => attention-free architecture
    n_kv_heads: int = 0
    head_dim: int = 0            # 0 => d_model // n_heads
    qk_norm: bool = False
    sliding_window: int = 0      # 0 => full causal attention
    global_every: int = 0        # gemma3: every Nth layer is global (rest SWA)
    rope_theta: float = 1e4
    # --- latent attention (MLA; kv_lora_rank 0 => plain GQA) ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- MoE ---
    n_experts: int = 0           # the router's outputs (the published count)
    experts_per_token: int = 0
    experts_held: int = 0        # experts 0..held-1 live here (0 => all)
    router_aux_weight: float = 0.01
    router_score: str = "softmax"  # softmax | sigmoid (+ a choice-only bias)
    router_scale: float = 1.0    # routed_scaling_factor
    n_shared_experts: int = 0    # one gated MLP of n_shared * moe_d_ff
    first_k_dense: int = 0       # leading layers with a dense MLP of d_ff
    moe_d_ff: int = 0            # expert hidden size (0 => d_ff)
    # --- SSM (mamba2 / rwkv6) ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_expand: int = 2
    conv_width: int = 4
    # --- hybrid (zamba2) ---
    shared_attn_every: int = 0   # invoke the single shared attn block every N layers
    # --- stack composition ---
    block_type: str = BLOCK_ATTN
    # --- modality frontend (stub per brief) ---
    frontend: str = FRONTEND_NONE
    n_prefix_embeds: int = 0     # vlm: number of prepended patch embeddings
    n_codebooks: int = 0         # audio: EnCodec codebooks (embeddings summed)
    # --- misc ---
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    source: str = ""             # citation

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.n_heads == 0:
            return 0
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def attention_free(self) -> bool:
        return self.block_type in (BLOCK_MAMBA2, BLOCK_RWKV6) and self.shared_attn_every == 0

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch supports long_500k (SSM / hybrid / windowed attn)."""
        if self.block_type in (BLOCK_MAMBA2, BLOCK_RWKV6):
            return True
        return self.sliding_window > 0

    def layer_window_sizes(self) -> list[int]:
        """Per-layer attention window (0 = full/global) for BLOCK_ATTN stacks."""
        out = []
        for i in range(self.n_layers):
            if self.sliding_window and self.global_every:
                # gemma3 pattern: every `global_every`-th layer is global.
                out.append(0 if (i + 1) % self.global_every == 0 else self.sliding_window)
            elif self.sliding_window:
                out.append(self.sliding_window)
            else:
                out.append(0)
        return out

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-FLOPs roofline)."""
        d, v = self.d_model, self.vocab_size
        n = v * d  # embedding
        if not self.tie_embeddings:
            n += v * d
        hd = self.resolved_head_dim
        for i in range(self.n_layers):
            if self.block_type == BLOCK_ATTN:
                n += self._attn_params()
                if self.is_moe and i >= self.first_k_dense:
                    n += self.n_held * 3 * d * self.expert_d_ff \
                        + d * self.n_experts \
                        + 3 * d * self.n_shared_experts * self.expert_d_ff
                    if self.router_score == "sigmoid":
                        n += self.n_experts  # the choice bias
                else:
                    n += 3 * d * self.d_ff
                n += 2 * d  # norms
            elif self.block_type == BLOCK_MAMBA2:
                di = self.ssm_expand * d
                n += d * (2 * di + 2 * self.ssm_state) + di * d + 2 * d
            elif self.block_type == BLOCK_RWKV6:
                n += 6 * d * d + 3 * d * self.d_ff // 2 + 2 * d
        if self.shared_attn_every:
            n += 4 * d * d + 3 * d * self.d_ff
        return n

    def _attn_params(self) -> int:
        d, h, hd = self.d_model, self.n_heads, self.resolved_head_dim
        if not self.is_mla:
            return d * h * hd + 2 * d * self.n_kv_heads * hd + h * hd * d
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return (d * h * qk                                   # q
                + d * (self.kv_lora_rank + self.qk_rope_head_dim)  # kv_a
                + self.kv_lora_rank                          # kv_a norm
                + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                           + self.v_head_dim)  # kv_b
                + h * self.v_head_dim * d)                   # o

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        if not self.is_moe:
            return self.param_count()
        full = self.param_count()
        per_layer_expert = 3 * self.d_model * self.expert_d_ff
        inactive = (self.n_layers - self.first_k_dense) * \
            (self.n_held - self.experts_per_token) * per_layer_expert
        return full - inactive

    def reduced(self) -> "ArchConfig":
        """Tiny same-family variant for CPU smoke tests (brief: 2 layers,
        d_model<=512, <=4 experts)."""
        d = min(self.d_model, 128)
        n_heads = min(self.n_heads, 4) if self.n_heads else 0
        mla = {}
        if self.is_mla:
            mla = dict(kv_lora_rank=32, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16)
        n_kv = min(self.n_kv_heads, n_heads) if self.n_kv_heads else 0
        if self.block_type == BLOCK_RWKV6:
            ssm_state, ssm_heads = 16, d // 16  # rwkv requires h*n == d
        else:
            ssm_state = min(self.ssm_state, 16) if self.ssm_state else 0
            ssm_heads = min(self.ssm_heads, 4) if self.ssm_heads else 0
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=2,
            d_model=d,
            d_ff=min(self.d_ff, 4 * d),
            moe_d_ff=min(self.expert_d_ff, 2 * d) if self.is_moe else 0,
            vocab_size=min(self.vocab_size, 512),
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=d // n_heads if n_heads else 0,
            n_experts=min(self.n_experts, 4) if self.is_moe else 0,
            experts_per_token=min(self.experts_per_token, 2) if self.is_moe else 0,
            experts_held=0,
            first_k_dense=min(self.first_k_dense, 1),
            ssm_state=ssm_state,
            ssm_heads=ssm_heads,
            sliding_window=min(self.sliding_window, 32) if self.sliding_window else 0,
            global_every=self.global_every,
            shared_attn_every=self.shared_attn_every,
            n_prefix_embeds=min(self.n_prefix_embeds, 8) if self.n_prefix_embeds else 0,
            **mla,
        )


@dataclass(frozen=True)
class InputShape:
    """One assigned (seq_len, global_batch) workload."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}
