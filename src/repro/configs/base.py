"""Configuration system: model / sharding / train configs + registry.

Every assigned architecture gets a module ``src/repro/configs/<id>.py`` that
exposes ``CONFIG`` (the exact published configuration) and ``reduced()`` (a
tiny same-family config for CPU smoke tests).  ``get_config`` resolves either.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | hybrid | ssm | vlm | audio
    num_layers: int             # decoder layers for enc-dec
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int                   # dense MLP hidden (per-expert hidden for MoE)
    vocab_size: int
    head_dim: int = 0           # 0 => d_model // num_heads

    # Attention variants
    qk_norm: bool = False
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    sliding_window: Optional[int] = None   # local-attention window size
    local_global_period: int = 0           # >0: every Nth layer is global (rest local)
    rope_theta: float = 10_000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl M-RoPE

    # MoE
    num_experts: int = 0
    num_experts_per_token: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01

    # SSM / hybrid
    ssm_state: int = 0          # Mamba2 d_state
    ssm_heads: int = 0          # Mamba2 heads (0 => derived)
    ssm_expand: int = 2         # Mamba2 expansion factor
    ssm_conv: int = 4           # conv1d width
    attn_every: int = 0         # zamba2: shared attn block after every Nth layer
    rwkv: bool = False
    rwkv_head_size: int = 64

    # Encoder-decoder
    encoder_layers: int = 0     # >0 => enc-dec; num_layers is the decoder depth

    # Misc architecture
    act: str = "silu"           # silu => SwiGLU, gelu => GeGLU
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    post_norm: bool = False     # gemma2-style additional post-block norms
    emb_scale: bool = False     # gemma-style sqrt(d_model) embedding scale

    # Numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    # Implementation switches (perf levers; do not change semantics)
    attn_chunk: int = 1024      # KV chunk for the chunked-ref path
    moe_impl: str = "dropping"  # dense | dropping (capacity-based EP dispatch)
    remat: str = "block"        # none | block (recompute everything) |
                                # policy (save matmul outputs, recompute
                                # elementwise only -- cheaper backward)
    fuse_kv: bool = True        # K/V projections fused via a stacked leading
                                # axis (never concat across the head dim:
                                # that miscompiles when heads are sharded)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def is_attention_free(self) -> bool:
        return self.rwkv

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Sharding configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardingConfig:
    mode: str = "dp_tp"        # dp_tp (params replicated over data) | fsdp_tp
    zero: int = 1              # 0: opt state like params; 1: opt sharded over data
    microbatches: int = 1      # gradient accumulation steps


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    seed: int = 0


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "granite-20b",
    "gemma2-2b",
    "qwen3-8b",
    "internlm2-1.8b",
    "zamba2-1.2b",
    "kimi-k2-1t-a32b",
    "llama4-scout-17b-a16e",
    "rwkv6-3b",
    "qwen2-vl-72b",
    "seamless-m4t-medium",
]


def _module_name(arch_id: str) -> str:
    return "repro.configs." + arch_id.replace("-", "_").replace(".", "_")


def get_config(arch_id: str, reduced: bool = False) -> ModelConfig:
    mod = importlib.import_module(_module_name(arch_id))
    return mod.reduced() if reduced else mod.CONFIG


def list_archs():
    return list(ARCH_IDS)


# ---------------------------------------------------------------------------
# Analytic parameter accounting
# ---------------------------------------------------------------------------

def param_count(cfg: ModelConfig) -> int:
    """Analytic parameter count for the configured model."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    qo = cfg.num_heads * hd
    kv = cfg.num_kv_heads * hd
    attn = d * qo + 2 * d * kv + qo * d  # wq, wk, wv, wo
    if cfg.qk_norm:
        attn += 2 * hd
    gated = cfg.act in ("silu", "gelu")
    mlp_dense = (3 if gated else 2) * d * cfg.d_ff

    def block_norms():
        return (4 if cfg.post_norm else 2) * d

    total = 0
    if cfg.rwkv:
        # time-mix: r,k,v,g,o (d*d each) + decay/low-rank (approx) + channel mix
        tmix = 5 * d * d + 2 * d * 32 * 2  # lora-ish decay/mix params (approx)
        cmix = 2 * d * int(cfg.d_ff)
        total += cfg.num_layers * (tmix + cmix + 2 * d)
    elif cfg.family == "hybrid":
        d_inner = cfg.ssm_expand * d
        mamba = (d * (2 * d_inner + 2 * cfg.ssm_state)  # in_proj(z,x) + B,C
                 + d_inner * cfg.ssm_conv                # conv
                 + d_inner                               # dt bias (per channel head)
                 + d_inner * d)                          # out_proj
        total += cfg.num_layers * (mamba + block_norms())
        n_attn = cfg.num_layers // max(cfg.attn_every, 1) if cfg.attn_every else 0
        if n_attn:
            total += attn + mlp_dense + block_norms()    # one shared block
    else:
        if cfg.is_moe:
            per_expert = (3 if gated else 2) * d * cfg.d_ff
            ffn = cfg.num_experts * per_expert + d * cfg.num_experts  # + router
        else:
            ffn = mlp_dense
        layers = cfg.num_layers + cfg.encoder_layers
        total += layers * (attn + ffn + block_norms())
        if cfg.encoder_layers:  # decoder cross-attention
            total += cfg.num_layers * (d * qo + 2 * d * kv + qo * d + d)
    total += cfg.vocab_size * d          # embedding
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * d      # lm head
    total += d                           # final norm
    return int(total)
