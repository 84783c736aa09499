"""Config system: one frozen dataclass tree per architecture.

Every assigned architecture is a ``ModelConfig`` in its own module
(``repro.configs.<id>``); ``get_config(name)`` resolves by id.  Reduced
configs for CPU smoke tests come from ``ModelConfig.reduced()`` so tests
always exercise the same code path as the full model.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3)."""
    kv_lora_rank: int
    q_lora_rank: Optional[int]      # None => full-rank q projection
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def latent_dim(self) -> int:    # what the paged pool stores per token
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0
    d_ff_shared: int = 0            # total shared-expert hidden dim
    first_dense_layers: int = 0     # leading layers with dense MLP
    router_aux_coef: float = 0.001  # load-balance loss weight
    capacity_factor: float = 0.0    # 0 => dropless (sort + ragged_dot)
    parallel_mode: str = "tp"       # "tp" (d_ff sharded) | "ep" (a2a)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Covers Mamba2 (kind='mamba2') and RWKV6 (kind='rwkv6')."""
    kind: str
    state_dim: int = 64             # N (mamba2) / ignored by rwkv6
    head_dim: int = 64
    expand: int = 2                 # d_inner = expand * d_model
    conv_width: int = 4             # mamba2 conv1d
    chunk: int = 64                 # chunked-scan length
    subchunk: int = 0               # rwkv6: unrolled inner tiles (0 = off)
    intra_dtype: str = "float32"    # chunk-intra intermediates (bf16 opt)
    decay_lora: int = 64            # rwkv6 low-rank for w
    mix_lora: int = 32              # rwkv6 ddlerp low-rank


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Auxiliary encoder (whisper audio encoder / ViT stub)."""
    num_layers: int
    num_frames: int                 # encoder sequence length (stub frontend)
    frontend: str = "stub"          # embeddings arrive precomputed


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // num_heads
    attention: str = "gqa"          # gqa | mla | none
    mla: Optional[MLAConfig] = None
    # local/global attention pattern: every `local_ratio + 1` layers, the
    # last is global and the rest are local with `local_window`.
    local_window: Optional[int] = None
    local_ratio: int = 0            # gemma2: 1 (alternating); gemma3: 5
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    query_scale: Optional[float] = None  # override head_dim**-0.5
    qk_norm: bool = False           # qwen3-style per-head RMSNorm on q,k
    mlp: str = "swiglu"             # swiglu | geglu
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    shared_attn_every: int = 0      # zamba2: shared attn block period
    shared_attn_lora: int = 0       # zamba2: per-group LoRA rank on shared
    encoder: Optional[EncoderConfig] = None  # whisper / internvl frontend
    num_image_tokens: int = 0       # internvl: patch embeds prepended
    rope_theta: float = 10000.0
    rope_theta_local: Optional[float] = None  # gemma3: local layers' theta
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    post_norms: bool = False        # gemma2/3: post-attn/post-mlp norms
    embed_scale: bool = False       # gemma family: x *= sqrt(d_model)
    # MiniCPM's muP multipliers: embeddings x scale_emb, each residual
    # branch x scale_depth / sqrt(num_layers), the head's input
    # x dim_model_base / d_model.  Unset: none applied.
    scale_emb: float = 1.0
    scale_depth: Optional[float] = None
    dim_model_base: Optional[int] = None
    dtype: str = "bfloat16"
    # serving/paging knobs (the paper's block quantum)
    kv_block_tokens: int = 64
    # beyond-paper: shard the MLA latent KV pool over 'model' on the
    # kv_lora dim (rope stream kept separate+replicated).  See §Perf.
    mla_latent_tp: bool = False

    # -- derived -----------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def layer_is_local(self, layer: int) -> bool:
        if self.local_ratio <= 0 or self.local_window is None:
            return False
        return (layer % (self.local_ratio + 1)) != self.local_ratio

    # -- reduced config for CPU smoke tests -----------------------------
    def reduced(self) -> "ModelConfig":
        rep = dict(
            num_layers=min(self.num_layers, 4 if self.shared_attn_every == 0
                           else 2 * max(1, self.shared_attn_every)),
            d_model=128, num_heads=4,
            kv_heads=min(self.kv_heads, 2) if self.kv_heads < self.num_heads else 4,
            head_dim=32, d_ff=256, vocab_size=512, dtype="float32",
            kv_block_tokens=8,
        )
        if self.mla is not None:
            rep["mla"] = MLAConfig(kv_lora_rank=32,
                                   q_lora_rank=48 if self.mla.q_lora_rank else None,
                                   qk_nope_head_dim=16, qk_rope_head_dim=8,
                                   v_head_dim=16)
        if self.moe is not None:
            rep["moe"] = dataclasses.replace(
                self.moe, num_experts=8, top_k=2, d_ff_expert=64,
                d_ff_shared=64 if self.moe.num_shared_experts else 0)
        if self.ssm is not None:
            rep["ssm"] = dataclasses.replace(self.ssm, state_dim=16,
                                             head_dim=16, chunk=8,
                                             decay_lora=8, mix_lora=8)
        if self.local_window is not None:
            rep["local_window"] = 16
        if self.encoder is not None:
            rep["encoder"] = dataclasses.replace(self.encoder, num_layers=2,
                                                 num_frames=16)
        if self.num_image_tokens:
            rep["num_image_tokens"] = 8
        if self.shared_attn_lora:
            rep["shared_attn_lora"] = 8
        return dataclasses.replace(self, **rep)


ARCH_IDS = [
    "qwen3_moe_30b_a3b", "deepseek_v2_lite_16b", "minicpm3_4b",
    "gemma2_27b", "gemma3_27b", "gemma_2b", "internvl2_1b",
    "rwkv6_7b", "zamba2_2p7b", "whisper_tiny",
]


def get_config(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{name.replace('-', '_')}")
    return mod.CONFIG
