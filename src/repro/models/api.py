"""Unified model API: ``build_model(cfg)`` and a concrete test batch.

Every model exposes:
  init(rng) -> (params, axes)        param_specs() -> (shapes, axes)
  forward(params, batch, remat=...)  loss(params, batch, remat=...)
  prefill(params, batch, state, lengths)   decode_step(params, tokens, state)
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.lm import DecoderLM
from repro.models.mamba2_lm import Mamba2LM
from repro.models.rwkv_lm import RWKVLM
from repro.models.whisper import WhisperModel
from repro.models.zamba2 import Zamba2LM


def build_model(cfg: ModelConfig, max_positions: int = 4096):
    if cfg.family == "audio":
        return WhisperModel(cfg, max_positions=max_positions)
    if cfg.family == "hybrid":
        return Zamba2LM(cfg)
    if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
        return RWKVLM(cfg)
    if cfg.ssm is not None and cfg.ssm.kind == "mamba2":
        return Mamba2LM(cfg)
    return DecoderLM(cfg)


def make_concrete_batch(cfg: ModelConfig, B: int, S: int, seed: int = 0):
    """Small concrete batch for smoke tests."""
    rng = jax.random.PRNGKey(seed)
    r1, r2, r3 = jax.random.split(rng, 3)
    toks = S
    batch: Dict[str, Any] = {}
    if cfg.family == "audio":
        batch["frames"] = 0.1 * jax.random.normal(
            r3, (B, cfg.encoder.num_frames, cfg.d_model), jnp.float32)
    if cfg.num_image_tokens:
        toks = S - cfg.num_image_tokens
        batch["image_embeds"] = 0.1 * jax.random.normal(
            r3, (B, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    batch["tokens"] = jax.random.randint(r1, (B, toks), 0, cfg.vocab_size)
    batch["targets"] = jax.random.randint(r2, (B, toks), 0, cfg.vocab_size)
    return batch
