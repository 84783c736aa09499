"""Whisper-tiny backbone: encoder-decoder transformer.

The conv/mel frontend is a STUB per the assignment: ``input_specs()``
provides precomputed frame embeddings (B, F, d).  Encoder: sinusoidal
positions + bidirectional layers.  Decoder: learned positions, causal
self-attention (paged KV at decode) + cross-attention over the encoder
output (static length -> its KV is computed once at prefill and stored
densely; only the *growing* self-attn stream needs the paper's block
pool).

Note: whisper's published max_target_positions is 448; the assigned
train_4k/decode_32k shapes exceed that, so the learned position table is
sized to the requested sequence (documented deviation, DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.paged_kv import PagedKVCache, PagedKVConfig
from repro.launch.shardings import constrain
from repro.models import attention as A
from repro.models.common import (AxTree, Params, chunked_lm_loss, dense_init,
                                 flash_attention, init_mlp, mlp, rmsnorm,
                                 sinusoidal_positions)
from repro.models.lm import (_stack_axes, eval_shape_with_aux,
                             write_token_paged)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class WhisperState:
    self_kv: PagedKVCache            # decoder self-attn, L = num_layers
    cross_k: jax.Array               # (L, B, F, KVH, hd)
    cross_v: jax.Array

    def tree_flatten(self):
        return (self.self_kv, self.cross_k, self.cross_v), None

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)


class WhisperModel:
    def __init__(self, cfg: ModelConfig, max_positions: int = 4096):
        self.cfg = cfg
        self.max_positions = max_positions

    def _init_enc_layer(self, rng):
        cfg = self.cfg
        r1, r2 = jax.random.split(rng)
        attn, attn_ax = A.init_gqa(r1, cfg)
        ff, ff_ax = init_mlp(r2, cfg.d_model, cfg.d_ff, cfg.jdtype)
        p = {"attn": attn, "ff": ff,
             "ln1": jnp.zeros((cfg.d_model,), cfg.jdtype),
             "ln2": jnp.zeros((cfg.d_model,), cfg.jdtype)}
        return p, AxTree(attn=attn_ax, ff=ff_ax, ln1=(None,), ln2=(None,))

    def _init_dec_layer(self, rng):
        cfg = self.cfg
        r1, r2, r3 = jax.random.split(rng, 3)
        attn, attn_ax = A.init_gqa(r1, cfg)
        xattn, xattn_ax = A.init_gqa(r2, cfg)
        ff, ff_ax = init_mlp(r3, cfg.d_model, cfg.d_ff, cfg.jdtype)
        p = {"attn": attn, "xattn": xattn, "ff": ff,
             "ln1": jnp.zeros((cfg.d_model,), cfg.jdtype),
             "lnx": jnp.zeros((cfg.d_model,), cfg.jdtype),
             "ln2": jnp.zeros((cfg.d_model,), cfg.jdtype)}
        return p, AxTree(attn=attn_ax, xattn=xattn_ax, ff=ff_ax,
                         ln1=(None,), lnx=(None,), ln2=(None,))

    def init(self, rng) -> Tuple[Params, AxTree]:
        cfg = self.cfg
        r = jax.random.split(rng, 5)
        p: Params = {
            "embed": dense_init(r[0], cfg.vocab_size, cfg.d_model,
                                cfg.jdtype, scale=1.0),
            "pos": 0.01 * jax.random.normal(
                r[1], (self.max_positions, cfg.d_model)).astype(cfg.jdtype),
            "enc_norm": jnp.zeros((cfg.d_model,), cfg.jdtype),
            "final_norm": jnp.zeros((cfg.d_model,), cfg.jdtype),
        }
        ax = AxTree(embed=("vocab", "embed"), pos=(None, "embed"),
                    enc_norm=(None,), final_norm=(None,))
        rngs = jax.random.split(r[2], cfg.encoder.num_layers)
        p["enc_layers"] = jax.vmap(lambda rr: self._init_enc_layer(rr)[0])(rngs)
        _, eax = eval_shape_with_aux(self._init_enc_layer,
                                     jax.random.PRNGKey(0))
        ax["enc_layers"] = _stack_axes(eax)
        rngs = jax.random.split(r[3], cfg.num_layers)
        p["dec_layers"] = jax.vmap(lambda rr: self._init_dec_layer(rr)[0])(rngs)
        _, dax = eval_shape_with_aux(self._init_dec_layer,
                                     jax.random.PRNGKey(0))
        ax["dec_layers"] = _stack_axes(dax)
        return p, ax

    def param_specs(self):
        return eval_shape_with_aux(lambda rr: self.init(rr),
                                   jax.random.PRNGKey(0))

    # ---------------- encoder ----------------
    def encode(self, p: Params, frames: jax.Array):
        """frames: (B, F, d) stub embeddings -> (B, F, d)."""
        cfg = self.cfg
        B, F, d = frames.shape
        x = frames.astype(cfg.jdtype) + sinusoidal_positions(F, d).astype(
            cfg.jdtype)[None]
        x = constrain(x, "batch", None, None)
        positions = jnp.arange(F)[None, :]

        def body(x, lp):
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps, gemma_style=True)
            y = A.gqa_fwd(lp["attn"], h, cfg, causal=False,
                          positions=positions, q_chunk=min(1024, F))
            x = constrain(x + y, "batch", "seq", None)
            h = rmsnorm(x, lp["ln2"], cfg.norm_eps, gemma_style=True)
            return constrain(x + mlp(h, lp["ff"], cfg.mlp),
                             "batch", "seq", None), None

        x, _ = jax.lax.scan(body, x, p["enc_layers"])
        return rmsnorm(x, p["enc_norm"], cfg.norm_eps, gemma_style=True)

    # ---------------- decoder (train / prefill) ----------------
    def forward_hidden(self, p: Params, batch: Dict[str, jax.Array], *,
                       remat: bool = False, collect_kv: bool = False, **_):
        cfg = self.cfg
        enc = self.encode(p, batch["frames"])
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = p["embed"][tokens] + p["pos"][:S][None]
        x = constrain(x, "batch", None, None)
        positions = jnp.arange(S)[None, :]
        enc_pos = jnp.arange(enc.shape[1])[None, :]

        def body(x, lp):
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps, gemma_style=True)
            y, kv = A.gqa_fwd_kv(lp["attn"], h, cfg, window=None,
                                 positions=positions,
                                 q_chunk=min(1024, S))
            x = constrain(x + y, "batch", "seq", None)
            # cross attention (not causal): q from x, kv from encoder
            h = rmsnorm(x, lp["lnx"], cfg.norm_eps, gemma_style=True)
            qx, kx, vx = A._gqa_qkv(lp["xattn"], h, cfg, positions)
            _, ke, ve = A._gqa_qkv(lp["xattn"], enc, cfg, enc_pos)
            o = flash_attention(qx, ke, ve, causal=False,
                                scale=cfg.query_scale,
                                q_chunk=min(1024, S))
            y = o.reshape(B, S, -1) @ lp["xattn"]["wo"]
            x = constrain(x + y, "batch", "seq", None)
            h = rmsnorm(x, lp["ln2"], cfg.norm_eps, gemma_style=True)
            x = constrain(x + mlp(h, lp["ff"], cfg.mlp), "batch", "seq", None)
            return x, (kv, (ke, ve))

        body_fn = jax.checkpoint(body) if remat else body
        x, kv_stack = jax.lax.scan(body_fn, x, p["dec_layers"])
        return x, jnp.zeros((), jnp.float32), kv_stack

    def forward(self, p, batch, **kw):
        cfg = self.cfg
        x, aux, kv = self.forward_hidden(p, batch, **kw)
        logits = (rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
                  @ p["embed"].T).astype(jnp.float32)
        return logits, aux, kv

    def loss(self, p, batch, *, remat: bool = False, **_):
        cfg = self.cfg
        x, _, _ = self.forward_hidden(p, batch, remat=remat)
        xn = rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
        nll, cnt = chunked_lm_loss(xn, p["embed"].T, batch["targets"])
        loss = nll / jnp.maximum(cnt, 1.0)
        return loss, {"nll": loss}

    # ---------------- serving ----------------
    def kv_config(self, max_seq: int, num_blocks: Optional[int] = None,
                  batch: int = 1, dp_groups: int = 1) -> PagedKVConfig:
        cfg = self.cfg
        bt = cfg.kv_block_tokens
        mbs = (max_seq + bt - 1) // bt
        return PagedKVConfig(
            num_layers=cfg.num_layers, kv_heads=cfg.kv_heads, head_dim=cfg.hd,
            block_tokens=bt, num_blocks=num_blocks or mbs * batch,
            max_blocks_per_seq=mbs, dtype=jnp.dtype(cfg.dtype),
            dp_groups=dp_groups)

    def init_state(self, batch: int, max_seq: int,
                   num_blocks: Optional[int] = None,
                   dp_groups: int = 1) -> WhisperState:
        cfg = self.cfg
        F = cfg.encoder.num_frames
        kv = PagedKVCache.create(
            self.kv_config(max_seq, num_blocks, batch, dp_groups), batch)
        z = jnp.zeros((cfg.num_layers, batch, F, cfg.kv_heads, cfg.hd),
                      cfg.jdtype)
        return WhisperState(kv, z, z)

    def prefill(self, p, batch, state: WhisperState, lengths):
        logits, _, kv_stack = self.forward(p, batch, collect_kv=True)
        (k_self, v_self), (ke, ve) = kv_stack
        kv = state.self_kv.write_prefill(k_self, v_self, lengths)
        idx = jnp.maximum(lengths - 1, 0)
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        return last, WhisperState(kv, ke, ve)

    def decode_step(self, p: Params, tokens: jax.Array,
                    state: WhisperState):
        cfg = self.cfg
        cache = state.self_kv
        tables, lens = cache.block_tables, cache.seq_lens
        bt = cache.config.block_tokens
        dp = cache.config.dp_groups
        B = tokens.shape[0]
        x = p["embed"][tokens] + p["pos"][lens]
        F = state.cross_k.shape[2]
        enc_pos_dummy = lens[:, None]  # rope disabled (theta=0)

        def body(x, xs):
            lp, kp, vp, ck, cv = xs
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps, gemma_style=True)
            y, (k_new, v_new) = A.gqa_decode(lp["attn"], h, cfg, kp, vp,
                                             tables, lens, dp_groups=dp)
            kp = write_token_paged(kp, k_new, tables, lens, bt, dp)
            vp = write_token_paged(vp, v_new, tables, lens, bt, dp)
            x = x + y
            # cross attention over static encoder KV
            h = rmsnorm(x, lp["lnx"], cfg.norm_eps, gemma_style=True)
            qx, _, _ = A._gqa_qkv(lp["xattn"], h[:, None], cfg,
                                  enc_pos_dummy)
            o = flash_attention(qx, ck, cv, causal=False,
                                scale=cfg.query_scale, q_chunk=1)
            x = x + (o.reshape(B, -1) @ lp["xattn"]["wo"])
            h = rmsnorm(x, lp["ln2"], cfg.norm_eps, gemma_style=True)
            x = x + mlp(h, lp["ff"], cfg.mlp)
            return x, (kp, vp)

        x, (kps, vps) = jax.lax.scan(
            body, x, (p["dec_layers"], cache.k_pool, cache.v_pool,
                      state.cross_k, state.cross_v))
        cache = dataclasses.replace(cache, k_pool=kps, v_pool=vps,
                                    seq_lens=lens + 1)
        logits = (rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
                  @ p["embed"].T).astype(jnp.float32)
        return logits, WhisperState(cache, state.cross_k, state.cross_v)
