"""Zamba2 hybrid: Mamba2 backbone + one SHARED attention/MLP block applied
every ``shared_attn_every`` layers with per-invocation LoRA adapters.

Structure: ``num_layers`` Mamba2 layers in G = L / every groups; after
each group the shared transformer block runs (weights shared across all
G invocations; a small per-group LoRA on wq/wk/wv differentiates them --
the Zamba2 paper's design point: attention quality at ~1/G the weight
memory, which pairs naturally with the paper's block-pool thesis: the
shared block's KV cache is G paged streams in one arena).

Decode state: conv (G,per,B,W-1,cd) + ssd (G,per,B,H,P,N) + a PagedKVCache
with num_layers = G.
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
from repro.models import mamba2 as M2
from repro.models.common import (AxTree, Params, chunked_lm_loss, dense_init,
                                 init_mlp, mlp, rmsnorm)
from repro.models.lm import (_stack_axes, eval_shape_with_aux,
                             write_token_paged)

_NEG = -1e30


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ZambaState:
    conv: jax.Array          # (G, per, B, W-1, conv_dim)
    ssd: jax.Array           # (G, per, B, H, P, N)
    kv: PagedKVCache         # L = G streams

    def tree_flatten(self):
        return (self.conv, self.ssd, self.kv), None

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)


class Zamba2LM:
    def __init__(self, cfg: ModelConfig):
        assert cfg.shared_attn_every > 0
        assert cfg.num_layers % cfg.shared_attn_every == 0
        self.cfg = cfg
        self.groups = cfg.num_layers // cfg.shared_attn_every
        self.per = cfg.shared_attn_every

    def _init_mamba_layer(self, rng):
        cfg = self.cfg
        m, max_ = M2.init_mamba2(rng, cfg)
        p = {"mamba": m, "ln": jnp.zeros((cfg.d_model,), cfg.jdtype)}
        return p, AxTree(mamba=max_, ln=(None,))

    def _init_lora(self, rng):
        cfg = self.cfg
        rk = cfg.shared_attn_lora
        d = cfg.d_model
        r = jax.random.split(rng, 6)
        p = {}
        ax = AxTree()
        for i, nm in enumerate(("q", "k", "v")):
            p[f"{nm}_a"] = dense_init(r[2 * i], d, rk, cfg.jdtype, scale=0.01)
            p[f"{nm}_b"] = jnp.zeros((rk, d), cfg.jdtype)
            ax[f"{nm}_a"] = ("embed", None)
            ax[f"{nm}_b"] = (None, "embed")
        return p, ax

    def init(self, rng) -> Tuple[Params, AxTree]:
        cfg = self.cfg
        r = jax.random.split(rng, 6)
        p: Params = {
            "embed": dense_init(r[0], cfg.vocab_size, cfg.d_model,
                                cfg.jdtype, scale=1.0),
            "final_norm": jnp.zeros((cfg.d_model,), cfg.jdtype),
        }
        ax = AxTree(embed=("vocab", "embed"), final_norm=(None,))
        # mamba stack, grouped (G, per, ...)
        rngs = jax.random.split(r[1], cfg.num_layers)
        flat = jax.vmap(lambda rr: self._init_mamba_layer(rr)[0])(rngs)
        p["mamba_layers"] = jax.tree.map(
            lambda t: t.reshape(self.groups, self.per, *t.shape[1:]), flat)
        _, max_ = eval_shape_with_aux(self._init_mamba_layer,
                                      jax.random.PRNGKey(0))
        ax["mamba_layers"] = jax.tree.map(
            lambda t: ("layers", "layers") + t, max_,
            is_leaf=lambda t: isinstance(t, tuple))
        # shared attention + mlp block (single copy)
        attn, attn_ax = A.init_gqa(r[2], cfg)
        ff, ff_ax = init_mlp(r[3], cfg.d_model, cfg.d_ff, cfg.jdtype)
        p["shared"] = {"attn": attn, "ff": ff,
                       "ln1": jnp.zeros((cfg.d_model,), cfg.jdtype),
                       "ln2": jnp.zeros((cfg.d_model,), cfg.jdtype)}
        ax["shared"] = AxTree(attn=attn_ax, ff=ff_ax, ln1=(None,),
                              ln2=(None,))
        # per-group LoRA on shared qkv
        rngs = jax.random.split(r[4], self.groups)
        p["lora"] = jax.vmap(lambda rr: self._init_lora(rr)[0])(rngs)
        _, lax_ = eval_shape_with_aux(self._init_lora, jax.random.PRNGKey(0))
        ax["lora"] = _stack_axes(lax_)
        return p, ax

    def param_specs(self):
        return eval_shape_with_aux(lambda rr: self.init(rr),
                                   jax.random.PRNGKey(0))

    # ---------------- shared block ----------------
    def _shared_params(self, p, lora):
        """Apply the group's LoRA to the shared attention weights."""
        sp = dict(p["shared"])
        attn = dict(sp["attn"])
        for nm, w in (("q", "wq"), ("k", "wk"), ("v", "wv")):
            attn[w] = attn[w] + lora[f"{nm}_a"] @ lora[f"{nm}_b"]
        sp["attn"] = attn
        return sp

    def _shared_fwd(self, sp, x, positions):
        cfg = self.cfg
        h = rmsnorm(x, sp["ln1"], cfg.norm_eps, gemma_style=True)
        y, kv = A.gqa_fwd_kv(sp["attn"], h, cfg, window=None,
                             positions=positions)
        x = constrain(x + y, "batch", "seq", None)
        h = rmsnorm(x, sp["ln2"], cfg.norm_eps, gemma_style=True)
        x = constrain(x + mlp(h, sp["ff"], cfg.mlp), "batch", "seq", None)
        return x, kv

    # ---------------- forward ----------------
    def forward_hidden(self, p: Params, batch: Dict[str, jax.Array], *,
                       remat: bool = False, state: Optional[ZambaState] = None,
                       collect_kv: bool = False,
                       lengths: Optional[jax.Array] = None, **_):
        cfg = self.cfg
        x = p["embed"][batch["tokens"]]
        x = constrain(x, "batch", None, None)
        B, S, _ = x.shape
        offs = (state.kv.seq_lens if state is not None
                else jnp.zeros((B,), jnp.int32))
        positions = offs[:, None] + jnp.arange(S)[None, :]

        def mamba_body(x, xs):
            if state is None:
                lp = xs
                cs = ss = None
            else:
                lp, cs, ss = xs
            h = rmsnorm(x, lp["ln"], cfg.norm_eps, gemma_style=True)
            # lengths masks right padding out of the SSM scan (padded
            # attention outputs are already causal-safe; the recurrent
            # state is what padding would otherwise pollute)
            y, (cs_o, ss_o) = M2.mamba2_fwd(lp["mamba"], h, cfg, cs, ss,
                                            lengths=lengths)
            return constrain(x + y, "batch", "seq", None), (cs_o, ss_o)

        def group_body(x, xs):
            if state is None:
                glp, lora = xs
                mx = glp
            else:
                glp, lora, cs_g, ss_g = xs
                mx = (glp, cs_g, ss_g)
            x, states = jax.lax.scan(mamba_body, x, mx)
            sp = self._shared_params(p, lora)
            x, kv = self._shared_fwd(sp, x, positions)
            ys = (states, kv) if collect_kv else (states, None)
            return x, ys

        gb = jax.checkpoint(group_body) if remat else group_body
        if state is None:
            xs = (p["mamba_layers"], p["lora"])
        else:
            xs = (p["mamba_layers"], p["lora"], state.conv, state.ssd)
        x, (states, kvs) = jax.lax.scan(gb, x, xs)
        return x, jnp.zeros((), jnp.float32), (states, kvs)

    def forward(self, p, batch, **kw):
        cfg = self.cfg
        x, aux, sk = self.forward_hidden(p, batch, **kw)
        logits = (rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
                  @ p["embed"].T).astype(jnp.float32)
        return logits, aux, sk

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
            num_layers=self.groups, kv_heads=cfg.kv_heads, head_dim=cfg.hd,
            block_tokens=bt, num_blocks=num_blocks or mbs * batch,
            max_blocks_per_seq=mbs, dtype=jnp.dtype(cfg.dtype),
            dp_groups=dp_groups)

    def init_recurrent(self, batch: int):
        """Zero (conv, ssd) recurrent state WITHOUT allocating a KV
        pool -- serving composes these with an externally owned
        ``PagedKVCache`` view (serve/arch.CompositeStrategy)."""
        cfg = self.cfg
        d_inner, H, P, N, W = M2._dims(cfg)
        conv = jnp.zeros((self.groups, self.per, batch, W - 1,
                          d_inner + 2 * N), jnp.float32)
        ssd = jnp.zeros((self.groups, self.per, batch, H, P, N), jnp.float32)
        return conv, ssd

    def init_state(self, batch: int, max_seq: int,
                   num_blocks: Optional[int] = None,
                   dp_groups: int = 1) -> ZambaState:
        conv, ssd = self.init_recurrent(batch)
        kv = PagedKVCache.create(
            self.kv_config(max_seq, num_blocks, batch, dp_groups), batch)
        return ZambaState(conv, ssd, kv)

    def prefill(self, p, batch, state: ZambaState, lengths):
        logits, _, (states, kvs) = self.forward(p, batch, state=state,
                                                collect_kv=True,
                                                lengths=lengths)
        kv = state.kv.write_prefill(kvs[0], kvs[1], lengths)
        idx = jnp.maximum(lengths - 1, 0)
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        return last, ZambaState(states[0], states[1], kv)

    # -- constant-state pool glue (serve/arch.ConstantStateStrategy) --
    @property
    def state_elems(self) -> int:
        """Float32 elements of ONE sequence's recurrent state -- the
        constant-state pool's (exact) block quantum."""
        d_inner, H, P, N, W = M2._dims(self.cfg)
        per_layer = (W - 1) * (d_inner + 2 * N) + H * P * N
        return self.groups * self.per * per_layer

    def state_to_rows(self, conv: jax.Array, ssd: jax.Array) -> jax.Array:
        """Flatten (G, per, B, ...) recurrent state to per-sequence
        (B, state_elems) rows -- one pool block per sequence."""
        B = conv.shape[2]
        c = jnp.moveaxis(conv, 2, 0).reshape(B, -1)
        s = jnp.moveaxis(ssd, 2, 0).reshape(B, -1)
        return jnp.concatenate([c, s], axis=1).astype(jnp.float32)

    def rows_to_state(self, rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Inverse of ``state_to_rows``."""
        d_inner, H, P, N, W = M2._dims(self.cfg)
        G, per = self.groups, self.per
        B = rows.shape[0]
        cd = d_inner + 2 * N
        csize = G * per * (W - 1) * cd
        conv = jnp.moveaxis(
            rows[:, :csize].reshape(B, G, per, W - 1, cd), 0, 2)
        ssd = jnp.moveaxis(
            rows[:, csize:].reshape(B, G, per, H, P, N), 0, 2)
        return conv, ssd

    def decode_step(self, p: Params, tokens: jax.Array, state: ZambaState):
        cfg = self.cfg
        x = p["embed"][tokens]
        cache = state.kv
        tables, lens = cache.block_tables, cache.seq_lens
        bt = cache.config.block_tokens

        def mamba_step_body(x, xs):
            lp, cs, ss = xs
            h = rmsnorm(x, lp["ln"], cfg.norm_eps, gemma_style=True)
            y, (cs, ss) = M2.mamba2_step(lp["mamba"], h, cfg, cs, ss)
            return x + y, (cs, ss)

        dp = cache.config.dp_groups

        def group_body(x, xs):
            glp, lora, cs_g, ss_g, kp, vp = xs
            x, states = jax.lax.scan(mamba_step_body, x, (glp, cs_g, ss_g))
            sp = self._shared_params(p, lora)
            h = rmsnorm(x, sp["ln1"], cfg.norm_eps, gemma_style=True)
            y, (k_new, v_new) = A.gqa_decode(sp["attn"], h, cfg, kp, vp,
                                             tables, lens, dp_groups=dp)
            kp = write_token_paged(kp, k_new, tables, lens, bt, dp)
            vp = write_token_paged(vp, v_new, tables, lens, bt, dp)
            x = x + y
            h = rmsnorm(x, sp["ln2"], cfg.norm_eps, gemma_style=True)
            x = x + mlp(h, sp["ff"], cfg.mlp)
            return x, (states, kp, vp)

        x, (states, kps, vps) = jax.lax.scan(
            group_body, x, (p["mamba_layers"], p["lora"], state.conv,
                            state.ssd, cache.k_pool, cache.v_pool))
        cache = dataclasses.replace(cache, k_pool=kps, v_pool=vps,
                                    seq_lens=cache.seq_lens + 1)
        logits = (rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
                  @ p["embed"].T).astype(jnp.float32)
        return logits, ZambaState(states[0], states[1], cache)
