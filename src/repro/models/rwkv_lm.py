"""RWKV6 language model: stacked (time-mix + channel-mix) blocks.

Attention-free: decode state is O(1) per layer (matrix state + two
token-shift vectors), so the paged-KV machinery is inapplicable by
design (DESIGN.md §5) -- long_500k runs here precisely because of that.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.shardings import constrain
from repro.models import rwkv6 as R
from repro.models.common import (AxTree, Params, chunked_lm_loss,
                                 dense_init, rmsnorm)
from repro.models.lm import _stack_axes, eval_shape_with_aux


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RWKVState:
    """Decode state: (L,B,d) shift vectors + (L,B,H,dk,dk) wkv state."""
    mix_x: jax.Array
    ffn_x: jax.Array
    wkv: jax.Array

    def tree_flatten(self):
        return (self.mix_x, self.ffn_x, self.wkv), None

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)


class RWKVLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def _init_layer(self, rng):
        cfg = self.cfg
        r1, r2 = jax.random.split(rng)
        mix, mix_ax = R.init_rwkv6_mix(r1, cfg)
        ffn, ffn_ax = R.init_rwkv6_ffn(r2, cfg)
        p = {"mix": mix, "ffn": ffn,
             "ln1": jnp.zeros((cfg.d_model,), cfg.jdtype),
             "ln2": jnp.zeros((cfg.d_model,), cfg.jdtype)}
        ax = AxTree(mix=mix_ax, ffn=ffn_ax, ln1=(None,), ln2=(None,))
        return p, ax

    def init(self, rng) -> Tuple[Params, AxTree]:
        cfg = self.cfg
        r = jax.random.split(rng, 3)
        p: Params = {
            "embed": dense_init(r[0], cfg.vocab_size, cfg.d_model,
                                cfg.jdtype, scale=1.0),
            "ln_in": jnp.zeros((cfg.d_model,), cfg.jdtype),
            "final_norm": jnp.zeros((cfg.d_model,), cfg.jdtype),
            "lm_head": dense_init(r[1], cfg.d_model, cfg.vocab_size,
                                  cfg.jdtype),
        }
        ax = AxTree(embed=("vocab", "embed"), ln_in=(None,),
                    final_norm=(None,), lm_head=("embed", "vocab"))
        rngs = jax.random.split(r[2], cfg.num_layers)
        p["layers"] = jax.vmap(lambda rr: self._init_layer(rr)[0])(rngs)
        _, lax_ = eval_shape_with_aux(self._init_layer, jax.random.PRNGKey(0))
        ax["layers"] = _stack_axes(lax_)
        return p, ax

    def param_specs(self):
        return eval_shape_with_aux(lambda rr: self.init(rr),
                                   jax.random.PRNGKey(0))

    def _layer(self, lp, x, state=None, lengths=None):
        """state: None (train from zeros) or (mix_x, ffn_x, wkv);
        ``lengths`` masks right padding out of the recurrence and picks
        the shift vectors at each row's true last position."""
        cfg = self.cfg
        mix_x = state.mix_x if state else None
        wkv = state.wkv if state else None
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps, gemma_style=True)
        y, (last_x, wkv_out) = R.rwkv6_mix_fwd(lp["mix"], h, cfg,
                                               prev_x=mix_x, state_in=wkv,
                                               lengths=lengths)
        x = constrain(x + y, "batch", "seq", None)
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps, gemma_style=True)
        prev = (state.ffn_x[:, None] if state
                else jnp.zeros_like(h[:, :1]))
        hh = jnp.concatenate([prev, h[:, :-1]], axis=1)
        x = constrain(x + R.rwkv6_ffn(lp["ffn"], h, hh), "batch", "seq", None)
        if lengths is not None:
            idx = (lengths.astype(jnp.int32) - 1)[:, None, None]
            last_h = jnp.take_along_axis(h, idx, axis=1)[:, 0]
        else:
            last_h = h[:, -1]
        new_state = RWKVState(last_x, last_h, wkv_out)
        return x, new_state

    def forward_hidden(self, p: Params, batch: Dict[str, jax.Array], *,
                       remat: bool = False, state: "RWKVState" = None,
                       lengths=None, **_):
        cfg = self.cfg
        x = rmsnorm(p["embed"][batch["tokens"]], p["ln_in"], cfg.norm_eps,
                    gemma_style=True)
        x = constrain(x, "batch", None, None)

        def body(x, xs):
            if state is None:
                lp = xs
                st = None
            else:
                lp, st = xs
            x, new_st = self._layer(lp, x, st, lengths=lengths)
            return x, new_st

        body_fn = jax.checkpoint(body) if remat else body
        xs = p["layers"] if state is None else (p["layers"], state)
        x, states = jax.lax.scan(body_fn, x, xs)
        return x, jnp.zeros((), jnp.float32), states

    def forward(self, p, batch, **kw):
        x, aux, states = self.forward_hidden(p, batch, **kw)
        cfg = self.cfg
        logits = (rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
                  @ p["lm_head"]).astype(jnp.float32)
        return logits, aux, states

    def loss(self, p, batch, *, remat: bool = False, **_):
        cfg = self.cfg
        x, _, _ = self.forward_hidden(p, batch, remat=remat)
        xn = rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
        nll, cnt = chunked_lm_loss(xn, p["lm_head"], batch["targets"])
        loss = nll / jnp.maximum(cnt, 1.0)
        return loss, {"nll": loss}

    # ---------------- serving ----------------
    def init_state(self, batch: int) -> RWKVState:
        cfg = self.cfg
        L, d, H = cfg.num_layers, cfg.d_model, cfg.num_heads
        dk = d // H
        return RWKVState(jnp.zeros((L, batch, d), cfg.jdtype),
                         jnp.zeros((L, batch, d), cfg.jdtype),
                         jnp.zeros((L, batch, H, dk, dk), jnp.float32))

    # -- constant-state pool glue (ConstantStateStrategy surface) --
    @property
    def state_elems(self) -> int:
        """Float32 elements of ONE sequence's decode state -- the
        constant-state pool's (exact) block quantum: two shift vectors
        and the per-head wkv matrix state, per layer."""
        cfg = self.cfg
        d, H = cfg.d_model, cfg.num_heads
        dk = d // H
        return cfg.num_layers * (2 * d + H * dk * dk)

    def state_to_rows(self, state: RWKVState) -> jax.Array:
        """Flatten the (L, B, ...) state to (B, state_elems) rows."""
        B = state.mix_x.shape[1]
        m = jnp.moveaxis(state.mix_x, 1, 0).reshape(B, -1)
        f = jnp.moveaxis(state.ffn_x, 1, 0).reshape(B, -1)
        w = jnp.moveaxis(state.wkv, 1, 0).reshape(B, -1)
        return jnp.concatenate([m, f, w], axis=1).astype(jnp.float32)

    def rows_to_state(self, rows: jax.Array) -> RWKVState:
        """Inverse of ``state_to_rows`` (shift vectors back in the
        compute dtype; the wkv state stays float32)."""
        cfg = self.cfg
        L, d, H = cfg.num_layers, cfg.d_model, cfg.num_heads
        dk = d // H
        B = rows.shape[0]
        m = jnp.moveaxis(rows[:, : L * d].reshape(B, L, d), 0, 1
                         ).astype(cfg.jdtype)
        f = jnp.moveaxis(rows[:, L * d: 2 * L * d].reshape(B, L, d), 0, 1
                         ).astype(cfg.jdtype)
        w = jnp.moveaxis(rows[:, 2 * L * d:].reshape(B, L, H, dk, dk), 0, 1)
        return RWKVState(m, f, w)

    def prefill(self, p, batch, state: RWKVState, lengths=None):
        logits, _, states = self.forward(p, batch, state=state,
                                         lengths=lengths)
        if lengths is None:
            return logits[:, -1], states
        idx = (lengths.astype(jnp.int32) - 1)[:, None, None]
        return jnp.take_along_axis(logits, idx, axis=1)[:, 0], states

    def decode_step(self, p: Params, tokens: jax.Array, state: RWKVState):
        cfg = self.cfg
        x = rmsnorm(p["embed"][tokens], p["ln_in"], cfg.norm_eps,
                    gemma_style=True)

        def body(x, xs):
            lp, mix_x, ffn_x, wkv = xs
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps, gemma_style=True)
            y, (last_x, wkv_new) = R.rwkv6_mix_step(lp["mix"], h, cfg,
                                                    mix_x, wkv)
            x = x + y
            h = rmsnorm(x, lp["ln2"], cfg.norm_eps, gemma_style=True)
            x = x + R.rwkv6_ffn(lp["ffn"], h, ffn_x)
            return x, (last_x, h, wkv_new)

        x, (mix_x, ffn_x, wkv) = jax.lax.scan(
            body, x, (p["layers"], state.mix_x, state.ffn_x, state.wkv))
        logits = (rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
                  @ p["lm_head"]).astype(jnp.float32)
        return logits, RWKVState(mix_x, ffn_x, wkv)
