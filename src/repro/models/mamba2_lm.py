"""Pure Mamba2 (SSD) language model: stacked mamba2 blocks, no attention.

Decode state is O(1) per layer -- a conv window plus the SSD matrix
state -- so serving uses the constant-state pool discipline (one
fixed-size Arena block per sequence, zero growth) instead of paged KV:
the `ConstantStateStrategy` in ``serve/arch.py``.  Prefill masks right
padding exactly (``mamba2_fwd(lengths=...)``), so a padded batched
prefill is token-identical to per-sequence prefill.

Where its widths allow, one sequence's state block is laid out in
128-lane rows (``state_block``): the conv windows of every layer, then
each layer's SSD state as a run of whole rows, so the served decode
(``decode_step_in_pool``) reads and writes each layer's state in place
inside the layer scan instead of relaying whole rows in and out.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.launch.shardings import constrain
from repro.models import mamba2 as M2
from repro.models.common import (AxTree, Params, chunked_lm_loss,
                                 dense_init, rmsnorm)
from repro.models.lm import _stack_axes, eval_shape_with_aux

LANES = 128                   # lanes of one row of a layered state block
TILE = 8 * LANES              # elements of one (8, 128) tile


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Mamba2State:
    """Decode state: (L,B,W-1,cd) conv windows + (L,B,H,P,N) SSD state."""
    conv: jax.Array
    ssd: jax.Array

    def tree_flatten(self):
        return (self.conv, self.ssd), None

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)


class Mamba2LM:
    def __init__(self, cfg: ModelConfig):
        assert cfg.ssm is not None and cfg.ssm.kind == "mamba2"
        self.cfg = cfg

    def _init_layer(self, rng):
        cfg = self.cfg
        m, max_ = M2.init_mamba2(rng, cfg)
        p = {"mamba": m, "ln": jnp.zeros((cfg.d_model,), cfg.jdtype)}
        return p, AxTree(mamba=max_, ln=(None,))

    def init(self, rng) -> Tuple[Params, AxTree]:
        cfg = self.cfg
        r = jax.random.split(rng, 3)
        p: Params = {
            "embed": dense_init(r[0], cfg.vocab_size, cfg.d_model,
                                cfg.jdtype, scale=1.0),
            "final_norm": jnp.zeros((cfg.d_model,), cfg.jdtype),
        }
        ax = AxTree(embed=("vocab", "embed"), final_norm=(None,))
        rngs = jax.random.split(r[1], cfg.num_layers)
        p["layers"] = jax.vmap(lambda rr: self._init_layer(rr)[0])(rngs)
        _, lax_ = eval_shape_with_aux(self._init_layer, jax.random.PRNGKey(0))
        ax["layers"] = _stack_axes(lax_)
        return p, ax

    def param_specs(self):
        return eval_shape_with_aux(lambda rr: self.init(rr),
                                   jax.random.PRNGKey(0))

    def forward_hidden(self, p: Params, batch: Dict[str, jax.Array], *,
                       remat: bool = False,
                       state: Optional[Mamba2State] = None,
                       lengths: Optional[jax.Array] = None, **_):
        cfg = self.cfg
        x = p["embed"][batch["tokens"]]
        x = constrain(x, "batch", None, None)

        def body(x, xs):
            if state is None:
                lp = xs
                cs = ss = None
            else:
                lp, cs, ss = xs
            h = rmsnorm(x, lp["ln"], cfg.norm_eps, gemma_style=True)
            y, (cs_o, ss_o) = M2.mamba2_fwd(lp["mamba"], h, cfg, cs, ss,
                                            lengths=lengths)
            return constrain(x + y, "batch", "seq", None), (cs_o, ss_o)

        body_fn = jax.checkpoint(body) if remat else body
        xs = (p["layers"] if state is None
              else (p["layers"], state.conv, state.ssd))
        x, (conv, ssd) = jax.lax.scan(body_fn, x, xs)
        return x, jnp.zeros((), jnp.float32), Mamba2State(conv, ssd)

    def forward(self, p, batch, **kw):
        cfg = self.cfg
        x, aux, state = self.forward_hidden(p, batch, **kw)
        logits = (rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
                  @ p["embed"].T).astype(jnp.float32)
        return logits, aux, state

    def loss(self, p, batch, *, remat: bool = False, **_):
        cfg = self.cfg
        x, _, _ = self.forward_hidden(p, batch, remat=remat)
        xn = rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
        nll, cnt = chunked_lm_loss(xn, p["embed"].T, batch["targets"])
        loss = nll / jnp.maximum(cnt, 1.0)
        return loss, {"nll": loss}

    # ---------------- serving ----------------
    def init_state(self, batch: int) -> Mamba2State:
        cfg = self.cfg
        d_inner, H, P, N, W = M2._dims(cfg)
        L = cfg.num_layers
        return Mamba2State(
            jnp.zeros((L, batch, W - 1, d_inner + 2 * N), jnp.float32),
            jnp.zeros((L, batch, H, P, N), jnp.float32))

    def prefill(self, p, batch, state: Mamba2State, lengths):
        logits, _, states = self.forward(p, batch, state=state,
                                         lengths=lengths)
        idx = jnp.maximum(lengths - 1, 0)
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        return last, states

    def _layer_step(self, lp, x, cs, ss):
        h = rmsnorm(x, lp["ln"], self.cfg.norm_eps, gemma_style=True)
        y, (cs, ss) = M2.mamba2_step(lp["mamba"], h, self.cfg, cs, ss)
        return x + y, cs, ss

    def _logits(self, p, x):
        cfg = self.cfg
        return (rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
                @ p["embed"].T).astype(jnp.float32)

    def decode_step(self, p: Params, tokens: jax.Array, state: Mamba2State):
        def body(x, xs):
            lp, cs, ss = xs
            x, cs, ss = self._layer_step(lp, x, cs, ss)
            return x, (cs, ss)

        x, (conv, ssd) = jax.lax.scan(
            body, p["embed"][tokens], (p["layers"], state.conv, state.ssd))
        return self._logits(p, x), Mamba2State(conv, ssd)

    def decode_step_in_pool(self, p: Params, tokens: jax.Array,
                            pool: jax.Array, rows: jax.Array):
        """``decode_step`` on the states held in blocks ``rows`` of a
        ``(NB, *state_block)`` pool, written back in place -> (logits,
        pool).  The conv windows are gathered once and written back after
        the layer scan; inside the scan, which carries the pool, each
        layer's SSD state is read with one DMA a row (``ops.gather_rows``),
        stepped, and written back the same way (``ops.scatter_rows``), so
        no op outputs a pool-sized copy.  Rows may repeat (slots padded to
        one sink block): every read of a piece precedes its writes."""
        cfg = self.cfg
        d_inner, H, P, N, W = M2._dims(cfg)
        L, B = cfg.num_layers, rows.shape[0]
        cd = d_inner + 2 * N
        conv_rows = L * (W - 1) * cd // LANES
        ssd_rows = H * P * N // LANES
        with jax.named_scope("state_gather"):
            conv = pool[rows, :conv_rows].reshape(B, L, W - 1, cd)
            conv = jnp.moveaxis(conv, 1, 0)

        def body(carry, xs):
            x, pool = carry
            lp, cs, layer = xs
            start = conv_rows + layer * ssd_rows
            ss = ops.gather_rows(pool, rows, start, ssd_rows)
            x, cs, ss = self._layer_step(lp, x, cs, ss.reshape(B, H, P, N))
            pool = ops.scatter_rows(pool, rows, start,
                                    ss.reshape(B, ssd_rows, LANES))
            return (x, pool), cs

        with jax.named_scope("layer_scan"):
            (x, pool), conv = jax.lax.scan(
                body, (p["embed"][tokens], pool),
                (p["layers"], conv, jnp.arange(L, dtype=jnp.int32)))
        with jax.named_scope("state_writeback"):
            pool = pool.at[rows, :conv_rows].set(
                jnp.moveaxis(conv, 0, 1).reshape(B, conv_rows, LANES))
        return self._logits(p, x), pool

    # -- constant-state pool glue (serve/arch.ConstantStateStrategy) --
    @property
    def state_elems(self) -> int:
        """Float32 elements of ONE sequence's recurrent state -- the
        constant-state pool's (exact) block quantum."""
        d_inner, H, P, N, W = M2._dims(self.cfg)
        L = self.cfg.num_layers
        return L * ((W - 1) * (d_inner + 2 * N) + H * P * N)

    @property
    def state_block(self) -> Optional[Tuple[int, int]]:
        """``(rows, LANES)``: one state block in 128-lane rows, in
        ``state_to_rows``'s element order -- the conv windows of every
        layer, then layer l's SSD state as ``H * P * N / LANES`` rows.
        None unless the conv windows and each layer's SSD state fill whole
        (8, 128) tiles, so that each is one contiguous run of tiles that a
        DMA moves as one piece: the pool then stays flat."""
        d_inner, H, P, N, W = M2._dims(self.cfg)
        conv = self.cfg.num_layers * (W - 1) * (d_inner + 2 * N)
        if conv % TILE or (H * P * N) % TILE:
            return None
        return self.state_elems // LANES, LANES

    def state_to_rows(self, state: Mamba2State) -> jax.Array:
        """Flatten the (L, B, ...) state to (B, state_elems) rows."""
        B = state.conv.shape[1]
        c = jnp.moveaxis(state.conv, 1, 0).reshape(B, -1)
        s = jnp.moveaxis(state.ssd, 1, 0).reshape(B, -1)
        return jnp.concatenate([c, s], axis=1).astype(jnp.float32)

    def state_to_blocks(self, state: Mamba2State) -> jax.Array:
        """``state_to_rows`` laid out as (B, *state_block) blocks, built
        in rows with no flat (B, state_elems) array between."""
        B = state.conv.shape[1]
        c = jnp.moveaxis(state.conv, 1, 0).reshape(B, -1, LANES)
        s = jnp.moveaxis(state.ssd, 1, 0).reshape(B, -1, LANES)
        return jnp.concatenate([c, s], axis=1).astype(jnp.float32)

    def rows_to_state(self, rows: jax.Array) -> Mamba2State:
        """Inverse of ``state_to_rows``."""
        d_inner, H, P, N, W = M2._dims(self.cfg)
        L = self.cfg.num_layers
        B = rows.shape[0]
        cd = d_inner + 2 * N
        csize = L * (W - 1) * cd
        conv = jnp.moveaxis(rows[:, :csize].reshape(B, L, W - 1, cd), 0, 1)
        ssd = jnp.moveaxis(rows[:, csize:].reshape(B, L, H, P, N), 0, 1)
        return Mamba2State(conv, ssd)
