"""Generic decoder-only LM covering qwen3-moe, deepseek-v2-lite, minicpm3,
gemma2/3, gemma-2b and internvl2 (LM backbone + stub image embeddings).

Layers are stacked and consumed by ``lax.scan`` (compile time flat in
depth).  Per-layer heterogeneity (local/global window, rope theta, moe
vs dense) rides along as scanned metadata arrays; MoE models with leading
dense layers run those outside the main scan.

Decode threads the PagedKVCache's per-layer pool slices through the scan
(xs in; updated slices out as ys, or for a packed latent pool only the
new latents, written in place after the scan) -- one block-table lookup
schedule shared by all layers, which is the paper's
single-arena/many-tenants design.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.paged_kv import (PagedKVCache, PagedKVConfig,
                                  pool_wide_ids, write_packed_tokens)
from repro.launch.shardings import constrain
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models.moe_sharded import moe_ffn_dispatch
from repro.models.common import (AxTree, Params, chunked_lm_loss, dense_init,
                                 init_mlp, jit_init, mlp, rmsnorm, stacked)

_NEG = -1e30


def write_token_paged(pool_l, kv_new, tables, seq_lens, bt,
                      dp_groups: int = 1):
    """Scatter one token's KV into the pool at each sequence's current
    position.  Group-batched when dp_groups > 1 (see PagedKVConfig)."""
    B = tables.shape[0]
    phys = tables[jnp.arange(B), seq_lens // bt]
    off = seq_lens % bt
    val = kv_new.astype(pool_l.dtype)
    if dp_groups <= 1:
        return pool_l.at[phys, off].set(val)
    NBl = pool_l.shape[0] // dp_groups
    Bl = B // dp_groups
    pg = pool_l.reshape(dp_groups, NBl, *pool_l.shape[1:])
    out = jax.vmap(lambda pl, ph, of, vv: pl.at[ph, of].set(vv))(
        pg, phys.reshape(dp_groups, Bl), off.reshape(dp_groups, Bl),
        val.reshape(dp_groups, Bl, *val.shape[1:]))
    return out.reshape(pool_l.shape)


def _stack_axes(ax):
    return jax.tree.map(lambda t: ("layers",) + t, ax,
                        is_leaf=lambda t: isinstance(t, tuple))


def eval_shape_with_aux(fn, *args):
    """eval_shape for a function returning (params, aux) where aux is a
    non-JAX pytree (logical-axis tuples): returns (shapes, aux)."""
    cell = {}

    def wrapped(*a):
        p, ax = fn(*a)
        cell["ax"] = ax
        return p

    shapes = jax.eval_shape(wrapped, *args)
    return shapes, cell["ax"]


class DecoderLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.is_moe = cfg.moe is not None
        self.n_dense = cfg.moe.first_dense_layers if self.is_moe else 0
        self.n_scan = cfg.num_layers - self.n_dense
        # jitted serving callables (decode_step / prefill / prefill_suffix).
        # jax.jit's signature cache keys the traces by input shape, i.e.
        # by (batch, padded seq, table width) bucket; the engine pads its
        # batches so steady-state steps always hit a warm trace.
        self._jit_cache: Dict[str, Any] = {}

    # ---------------- params ----------------
    def _init_layer(self, rng, moe_layer: bool):
        cfg = self.cfg
        r1, r2 = jax.random.split(rng)
        if cfg.attention == "mla":
            attn, attn_ax = A.init_mla(r1, cfg)
        else:
            attn, attn_ax = A.init_gqa(r1, cfg)
        if moe_layer:
            ff, ff_ax = MOE.init_moe(r2, cfg)
        else:
            ff, ff_ax = init_mlp(r2, cfg.d_model, cfg.d_ff, cfg.jdtype)
        p = {"attn": attn, "ff": ff,
             "ln1": jnp.zeros((cfg.d_model,), cfg.jdtype),
             "ln2": jnp.zeros((cfg.d_model,), cfg.jdtype)}
        ax = AxTree(attn=attn_ax, ff=ff_ax, ln1=(None,), ln2=(None,))
        if cfg.post_norms:
            p["ln1_post"] = jnp.zeros((cfg.d_model,), cfg.jdtype)
            p["ln2_post"] = jnp.zeros((cfg.d_model,), cfg.jdtype)
            ax["ln1_post"] = (None,)
            ax["ln2_post"] = (None,)
        return p, ax

    def init(self, rng) -> Tuple[Params, AxTree]:
        return jit_init(self._init_params, rng)

    def _init_params(self, rng) -> Tuple[Params, AxTree]:
        cfg = self.cfg
        r = jax.random.split(rng, 5)
        p: Params = {"embed": dense_init(r[0], cfg.vocab_size, cfg.d_model,
                                         cfg.jdtype, scale=1.0),
                     "final_norm": jnp.zeros((cfg.d_model,), cfg.jdtype)}
        ax = AxTree(embed=("vocab", "embed"), final_norm=(None,))
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(r[1], cfg.d_model, cfg.vocab_size,
                                      cfg.jdtype)
            ax["lm_head"] = ("embed", "vocab")
        rngs = jax.random.split(r[2], self.n_scan)
        p["layers"] = jax.vmap(
            lambda rr: self._init_layer(rr, self.is_moe)[0])(rngs)
        _, layer_ax = eval_shape_with_aux(
            lambda rr: self._init_layer(rr, self.is_moe),
            jax.random.PRNGKey(0))
        ax["layers"] = _stack_axes(layer_ax)
        if self.n_dense:
            rngs = jax.random.split(r[3], self.n_dense)
            p["dense_layers"] = jax.vmap(
                lambda rr: self._init_layer(rr, False)[0])(rngs)
            _, dax = eval_shape_with_aux(
                lambda rr: self._init_layer(rr, False), jax.random.PRNGKey(0))
            ax["dense_layers"] = _stack_axes(dax)
        if cfg.num_image_tokens:
            p["img_proj"] = dense_init(r[4], cfg.d_model, cfg.d_model,
                                       cfg.jdtype)
            ax["img_proj"] = ("embed", "embed")
        return p, ax

    def param_specs(self):
        """(ShapeDtypeStruct tree, axes tree) without allocating."""
        return eval_shape_with_aux(
            lambda rr: self.init(rr), jax.random.PRNGKey(0))

    # ---------------- per-layer metadata ----------------
    def _layer_meta(self, which: str):
        """Scanned metadata arrays for layers [n_dense:)."""
        cfg = self.cfg
        idxs = range(self.n_dense, cfg.num_layers)
        windows = jnp.asarray(
            [(cfg.local_window if cfg.layer_is_local(i) else 0) or 0
             for i in idxs], jnp.int32)
        thetas = jnp.asarray(
            [(cfg.rope_theta_local if (cfg.layer_is_local(i) and
                                       cfg.rope_theta_local) else
              cfg.rope_theta) for i in idxs], jnp.float32)
        return windows, thetas

    # ---------------- embedding / head ----------------
    def _embed_tokens(self, p, tokens):
        cfg = self.cfg
        x = p["embed"][tokens]
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if cfg.scale_emb != 1.0:
            x = x * jnp.asarray(cfg.scale_emb, x.dtype)
        return x

    def _embed(self, p, batch):
        cfg = self.cfg
        x = self._embed_tokens(p, batch["tokens"])
        if cfg.num_image_tokens:
            img = batch["image_embeds"].astype(x.dtype) @ p["img_proj"]
            x = jnp.concatenate([img, x], axis=1)
        return x

    def _final_norm(self, p, x):
        """The head's input: the final norm, then MiniCPM's width
        multiplier ``dim_model_base / d_model`` where one is set."""
        cfg = self.cfg
        x = rmsnorm(x, p["final_norm"], cfg.norm_eps, gemma_style=True)
        if cfg.dim_model_base:
            x = x * jnp.asarray(cfg.dim_model_base / cfg.d_model, x.dtype)
        return x

    def _residual(self, x, y):
        """x + y, with MiniCPM's depth multiplier ``scale_depth /
        sqrt(num_layers)`` on the branch where one is set."""
        cfg = self.cfg
        if cfg.scale_depth:
            y = y * jnp.asarray(cfg.scale_depth / cfg.num_layers ** 0.5,
                                y.dtype)
        return x + y

    def _head(self, p, x):
        cfg = self.cfg
        w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
        logits = self._final_norm(p, x) @ w
        logits = logits.astype(jnp.float32)
        if cfg.final_softcap is not None:
            logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
        return logits

    # ---------------- layer body (training) ----------------
    def _layer_fwd(self, lp, x, positions, window, theta, moe_layer: bool,
                   q_chunk: int, collect_kv: bool):
        cfg = self.cfg
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps, gemma_style=True)
        if cfg.attention == "mla":
            y, latent = A.mla_fwd_kv(lp["attn"], h, cfg, positions=positions,
                                     q_chunk=q_chunk)
            kv = (latent, None)       # uniform (k-like, v-like) tuple
        else:
            y, kv = A.gqa_fwd_kv(lp["attn"], h, cfg, window=window,
                                 positions=positions, q_chunk=q_chunk,
                                 rope_theta=theta)
        if cfg.post_norms:
            y = rmsnorm(y, lp["ln1_post"], cfg.norm_eps, gemma_style=True)
        x = self._residual(x, y)
        x = constrain(x, "batch", "seq", None)
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps, gemma_style=True)
        aux = jnp.zeros((), jnp.float32)
        if moe_layer:
            y, aux = moe_ffn_dispatch(lp["ff"], h, cfg)
        else:
            y = mlp(h, lp["ff"], cfg.mlp)
        if cfg.post_norms:
            y = rmsnorm(y, lp["ln2_post"], cfg.norm_eps, gemma_style=True)
        x = self._residual(x, y)
        x = constrain(x, "batch", "seq", None)
        return x, aux, (kv if collect_kv else None)

    # ---------------- forward (train / prefill) ----------------
    def forward_hidden(self, p: Params, batch: Dict[str, jax.Array], *,
                       q_chunk: int = 1024, remat: bool = False,
                       collect_kv: bool = False):
        """Returns (final hidden x, aux_loss, kv_stack or None)."""
        cfg = self.cfg
        x = self._embed(p, batch)
        B, S, _ = x.shape
        positions = jnp.arange(S)[None, :]
        x = constrain(x, "batch", None, None)

        aux_total = jnp.zeros((), jnp.float32)
        dense_kv = []
        for i in range(self.n_dense):
            lp = jax.tree.map(lambda t: t[i], p["dense_layers"])
            x, aux, kv = self._layer_fwd(lp, x, positions, None, None, False,
                                         q_chunk, collect_kv)
            aux_total += aux
            dense_kv.append(kv)

        windows, thetas = self._layer_meta("scan")

        def body(carry, xs):
            x, aux_acc = carry
            lp, window, theta = xs
            x, aux, kv = self._layer_fwd(lp, x, positions, window, theta,
                                         self.is_moe, q_chunk, collect_kv)
            return (x, aux_acc + aux), kv

        body_fn = jax.checkpoint(body) if remat else body
        (x, aux_total), kv_stack = jax.lax.scan(
            body_fn, (x, aux_total), (p["layers"], windows, thetas))
        if collect_kv and self.n_dense:
            kv_stack = (dense_kv, kv_stack)
        return x, aux_total, kv_stack

    def forward(self, p: Params, batch: Dict[str, jax.Array], *,
                q_chunk: int = 1024, remat: bool = False,
                collect_kv: bool = False):
        """Returns (logits, aux_loss, kv_stack or None)."""
        x, aux, kv = self.forward_hidden(p, batch, q_chunk=q_chunk,
                                         remat=remat, collect_kv=collect_kv)
        return self._head(p, x), aux, kv

    def loss(self, p: Params, batch: Dict[str, jax.Array], *,
             remat: bool = False, q_chunk: int = 1024):
        cfg = self.cfg
        x, aux, _ = self.forward_hidden(p, batch, remat=remat,
                                        q_chunk=q_chunk)
        if cfg.num_image_tokens:            # loss only on text positions
            x = x[:, cfg.num_image_tokens:]
        xn = self._final_norm(p, x)
        w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
        nll, cnt = chunked_lm_loss(xn, w, batch["targets"],
                                   final_softcap=cfg.final_softcap)
        loss = nll / jnp.maximum(cnt, 1.0)
        if self.is_moe:
            loss = loss + cfg.moe.router_aux_coef * aux / max(1, self.n_scan)
        return loss, {"nll": loss, "aux": aux}

    # ---------------- serving ----------------
    def kv_config(self, max_seq: int, num_blocks: Optional[int] = None,
                  batch: int = 1, dp_groups: int = 1) -> PagedKVConfig:
        cfg = self.cfg
        bt = cfg.kv_block_tokens
        mbs = (max_seq + bt - 1) // bt
        latent = cfg.attention == "mla"
        split = latent and cfg.mla_latent_tp
        if latent:
            hd = cfg.mla.kv_lora_rank if split else cfg.mla.latent_dim
        else:
            hd = cfg.hd
        return PagedKVConfig(
            num_layers=cfg.num_layers,
            kv_heads=1 if latent else cfg.kv_heads,
            head_dim=hd,
            block_tokens=bt,
            num_blocks=num_blocks if num_blocks else mbs * batch,
            max_blocks_per_seq=mbs,
            latent=latent,
            latent_rope=(cfg.mla.qk_rope_head_dim if split else 0),
            dtype=jnp.dtype(cfg.dtype),
            dp_groups=dp_groups)

    def _write_token(self, pool_l, kv_new, tables, seq_lens, bt,
                     dp_groups: int = 1):
        return write_token_paged(pool_l, kv_new, tables, seq_lens, bt,
                                 dp_groups)

    @property
    def supports_suffix_prefill(self) -> bool:
        """Suffix-only prefill reads prefix KV through the block table --
        implemented for the GQA/MQA pool layout; MLA falls back to full
        recompute."""
        return self.cfg.attention != "mla"

    def _jitted(self, name: str, fn):
        """One jitted trace per serving entry point, shared by EVERY
        caller (engine and reference decoders alike) so token-identity
        comparisons never cross a jit/eager numerics boundary.  The
        PagedKVCache argument (position 2 in all three) is donated: its
        pool buffers are reused in place on backends that support it."""
        j = self._jit_cache.get(name)
        if j is None:
            j = jax.jit(fn, donate_argnums=(2,))
            self._jit_cache[name] = j
        return j

    def decode_step(self, p: Params, tokens: jax.Array,
                    cache: PagedKVCache):
        """tokens: (B,) -> (logits (B, V), updated cache).  Runs the
        cached jitted trace -- steady-state decode is one Python dispatch
        into a warm executable."""
        return self._jitted("decode_step", self._decode_step_impl)(
            p, tokens, cache)

    def _decode_step_impl(self, p: Params, tokens: jax.Array,
                          cache: PagedKVCache):
        cfg = self.cfg
        bt = cache.config.block_tokens
        x = self._embed_tokens(p, tokens)
        x = constrain(x, "batch", None)
        tables, lens = cache.block_tables, cache.seq_lens
        dp = cache.config.dp_groups

        def layer_decode(lp, x, k_pool_l, v_pool_l, window, theta):
            """One layer over the pool as it stands; every attention
            variant merges the new token's own term itself, so the
            layer returns that token's entries instead of writing them."""
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps, gemma_style=True)
            if cfg.attention == "mla" and cache.config.latent_rope:
                y, new = A.mla_decode_split(
                    lp["attn"], h, cfg, k_pool_l, v_pool_l, tables, lens,
                    dp_groups=dp)
            elif cfg.attention == "mla":
                y, latent_new = A.mla_decode(lp["attn"], h, cfg, k_pool_l,
                                             tables, lens, dp_groups=dp)
                new = (latent_new,)
            else:
                y, new = A.gqa_decode(
                    lp["attn"], h, cfg, k_pool_l, v_pool_l, tables, lens,
                    window=window, rope_theta=theta, dp_groups=dp)
            if cfg.post_norms:
                y = rmsnorm(y, lp["ln1_post"], cfg.norm_eps, gemma_style=True)
            x = self._residual(x, y)
            h = rmsnorm(x, lp["ln2"], cfg.norm_eps, gemma_style=True)
            if self.is_moe and "router" in lp["ff"]:
                y, _ = moe_ffn_dispatch(lp["ff"], h[:, None], cfg)
                y = y[:, 0]
            else:
                y = mlp(h, lp["ff"], cfg.mlp)
            if cfg.post_norms:
                y = rmsnorm(y, lp["ln2_post"], cfg.norm_eps, gemma_style=True)
            x = self._residual(x, y)
            return constrain(x, "batch", None), tuple(new)

        packed = cache.config.packed

        def write(pool_l, new, vpool_l):
            """A per-layer pool slice with this step's token written in,
            for pools that are not packed."""
            kp = self._write_token(pool_l, new[0], tables, lens, bt, dp)
            vp = (None if vpool_l is None else
                  self._write_token(vpool_l, new[1], tables, lens, bt, dp))
            return kp, vp

        # leading dense layers (deepseek): unscanned
        dense_new = []
        for i in range(self.n_dense):
            lp = jax.tree.map(lambda t: t[i], p["dense_layers"])
            kp = cache.k_pool[i]
            vp = cache.v_pool[i] if cache.v_pool is not None else None
            x, new = layer_decode(lp, x, kp, vp, None, None)
            if packed:
                dense_new.append(new[0])
                continue
            kp, vp = write(kp, new, vp)
            cache = dataclasses.replace(
                cache, k_pool=cache.k_pool.at[i].set(kp),
                v_pool=(cache.v_pool.at[i].set(vp)
                        if vp is not None else cache.v_pool))

        windows, thetas = self._layer_meta("scan")

        # Pools thread through the scan as xs.  A packed latent pool is
        # only read there: each layer emits its new token's latent as ys
        # (L, B, W), written into the donated pool once after the scan,
        # in place.  Other pools emit their updated slices as ys (each
        # layer's slice written once to the stacked output).  A
        # carry-with-DUS variant was tried and REFUTED: XLA copies the
        # whole carry per iteration under the read-modify-write
        # (EXPERIMENTS.md §Perf).
        def body(x, xs):
            if cache.v_pool is None:
                lp, kp, window, theta = xs
                vp = None
            else:
                lp, kp, vp, window, theta = xs
            x, new = layer_decode(lp, x, kp, vp, window, theta)
            if packed:
                return x, new[0]
            kp, vp = write(kp, new, vp)
            return x, ((kp,) if vp is None else (kp, vp))

        k_scan = cache.k_pool[self.n_dense:]
        if cache.v_pool is None:
            xs = (p["layers"], k_scan, windows, thetas)
        else:
            xs = (p["layers"], k_scan, cache.v_pool[self.n_dense:],
                  windows, thetas)
        x, ys = jax.lax.scan(body, x, xs)
        if packed:
            latents = (jnp.concatenate([jnp.stack(dense_new), ys])
                       if dense_new else ys)
            phys = pool_wide_ids(tables[jnp.arange(tables.shape[0]),
                                        lens // bt], dp,
                                 cache.k_pool.shape[1])
            k_new = write_packed_tokens(cache.k_pool, latents, phys,
                                        lens % bt)
            v_new = None
        else:
            k_new = (cache.k_pool.at[self.n_dense:].set(ys[0])
                     if self.n_dense else ys[0])
            if cache.v_pool is None:
                v_new = None
            else:
                v_new = (cache.v_pool.at[self.n_dense:].set(ys[1])
                         if self.n_dense else ys[1])
        cache = dataclasses.replace(cache, k_pool=k_new, v_pool=v_new,
                                    seq_lens=cache.seq_lens + 1)
        logits = self._head(p, x[:, None] if x.ndim == 2 else x)
        return logits.reshape(tokens.shape[0], -1), cache

    def decode_fused(self, p: Params, tokens: jax.Array,
                     cache: PagedKVCache, upd_slots: jax.Array,
                     upd_tables: jax.Array, upd_lens: jax.Array):
        """Resident decode tail: delta-scatter + decode + argmax in ONE
        jitted, cache-donated trace.  ``upd_slots`` (W,) names the slots
        whose mapping changed since the last step (padded with
        ``slots``, dropped by the scatter); their rows/lens are spliced
        into the device-resident table before the step.  Returns
        ``(next_tokens (B,), cache)`` -- only the (B,) token array ever
        crosses to host.  W is shape-bucketed, so steady state (W = 1
        bucket or 0 dirty rows) reuses one warm executable."""
        return self._jitted("decode_fused", self._decode_fused_impl)(
            p, tokens, cache, upd_slots, upd_tables, upd_lens)

    def _decode_fused_impl(self, p: Params, tokens: jax.Array,
                           cache: PagedKVCache, upd_slots: jax.Array,
                           upd_tables: jax.Array, upd_lens: jax.Array):
        tables = cache.block_tables.at[upd_slots].set(upd_tables,
                                                      mode="drop")
        lens = cache.seq_lens.at[upd_slots].set(upd_lens, mode="drop")
        cache = dataclasses.replace(cache, block_tables=tables,
                                    seq_lens=lens)
        # Same math as decode_step: _decode_step_impl is inlined into
        # this trace, so the served decode and a decode_step reference
        # agree token for token by construction.
        logits, cache = self._decode_step_impl(p, tokens, cache)
        return jnp.argmax(logits, axis=-1), cache

    def prefill(self, p: Params, batch: Dict[str, jax.Array],
                cache: PagedKVCache, lengths: jax.Array):
        """Run the forward pass and write the whole prompt's KV stream.

        batch["tokens"]: (B, S) block-aligned.  Returns (last_logits,
        cache with seq_lens = lengths).  Jit-cached per (B, S) bucket.
        """
        return self._jitted("prefill", self._prefill_impl)(
            p, batch, cache, lengths)

    def _prefill_impl(self, p: Params, batch: Dict[str, jax.Array],
                      cache: PagedKVCache, lengths: jax.Array):
        cfg = self.cfg
        logits, _, kv_stack = self.forward(p, batch, collect_kv=True)
        if self.n_dense:
            dense_kv, kv_scan = kv_stack
            k_all = jnp.concatenate([kv[0][None] for kv in dense_kv]
                                    + [kv_scan[0]], axis=0)
            v_all = (None if kv_scan[1] is None else jnp.concatenate(
                [kv[1][None] for kv in dense_kv] + [kv_scan[1]], axis=0))
        else:
            k_all, v_all = kv_stack
        if cfg.attention == "mla" and cache.config.latent_rope:
            lora = cfg.mla.kv_lora_rank
            cache = cache.write_prefill(k_all[..., :lora],
                                        k_all[..., lora:], lengths)
        elif cfg.attention == "mla":
            # latent stream (L, B, S, latent); the latent pool is headless
            cache = cache.write_prefill(k_all, None, lengths)
        else:
            cache = cache.write_prefill(k_all, v_all, lengths)
        idx = jnp.maximum(lengths - 1, 0)
        last = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1)[:, 0]
        return last, cache

    def prefill_suffix(self, p: Params, tokens: jax.Array,
                       cache: PagedKVCache, lengths: jax.Array,
                       starts: jax.Array, write_tables: jax.Array):
        """Suffix-only prefill: run the forward pass over just the
        un-cached tail of each prompt, attending through the block table
        to the COW-shared prefix blocks.  Jit-cached per (B, SQ) bucket.

        tokens: (B, SQ) block-aligned suffix tokens; row b's token i sits
        at absolute position starts[b] + i (starts block-aligned).
        lengths: (B,) full prompt lengths.  write_tables: (B, SQ // bt)
        physical destinations for the suffix KV (sink where the block is
        aliased from the parent).  Returns (last_logits, cache with
        seq_lens = lengths).  Requires ``supports_suffix_prefill``.
        """
        return self._jitted("prefill_suffix", self._prefill_suffix_impl)(
            p, tokens, cache, lengths, starts, write_tables)

    def _prefill_suffix_impl(self, p: Params, tokens: jax.Array,
                             cache: PagedKVCache, lengths: jax.Array,
                             starts: jax.Array, write_tables: jax.Array):
        cfg = self.cfg
        assert cfg.attention != "mla", "suffix prefill is GQA/MQA-only"
        bt = cache.config.block_tokens
        x = self._embed_tokens(p, tokens)
        x = constrain(x, "batch", None, None)
        tables = cache.block_tables
        dp = cache.config.dp_groups

        def layer_suffix(lp, x, k_pool_l, v_pool_l, window, theta):
            h = rmsnorm(x, lp["ln1"], cfg.norm_eps, gemma_style=True)
            y, (k_pool_l, v_pool_l) = A.gqa_prefill_paged(
                lp["attn"], h, cfg, k_pool_l, v_pool_l, tables, lengths,
                starts, write_tables, window=window, rope_theta=theta,
                dp_groups=dp)
            if cfg.post_norms:
                y = rmsnorm(y, lp["ln1_post"], cfg.norm_eps, gemma_style=True)
            x = self._residual(x, y)
            h = rmsnorm(x, lp["ln2"], cfg.norm_eps, gemma_style=True)
            if self.is_moe and "router" in lp["ff"]:
                y, _ = moe_ffn_dispatch(lp["ff"], h, cfg)
            else:
                y = mlp(h, lp["ff"], cfg.mlp)
            if cfg.post_norms:
                y = rmsnorm(y, lp["ln2_post"], cfg.norm_eps, gemma_style=True)
            x = self._residual(x, y)
            return constrain(x, "batch", None, None), k_pool_l, v_pool_l

        # leading dense layers (deepseek): unscanned
        for i in range(self.n_dense):
            lp = jax.tree.map(lambda t: t[i], p["dense_layers"])
            x, kp, vp = layer_suffix(lp, x, cache.k_pool[i],
                                     cache.v_pool[i], None, None)
            cache = dataclasses.replace(
                cache, k_pool=cache.k_pool.at[i].set(kp),
                v_pool=cache.v_pool.at[i].set(vp))

        windows, thetas = self._layer_meta("scan")

        # pools thread through the scan as xs -> ys, exactly like decode
        def body(x, xs):
            lp, kp, vp, window, theta = xs
            x, kp, vp = layer_suffix(lp, x, kp, vp, window, theta)
            return x, (kp, vp)

        xs = (p["layers"], cache.k_pool[self.n_dense:],
              cache.v_pool[self.n_dense:], windows, thetas)
        x, pools = jax.lax.scan(body, x, xs)
        k_new = (cache.k_pool.at[self.n_dense:].set(pools[0])
                 if self.n_dense else pools[0])
        v_new = (cache.v_pool.at[self.n_dense:].set(pools[1])
                 if self.n_dense else pools[1])
        cache = dataclasses.replace(cache, k_pool=k_new, v_pool=v_new,
                                    seq_lens=lengths)
        logits = self._head(p, x)
        idx = jnp.maximum(lengths - starts - 1, 0)
        last = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1)[:, 0]
        return last, cache
