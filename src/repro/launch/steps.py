"""Jitted, sharded train/serve steps.

``build_train_step`` / ``build_serve_step`` wire a model to a mesh:
parameters Megatron-style over "model" (from the logical-axis trees),
batch over ("pod", "data"), paged KV pools co-sharded with the batch
(dp-grouped block ids keep every table gather local -- see
PagedKVConfig.dp_groups), optimizer state sharded like the params
(optionally ZeRO-1 over the data axis).

The returned ``Step.lower(*specs)`` lowers under the sharding-rules
context so ``constrain()`` calls inside the models resolve, so a step
can be compiled for a described topology as well as run.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.paged_kv import PagedKVCache
from repro.launch import shardings as SH
from repro.launch.mesh import batch_axes
from repro.models.rwkv_lm import RWKVState
from repro.models.whisper import WhisperState
from repro.models.zamba2 import ZambaState
from repro.optim import adamw as OPT


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def rules_for(cfg: ModelConfig, mesh: Mesh,
              overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Arch-aware sharding rules: attention weights replicate (and the
    attention goes context-parallel) when heads don't divide the model
    axis -- gemma-2b MQA, whisper 6H, internvl 14H, minicpm3 40H."""
    rules: Dict[str, Any] = {}
    tp = mesh.shape.get("model", 1)
    if tp > 1 and cfg.num_heads % tp != 0:
        rules["attn_heads"] = None
    if tp > 1 and cfg.vocab_size % tp != 0:
        # jit in_shardings require divisibility; the replicated embed is
        # small for exactly these archs (internvl 272MB, minicpm 376MB,
        # whisper 38MB)
        rules["vocab"] = None
    if overrides:
        rules.update(overrides)
    return rules


def _div(n: int, mesh: Mesh, axes: Tuple[str, ...]) -> bool:
    prod = 1
    for a in axes:
        prod *= mesh.shape[a]
    return n % prod == 0 and n >= prod


def dp_groups_for(mesh: Mesh, global_batch: int) -> int:
    bax = batch_axes(mesh)
    prod = 1
    for a in bax:
        prod *= mesh.shape[a]
    return prod if global_batch % prod == 0 and global_batch >= prod else 1


# ---------------------------------------------------------------------------
# batch / state shardings
# ---------------------------------------------------------------------------
def batch_shardings(mesh: Mesh, batch_specs: Dict[str, Any],
                    global_batch: int):
    bax = batch_axes(mesh)
    b = bax if _div(global_batch, mesh, bax) else None

    def one(v):
        return _ns(mesh, b, *([None] * (len(v.shape) - 1)))

    return jax.tree.map(one, batch_specs)


def _kv_head_axis(mesh: Mesh, kvh: int) -> Optional[str]:
    return "model" if ("model" in mesh.axis_names and kvh >= mesh.shape["model"]
                       and kvh % mesh.shape["model"] == 0) else None


def paged_cache_shardings(mesh: Mesh, cache: PagedKVCache):
    cfg = cache.config
    bax = batch_axes(mesh)
    B = cache.block_tables.shape[0]
    b = bax if (cfg.dp_groups > 1 and _div(B, mesh, bax)) else None
    if cfg.latent and cfg.latent_rope:
        # latent TP: lora stream sharded over 'model' on its last dim
        la = ("model" if ("model" in mesh.axis_names and
                          cfg.head_dim % mesh.shape["model"] == 0) else None)
        kpool = _ns(mesh, None, b, None, la)
        vpool = _ns(mesh, None, b, None, None)
    elif cfg.latent:
        kpool = _ns(mesh, None, b, None, None)
        vpool = None
    else:
        ha = _kv_head_axis(mesh, cfg.kv_heads)
        kpool = _ns(mesh, None, b, None, ha, None)
        vpool = kpool
    return PagedKVCache(
        k_pool=kpool, v_pool=vpool,
        block_tables=_ns(mesh, b, None),
        seq_lens=_ns(mesh, b),
        config=cfg)


def state_shardings(mesh: Mesh, state, cfg: ModelConfig):
    bax = batch_axes(mesh)
    if isinstance(state, PagedKVCache):
        return paged_cache_shardings(mesh, state)
    if isinstance(state, RWKVState):
        B = state.mix_x.shape[1]
        b = bax if _div(B, mesh, bax) else None
        H = state.wkv.shape[2]
        ha = _kv_head_axis(mesh, H)
        return RWKVState(_ns(mesh, None, b, None), _ns(mesh, None, b, None),
                         _ns(mesh, None, b, ha, None, None))
    if isinstance(state, ZambaState):
        B = state.conv.shape[2]
        b = bax if _div(B, mesh, bax) else None
        H = state.ssd.shape[3]
        ha = _kv_head_axis(mesh, H)
        return ZambaState(_ns(mesh, None, None, b, None, None),
                          _ns(mesh, None, None, b, ha, None, None),
                          paged_cache_shardings(mesh, state.kv))
    if isinstance(state, WhisperState):
        B = state.cross_k.shape[1]
        b = bax if _div(B, mesh, bax) else None
        ha = _kv_head_axis(mesh, state.cross_k.shape[3])
        cross = _ns(mesh, None, b, None, ha, None)
        return WhisperState(paged_cache_shardings(mesh, state.self_kv),
                            cross, cross)
    raise TypeError(type(state))


def opt_shardings(mesh: Mesh, param_shard, param_shapes,
                  zero1: bool = False) -> OPT.AdamWState:
    """Moments shard like params; ZeRO-1 additionally shards the first
    replicated, data-divisible dim of each moment over 'data'."""

    def moment(ns: NamedSharding, shape):
        spec = list(ns.spec) + [None] * (len(shape.shape) - len(ns.spec))
        if zero1 and "data" in mesh.axis_names:
            for i, (s, dim) in enumerate(zip(spec, shape.shape)):
                if s is None and dim % mesh.shape["data"] == 0 and \
                        dim >= mesh.shape["data"]:
                    spec[i] = "data"
                    break
        return _ns(mesh, *spec)

    mu = jax.tree.map(moment, param_shard, param_shapes)
    return OPT.AdamWState(step=_ns(mesh), mu=mu, nu=mu)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Step:
    jitted: Any
    mesh: Mesh
    rules: Optional[Dict[str, Any]]
    in_shardings: Any
    out_shardings: Any

    def lower(self, *arg_specs):
        with self.mesh, SH.use_rules(self.mesh, self.rules):
            return self.jitted.lower(*arg_specs)

    def __call__(self, *args):
        with self.mesh, SH.use_rules(self.mesh, self.rules):
            return self.jitted(*args)


def build_train_step(model, mesh: Mesh, opt_cfg: OPT.AdamWConfig, *,
                     rules: Optional[Dict[str, Any]] = None,
                     remat: bool = True, zero1: bool = False,
                     donate: bool = True) -> Step:
    rules = rules_for(model.cfg, mesh, rules)
    pshapes, axes = model.param_specs()
    pshard = SH.param_shardings(axes, mesh, rules)
    oshard = opt_shardings(mesh, pshard, pshapes, zero1=zero1)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return model.loss(p, batch, remat=remat)

        (loss, mets), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state, om = OPT.apply_updates(opt_cfg, params, grads,
                                                  opt_state)
        mets = {**mets, **om, "loss": loss}
        return params, opt_state, mets

    jitted = jax.jit(
        train_step,
        in_shardings=(pshard, oshard, None),
        out_shardings=(pshard, oshard, None),
        donate_argnums=(0, 1) if donate else ())
    return Step(jitted, mesh, rules, (pshard, oshard), (pshard, oshard))


def build_serve_step(model, mesh: Mesh, state_example, *,
                     rules: Optional[Dict[str, Any]] = None,
                     donate: bool = True) -> Step:
    """state_example: state pytree (arrays or ShapeDtypeStructs) used to
    derive shardings."""
    cfg = model.cfg
    rules = rules_for(cfg, mesh, rules)
    pshapes, axes = model.param_specs()
    pshard = SH.param_shardings(axes, mesh, rules)
    sshard = state_shardings(mesh, state_example, cfg)
    tokens_b = None
    B = (state_example.block_tables.shape[0]
         if isinstance(state_example, PagedKVCache) else None)
    if B is None:
        B = jax.tree.leaves(state_example)[0].shape[1]
    bax = batch_axes(mesh)
    tshard = _ns(mesh, bax if _div(B, mesh, bax) else None)

    def serve_step(params, tokens, state):
        logits, state = model.decode_step(params, tokens, state)
        return logits, state

    jitted = jax.jit(
        serve_step,
        in_shardings=(pshard, tshard, sshard),
        out_shardings=(_ns(mesh, tshard.spec[0],
                           rules.get("vocab", "model")), sshard),
        donate_argnums=(2,) if donate else ())
    return Step(jitted, mesh, rules, (pshard, tshard, sshard), sshard)


def build_prefill_step(model, mesh: Mesh, state_example, global_batch: int, *,
                       rules: Optional[Dict[str, Any]] = None) -> Step:
    rules = rules_for(model.cfg, mesh, rules)
    pshapes, axes = model.param_specs()
    pshard = SH.param_shardings(axes, mesh, rules)
    sshard = state_shardings(mesh, state_example, model.cfg)
    bax = batch_axes(mesh)
    b = bax if _div(global_batch, mesh, bax) else None

    def prefill_step(params, batch, state, lengths):
        return model.prefill(params, batch, state, lengths)

    jitted = jax.jit(
        prefill_step,
        in_shardings=(pshard, None, sshard, _ns(mesh, b)),
        out_shardings=(_ns(mesh, b, rules.get("vocab", "model")), sshard))
    return Step(jitted, mesh, rules, None, None)
