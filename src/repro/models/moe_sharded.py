"""Mesh-aware MoE dispatch.

Sorting tokens by expert must be a *per-shard* operation: under plain
GSPMD, a single argsort over the token axis has global semantics and XLA
would all-gather every token to honor it.  So when a mesh is active the
MoE layer runs inside ``shard_map``: each device routes and sorts its
local tokens and hits the experts with ``ragged_dot``.

Two expert layouts:
  * ``tp`` (baseline): all experts on every device, hidden dim (d_ff)
    sharded over "model"; one psum after the down-projection (same
    collective bill as a dense Megatron MLP).
  * ``ep``: experts sharded over "model" with an all_to_all exchange
    (tokens travel to their experts' owners and back).  Collective bytes
    scale with top_k * d_model instead of d_model per token -- cheaper
    than TP's full-activation psum when top_k < model_parallelism; the
    §Perf hillclimb quantifies this on qwen3-moe.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch import shardings as SH
from repro.models import moe as MOE


def moe_ffn_dispatch(lp, x3d: jax.Array, cfg: ModelConfig):
    """x3d: (B, S, d) -> (y, aux).  Picks local vs shard_map execution."""
    B, S, d = x3d.shape
    ctx_mesh = SH.active_mesh()
    if ctx_mesh is None:
        y, aux = MOE.moe_ffn(lp, x3d.reshape(B * S, d), cfg)
        return y.reshape(B, S, d), aux
    mode = getattr(cfg.moe, "parallel_mode", "tp")
    fn = _moe_ep_shardmap if mode == "ep" else _moe_tp_shardmap
    return fn(lp, x3d, cfg, ctx_mesh)


def _batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _moe_tp_shardmap(lp, x3d, cfg, mesh):
    """Experts replicated, d_ff_expert sharded over 'model'."""
    bax = _batch_axes(mesh)
    wspec = {k: P(None, None, "model") if k in ("wi", "wg")
             else P(None, "model", None) if k == "wo"
             else P(None, "model") if k in ("shared_wi", "shared_wg")
             else P("model", None) if k == "shared_wo"
             else P(*(None,) * lp[k].ndim) for k in lp}

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(wspec, P(bax, None, None)),
        out_specs=(P(bax, None, None), P()),
        check_vma=False)
    def run(w, x):
        B, S, d = x.shape
        y, aux = MOE.moe_ffn(w, x.reshape(B * S, d), cfg)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, bax + ("model",))
        return y.reshape(B, S, d), aux

    return run(lp, x3d)


def _moe_ep_shardmap(lp, x3d, cfg, mesh):
    """Experts sharded over 'model'; all_to_all token exchange.

    Capacity-based: each device sends up to C tokens per expert shard
    (C = local_tokens * top_k * cap / E_local, rounded up), so the a2a
    has a static shape.  Overflow drops (capacity_factor controls risk),
    matching standard EP implementations.
    """
    e = cfg.moe
    bax = _batch_axes(mesh)
    ep = mesh.shape["model"]
    assert e.num_experts % ep == 0
    e_loc = e.num_experts // ep
    wspec = {k: P("model", None, None) if k in ("wi", "wg", "wo")
             else P(None, "model") if k in ("shared_wi", "shared_wg")
             else P("model", None) if k == "shared_wo"
             else P(*(None,) * lp[k].ndim) for k in lp}

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(wspec, P(bax, None, None)),
        out_specs=(P(bax, None, None), P()),
        check_vma=False)
    def run(w, x):
        B, S, d = x.shape
        T = B * S
        xt = x.reshape(T, d)
        weights, experts, aux = MOE.route(w["router"], xt, e)
        cap_f = e.capacity_factor if e.capacity_factor > 0 else 1.25
        C = int(T * e.top_k * cap_f) // e.num_experts + 1
        # slot each (token, k) into its expert's capacity buffer
        flat_e = experts.reshape(-1)                      # (T*k,)
        order = jnp.argsort(flat_e)
        tok = order // e.top_k
        sorted_e = flat_e[order]
        pos_in_e = jnp.arange(T * e.top_k) - jnp.searchsorted(
            sorted_e, sorted_e, side="left")              # rank within expert
        keep = pos_in_e < C
        slot = sorted_e * C + pos_in_e                    # global slot id
        buf = jnp.zeros((e.num_experts * C, d), xt.dtype)
        buf = buf.at[jnp.where(keep, slot, e.num_experts * C)].set(
            xt[tok], mode="drop")
        # a2a: (E, C, d) -> exchange expert shards across 'model'
        buf = buf.reshape(ep, e_loc * C, d)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0,
                                  tiled=False)            # (ep, e_loc*C, d)
        ys = recv.reshape(ep, e_loc, C, d).transpose(1, 0, 2, 3) \
                 .reshape(e_loc, ep * C, d)
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", ys, w["wg"])) *
             jnp.einsum("ecd,edf->ecf", ys, w["wi"]))
        out = jnp.einsum("ecf,efd->ecd", h, w["wo"])
        out = out.reshape(e_loc, ep, C, d).transpose(1, 0, 2, 3) \
                 .reshape(ep, e_loc * C, d)
        back = jax.lax.all_to_all(out, "model", split_axis=0, concat_axis=0,
                                  tiled=False).reshape(e.num_experts * C, d)
        # gather back to tokens, weighted
        w_sorted = weights.reshape(-1)[order] * keep
        contrib = back[jnp.minimum(slot, e.num_experts * C - 1)] * \
            w_sorted[:, None].astype(back.dtype)
        y = jnp.zeros((T, d), back.dtype).at[tok].add(contrib)
        if e.num_shared_experts:
            hs = jax.nn.silu(xt @ w["shared_wg"]) * (xt @ w["shared_wi"])
            y = y + jax.lax.psum(hs @ w["shared_wo"], "model")
        aux = jax.lax.pmean(aux, bax + ("model",))
        return y.reshape(B, S, d).astype(x.dtype), aux

    return run(lp, x3d)
