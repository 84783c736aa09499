"""Mixture-of-Experts layer: dropless token-choice with sort + ragged_dot.

Routing: softmax router, top-k.  Tokens are sorted by assigned expert and
hit their experts through ``jax.lax.ragged_dot`` (group-sizes per expert),
so nothing is dropped and no (T, E, C) dispatch one-hot is materialized.

Distribution modes (see DESIGN.md §4):
  * ``tp``  (baseline): every device holds all experts, sharded on the
    hidden (d_ff_expert) dim over "model" -- TP-in-expert, collective
    cost identical to a dense MLP (one psum after down-proj).
  * ``ep``  (hillclimb): experts sharded over "model"; tokens routed with
    an all_to_all inside shard_map.  Implemented in
    ``repro.launch.shardmoe`` and toggled per-config.

This module is mesh-agnostic: it computes on whatever token shard it is
handed (works single-device in smoke tests and inside shard_map/pjit).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.common import AxTree, Params, dense_init

# ---------------------------------------------------------------------------
# Blocked grouped matmul ("megablox-lite").
#
# ``jax.lax.ragged_dot`` has no grouped kernel on the CPU backend: it
# lowers to a DENSE (tokens, E*d) x (E*d, f) contraction -- 550 GB
# intermediates and ~20x phantom FLOPs for qwen3, which would poison any
# compiled-HLO cost count.  Instead we pad each expert's token run to a multiple
# of ``block`` rows inside a fixed (Tk + E*block) buffer and run ONE
# batched (nb, m, d) x (nb, d, f) matmul with per-block expert weights --
# the same schedule a TPU grouped-matmul kernel (megablox) executes, so
# FLOPs/bytes in the compiled HLO are honest (padding waste <= E*block
# tokens, ~6% at qwen3 scale).  Plain autodiff gives the right backward
# (scatter-add into the expert weights).
# ---------------------------------------------------------------------------


def _group_layout(group_sizes: jax.Array, Tk: int, block: int):
    """Returns (pos (Tk,), block_expert (nb,)) for sorted tokens."""
    E = group_sizes.shape[0]
    m = block
    padded = ((group_sizes + m - 1) // m) * m
    ends = jnp.cumsum(group_sizes)
    pends = jnp.cumsum(padded)
    starts = ends - group_sizes
    pstarts = pends - padded
    j = jnp.arange(Tk, dtype=jnp.int32)
    e_of = jnp.searchsorted(ends, j, side="right").astype(jnp.int32)
    e_of = jnp.minimum(e_of, E - 1)
    pos = pstarts[e_of] + (j - starts[e_of])
    nb = (Tk + E * m) // m
    blk_expert = jnp.searchsorted(pends, jnp.arange(nb, dtype=jnp.int32) * m,
                                  side="right").astype(jnp.int32)
    return pos, jnp.minimum(blk_expert, E - 1)


def scatter_to_blocks(x: jax.Array, pos: jax.Array, block: int, E: int):
    """x: (Tk, d) sorted -> (nb, m, d) block-padded buffer."""
    Tk, d = x.shape
    buf = jnp.zeros((Tk + E * block, d), x.dtype).at[pos].set(x)
    return buf.reshape(-1, block, d)


def blocks_matmul(buf: jax.Array, w: jax.Array, blk_expert: jax.Array):
    """(nb, m, d) x w[blk_expert] -> (nb, m, f)."""
    return jnp.einsum("bmd,bdf->bmf", buf, w[blk_expert])


def gather_from_blocks(buf: jax.Array, pos: jax.Array) -> jax.Array:
    nb, m, f = buf.shape
    return buf.reshape(nb * m, f)[pos]


def grouped_matmul(x, w, group_sizes, *, block: int = 256):
    """x: (Tk, d) sorted by group; w: (E, d, f) -> (Tk, f)."""
    E = w.shape[0]
    block = min(block, max(1, x.shape[0]))
    pos, blk_e = _group_layout(group_sizes, x.shape[0], block)
    buf = scatter_to_blocks(x, pos, block, E)
    return gather_from_blocks(blocks_matmul(buf, w, blk_e), pos)


def init_moe(rng, cfg: ModelConfig) -> Tuple[Params, AxTree]:
    e = cfg.moe
    d, dtype = cfg.d_model, cfg.jdtype
    r = jax.random.split(rng, 6)
    p: Params = {
        "router": dense_init(r[0], d, e.num_experts, jnp.float32, scale=0.02),
        # stacked expert weights: (E, d, f) / (E, f, d)
        "wi": dense_init(r[1], d, e.num_experts * e.d_ff_expert, dtype
                         ).reshape(d, e.num_experts, e.d_ff_expert).transpose(1, 0, 2),
        "wg": dense_init(r[2], d, e.num_experts * e.d_ff_expert, dtype
                         ).reshape(d, e.num_experts, e.d_ff_expert).transpose(1, 0, 2),
        "wo": dense_init(r[3], e.d_ff_expert, e.num_experts * d, dtype
                         ).reshape(e.d_ff_expert, e.num_experts, d).transpose(1, 0, 2),
    }
    ax = AxTree(router=(None, None),
                wi=("expert", "embed", "heads"),
                wg=("expert", "embed", "heads"),
                wo=("expert", "heads", "embed"))
    if e.num_shared_experts:
        p["shared_wi"] = dense_init(r[4], d, e.d_ff_shared, dtype)
        p["shared_wg"] = dense_init(r[5], d, e.d_ff_shared, dtype)
        p["shared_wo"] = dense_init(r[4], e.d_ff_shared, d, dtype)
        ax.update(shared_wi=("embed", "heads"), shared_wg=("embed", "heads"),
                  shared_wo=("heads", "embed"))
    return p, ax


def route(router_w: jax.Array, x: jax.Array, e: MoEConfig):
    """x: (T, d) -> (weights (T, k), experts (T, k) int32, aux_loss)."""
    logits = x.astype(jnp.float32) @ router_w            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, e.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    T = x.shape[0]
    counts = jnp.zeros(e.num_experts, jnp.float32).at[experts.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(T * e.top_k, 1)
    pbar = probs.mean(axis=0)
    aux = e.num_experts * jnp.sum(f * pbar)
    return weights, experts, aux


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig):
    """Dropless MoE over a token shard.  x: (T, d) -> (y (T, d), aux)."""
    e = cfg.moe
    T, d = x.shape
    weights, experts, aux = route(p["router"], x, e)

    # sort token-replicas by expert
    flat_expert = experts.reshape(-1)                    # (T*k,)
    order = jnp.argsort(flat_expert)
    token_of = order // e.top_k                          # source token
    xs = x[token_of]                                     # (T*k, d) sorted
    group_sizes = jnp.zeros(e.num_experts, jnp.int32).at[flat_expert].add(1)

    # one block layout + scatter shared by all three expert matmuls
    block = min(256, max(1, xs.shape[0]))
    pos, blk_e = _group_layout(group_sizes, xs.shape[0], block)
    buf = scatter_to_blocks(xs, pos, block, e.num_experts)
    h = (jax.nn.silu(blocks_matmul(buf, p["wg"], blk_e)) *
         blocks_matmul(buf, p["wi"], blk_e))
    ys = gather_from_blocks(blocks_matmul(h, p["wo"], blk_e), pos)

    # un-sort and combine with routing weights
    w_sorted = weights.reshape(-1)[order]
    y = jnp.zeros((T, d), ys.dtype).at[token_of].add(
        ys * w_sorted[:, None].astype(ys.dtype))

    if e.num_shared_experts:
        h = jax.nn.silu(x @ p["shared_wg"]) * (x @ p["shared_wi"])
        y = y + h @ p["shared_wo"]
    return y.astype(x.dtype), aux
