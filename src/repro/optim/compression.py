"""Error-feedback int8 gradient compression for the DP gradient sync.

At 1000+ node scale the data-parallel gradient reduction is the largest
recurring collective.  This module compresses gradients to int8 with a
per-block scale (block = the paper's fixed-size quantum: 8192 f32 values
= 32 KB) and keeps the quantization residual in an error-feedback buffer
so the bias cancels across steps (1-bit Adam lineage).

Usage: the compressed train step (train/compressed.py) computes
per-device gradients inside ``jax.shard_map`` over the data axes and
calls ``sync_mean`` instead of ``psum``:

  1. add residual to the local gradient,
  2. quantize to int8 + f32 per-block scales,
  3. all_gather (int8, scales) over the data axes -- 4x fewer bytes than
     an f32 all-gather, ~2x fewer than bf16 ring all-reduce traffic;
     (a psum of int8 would overflow, and XLA's all-reduce cannot carry
     per-shard scales),
  4. dequantize + average locally; store the new residual.

The collective-bytes saving is measured in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

BLOCK = 8192  # f32 values per scale block (the paper's 32 KB quantum)


def quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: flat f32, length multiple of BLOCK -> (int8 codes, f32 scales)."""
    xb = x.reshape(-1, BLOCK)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), axis=1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xb / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * scale[:, None]).reshape(-1)


def flatten_tree(tree) -> Tuple[jax.Array, Any, List]:
    """Pytree -> (padded flat f32 vector, treedef, shapes)."""
    flat, treedef = jax.tree.flatten(tree)
    shapes = [(f.shape, f.size) for f in flat]
    parts = []
    for f in flat:
        v = f.astype(jnp.float32).reshape(-1)
        parts.append(jnp.pad(v, (0, (-v.size) % BLOCK)))
    return jnp.concatenate(parts), treedef, shapes


def unflatten_tree(vec: jax.Array, treedef, shapes):
    out, off = [], 0
    for shp, n in shapes:
        out.append(jax.lax.dynamic_slice_in_dim(vec, off, n).reshape(shp))
        off += n + ((-n) % BLOCK)
    return treedef.unflatten(out)


def sync_mean(vec: jax.Array, residual: jax.Array,
              axes: Tuple[str, ...]) -> Tuple[jax.Array, jax.Array]:
    """Inside shard_map: error-feedback int8 mean over ``axes``.

    vec/residual: this device's flat gradient + residual (full length).
    Returns (mean vector, new residual).
    """
    v = vec + residual
    q, s = quantize(v)
    new_r = v - dequantize(q, s)
    qg = jax.lax.all_gather(q, axes)          # (n, blocks, BLOCK)
    sg = jax.lax.all_gather(s, axes)          # (n, blocks)
    qg = qg.reshape(-1, *q.shape)
    sg = sg.reshape(-1, *s.shape)
    n = qg.shape[0]
    total = jnp.sum(jax.vmap(dequantize)(qg, sg), axis=0)
    return total / n, new_r
