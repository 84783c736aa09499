"""Pure-jnp oracles for every Pallas kernel (no pallas imports).

The kernels are TPU-targeted; on the CPU they are validated in
interpret mode against these oracles (tests/test_kernels.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG = -1e30


def tree_gather_ref(leaves: jax.Array, leaf_table: jax.Array) -> jax.Array:
    return leaves[leaf_table]


def tree_block_sum_ref(leaves: jax.Array, leaf_table: jax.Array) -> jax.Array:
    return jnp.sum(leaves[leaf_table].astype(jnp.float32), axis=1)


def tree_gather_rows_ref(pool: jax.Array, row_ids: jax.Array,
                         leaf_table: jax.Array, rows_per_block: int) -> jax.Array:
    phys = leaf_table[row_ids // rows_per_block]
    return pool[phys, row_ids % rows_per_block]


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_tables: jax.Array, seq_lens: jax.Array, *,
                        scale: Optional[float] = None,
                        softcap: Optional[float] = None,
                        window: Optional[int] = None,
                        v_dim: Optional[int] = None) -> jax.Array:
    """Dense-gather decode attention.  Shapes as in kernels.paged_attention."""
    B, KVH, G, HD = q.shape
    NB, BT, _, _ = k_pool.shape
    MB = block_tables.shape[1]
    VD = v_dim if v_dim is not None else v_pool.shape[-1]
    if scale is None:
        scale = HD ** -0.5

    tbl = jnp.maximum(block_tables, 0)
    k = k_pool[tbl].reshape(B, MB * BT, KVH, HD)      # (B, S, KVH, HD)
    v = v_pool[tbl].reshape(B, MB * BT, KVH, -1)[..., :VD]

    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(MB * BT)[None, :]
    valid = pos < seq_lens[:, None]
    if window is not None:
        valid = jnp.logical_and(valid, pos >= (seq_lens[:, None] - window))
    s = jnp.where(valid[:, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_attention_append_ref(q: jax.Array, k_new: jax.Array,
                               v_new: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_tables: jax.Array,
                               seq_lens: jax.Array, *,
                               scale: Optional[float] = None,
                               softcap: Optional[float] = None,
                               window: Optional[int] = None):
    """Append-then-attend decode step (fused-kernel oracle).

    Writes the new token's K/V rows into the tail block named by the
    table (``tables[b, seq_lens[b] // BT]`` at offset ``seq_lens[b] %
    BT``), then attends over ``seq_lens + 1`` positions -- the resident
    decode tail's single-pass discipline.  Returns ``(o, k_pool,
    v_pool)``.  Rows whose table is full (``seq_lens == MB * BT``) drop
    the write and attend over the full table; rows sharing a tail block
    (empty slots parked on the sink) scatter in unspecified order, which
    only ever touches sink garbage.

    q     : (B, KVH, G, HD);  k_new: (B, KVH, HD);  v_new: (B, KVH, VD)
    pools / tables / lens as in ``paged_attention_ref``.
    """
    B = q.shape[0]
    NB, BT = k_pool.shape[:2]
    MB = block_tables.shape[1]
    jt = jnp.minimum(seq_lens // BT, MB - 1)
    phys = jnp.maximum(block_tables[jnp.arange(B), jt], 0)
    off = seq_lens - jt * BT                 # >= BT only when table full
    k_pool = k_pool.at[phys, off].set(k_new.astype(k_pool.dtype),
                                      mode="drop")
    v_pool = v_pool.at[phys, off].set(v_new.astype(v_pool.dtype),
                                      mode="drop")
    o = paged_attention_ref(q, k_pool, v_pool, block_tables, seq_lens + 1,
                            scale=scale, softcap=softcap, window=window)
    return o, k_pool, v_pool


def paged_prefill_attention_ref(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, block_tables: jax.Array,
                                kv_lens: jax.Array, q_starts: jax.Array, *,
                                scale: Optional[float] = None,
                                softcap: Optional[float] = None,
                                window: Optional[int] = None,
                                v_dim: Optional[int] = None) -> jax.Array:
    """Dense-gather suffix prefill attention.

    Query i of row b sits at absolute position q_starts[b] + i and
    attends causally to kv positions <= that, bounded by kv_lens[b]
    (window, when set, uses the flash convention kv > q - window).
    Shapes as in kernels.paged_prefill.
    """
    B, SQ, KVH, G, HD = q.shape
    NB, BT, _, _ = k_pool.shape
    MB = block_tables.shape[1]
    VD = v_dim if v_dim is not None else v_pool.shape[-1]
    if scale is None:
        scale = HD ** -0.5

    tbl = jnp.maximum(block_tables, 0)
    k = k_pool[tbl].reshape(B, MB * BT, KVH, HD)      # (B, S, KVH, HD)
    v = v_pool[tbl].reshape(B, MB * BT, KVH, -1)[..., :VD]

    s = jnp.einsum("bqhgd,bshd->bhgqs", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    kv_pos = jnp.arange(MB * BT)[None, None, :]        # (1, 1, S)
    q_abs = (q_starts[:, None] + jnp.arange(SQ)[None, :])[:, :, None]
    valid = jnp.logical_and(kv_pos <= q_abs,
                            kv_pos < kv_lens[:, None, None])
    if window is not None:
        valid = jnp.logical_and(valid, kv_pos > q_abs - window)
    vmask = valid[:, None, None, :, :]
    s = jnp.where(vmask, s, _NEG)
    # masked normalization (not jax.nn.softmax): a fully-masked query row
    # -- possible for padding rows past the suffix under a tight window --
    # yields 0, matching the kernel's l == 0 convention.
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * vmask
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqs,bshd->bqhgd", p / jnp.maximum(l, 1e-30),
                   v.astype(jnp.float32))
    return o.astype(q.dtype)
