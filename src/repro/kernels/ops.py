"""Jit'd public wrappers for the Pallas kernels.

Dispatch policy: on TPU backends the Mosaic kernels run natively; on CPU
``interpret=True`` executes the kernel bodies exactly (the reference
path is ``repro.models.attention.decode_attention``).  ``force``
overrides for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import block_copy as _bc
from repro.kernels import paged_attention as _pa
from repro.kernels import paged_prefill as _pp
from repro.kernels import tree_gather as _tg
from repro.kernels import ref as kref


def _use_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def tree_gather(leaves, leaf_table, interpret: Optional[bool] = None):
    return _tg.tree_gather(leaves, leaf_table,
                           interpret=_use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def tree_block_sum(leaves, leaf_table, interpret: Optional[bool] = None):
    return _tg.tree_block_sum(leaves, leaf_table,
                              interpret=_use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def tree_gather_rows(pool, row_ids, leaf_table, rows_per_block: int,
                     interpret: Optional[bool] = None):
    return _tg.tree_gather_rows(pool, row_ids, leaf_table, rows_per_block,
                                interpret=_use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "scale", "softcap", "window", "v_dim", "interpret"))
def paged_attention(q, k_pool, v_pool, block_tables, seq_lens,
                    scale: Optional[float] = None,
                    softcap: Optional[float] = None,
                    window: Optional[int] = None,
                    v_dim: Optional[int] = None,
                    interpret: Optional[bool] = None):
    return _pa.paged_attention(
        q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
        softcap=softcap, window=window, v_dim=v_dim,
        interpret=_use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "scale", "softcap", "window", "interpret"), donate_argnums=(3, 4))
def paged_attention_append(q, k_new, v_new, k_pool, v_pool, block_tables,
                           seq_lens,
                           scale: Optional[float] = None,
                           softcap: Optional[float] = None,
                           window: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Fused append-then-attend decode step; pools donated (in-place)."""
    return _pa.paged_attention_append(
        q, k_new, v_new, k_pool, v_pool, block_tables, seq_lens,
        scale=scale, softcap=softcap, window=window,
        interpret=_use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "scale", "softcap", "window", "v_dim", "q_chunk", "interpret"))
def paged_prefill_attention(q, k_pool, v_pool, block_tables, kv_lens,
                            q_starts,
                            scale: Optional[float] = None,
                            softcap: Optional[float] = None,
                            window: Optional[int] = None,
                            v_dim: Optional[int] = None,
                            q_chunk: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Suffix prefill attention through the block table (COW sharing)."""
    return _pp.paged_prefill_attention(
        q, k_pool, v_pool, block_tables, kv_lens, q_starts, scale=scale,
        softcap=softcap, window=window, v_dim=v_dim, q_chunk=q_chunk,
        interpret=_use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def block_copy(pool, src, dst, interpret: Optional[bool] = None):
    return _bc.block_copy(pool, src, dst,
                          interpret=_use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_blocks(pool, idx, interpret: Optional[bool] = None):
    """Compact (L, n, *block) gather of blocks ``idx`` (swap-out path)."""
    return _bc.gather_blocks(pool, idx,
                             interpret=_use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def copy_pool_blocks(pool, src, dst, interpret: Optional[bool] = None):
    """Layer-stacked block copy plan (COW fulfilment / relocation)."""
    return _bc.copy_pool_blocks(pool, src, dst,
                                interpret=_use_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter_blocks(pool, idx, payload, interpret: Optional[bool] = None):
    """Scatter a compact (L, n, *block) payload into blocks ``idx``
    (swap-in path -- the inverse of ``gather_blocks``)."""
    return _bc.scatter_blocks(pool, idx, payload,
                              interpret=_use_interpret(interpret))


def gather_rows(pool, rows, start, n: int, interpret: Optional[bool] = None):
    """``pool[rows, start:start + n]`` of a (NB, R, 128) pool, one DMA a
    row (the layered state decode's read of one layer)."""
    return _bc.gather_rows(pool, rows, start, n,
                           interpret=_use_interpret(interpret))


def scatter_rows(pool, rows, start, upd, interpret: Optional[bool] = None):
    """``pool.at[rows, start:start + n].set(upd)`` in place, one DMA a row
    (the inverse of ``gather_rows``)."""
    return _bc.scatter_rows(pool, rows, start, upd,
                            interpret=_use_interpret(interpret))


# re-export oracles for convenience
tree_gather_ref = kref.tree_gather_ref
tree_block_sum_ref = kref.tree_block_sum_ref
tree_gather_rows_ref = kref.tree_gather_rows_ref
paged_attention_ref = kref.paged_attention_ref
paged_attention_append_ref = kref.paged_attention_append_ref
paged_prefill_attention_ref = kref.paged_prefill_attention_ref
