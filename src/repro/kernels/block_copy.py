"""Pallas TPU kernel: batched physical block copy (swap-in / compaction).

Executes a (src, dst) copy plan against the arena: the device-side half
of the paper's 'Relocation / Migration' and 'Swapping' rows.  The plan
is a scalar-prefetch operand, so the DMA schedule is driven from SMEM —
the same discipline as the other kernels; compaction plans come from
``core.block_table.compaction_plan``.

Copies must be applied to a SNAPSHOT (the plan generator guarantees
src/dst disjointness for compaction: movers come from beyond the dense
prefix, holes lie inside it — asserted in core.block_table tests).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None


def _copy_kernel(src_ref, dst_ref, pool_ref, out_ref):
    out_ref[...] = pool_ref[...]


def _dma_copy_kernel(src_ref, dst_ref, pool_ref, out_ref, sem):
    i = pl.program_id(0)
    copy = pltpu.make_async_copy(pool_ref.at[src_ref[i]],
                                 out_ref.at[dst_ref[i]], sem)
    copy.start()
    copy.wait()


def _row_copies(rows_ref, start_ref, pool_ref, buf_ref, sem, *, to_pool):
    """One DMA a row ``b`` between ``pool_ref[rows[b], start:start + n]``
    and ``buf_ref[b]`` (into the pool if ``to_pool``): all started, then
    all waited on one semaphore, each wait taking one copy's bytes."""
    n = buf_ref.shape[1]
    start = pl.multiple_of(start_ref[0], 8)

    def copy(b):
        piece = pool_ref.at[rows_ref[b], pl.ds(start, n)]
        src, dst = ((buf_ref.at[b], piece) if to_pool
                    else (piece, buf_ref.at[b]))
        return pltpu.make_async_copy(src, dst, sem)

    def launch(b, carry):
        copy(b).start()
        return carry

    def wait(b, carry):
        copy(b).wait()
        return carry

    jax.lax.fori_loop(0, rows_ref.shape[0], launch, 0)
    jax.lax.fori_loop(0, rows_ref.shape[0], wait, 0)


def _row_gather_kernel(rows_ref, start_ref, pool_ref, out_ref, sem):
    _row_copies(rows_ref, start_ref, pool_ref, out_ref, sem, to_pool=False)


def _row_scatter_kernel(rows_ref, start_ref, upd_ref, pool_ref, out_ref,
                        sem):
    _row_copies(rows_ref, start_ref, out_ref, upd_ref, sem, to_pool=True)


def _gather_kernel(idx_ref, pool_ref, out_ref):
    out_ref[...] = pool_ref[...]


def _scatter_kernel(idx_ref, payload_ref, pool_ref, out_ref):
    out_ref[...] = payload_ref[...]


def block_copy(pool: jax.Array, src: jax.Array, dst: jax.Array,
               *, interpret: bool = False) -> jax.Array:
    """pool: (NB, *block); src/dst: (n,) int32 -> pool with plan applied.

    Grid step i DMAs block ``src[i]`` into position ``dst[i]`` straight
    from HBM to HBM: a flat pool's block is one whole sequence state
    (52 MB for Mamba2-370M), far beyond what VMEM staging can hold.
    Output aliases the pool, so untouched blocks stay in place.  On TPU
    each block must be one contiguous tile run: the pool is laid out
    ``(NB, 1, E)`` with E a multiple of 128, or in rows, ``(NB, E / 128,
    128)`` (see ``serve.arch.ConstantStateManager``); Mosaic refuses a
    single row of an ``(NB, E)`` array, whose (8, 128) tiles interleave
    8 rows.
    """
    n = src.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _dma_copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        interpret=interpret,
        input_output_aliases={2: 0},
    )(src, dst, pool)


def gather_blocks(pool: jax.Array, idx: jax.Array,
                  *, interpret: bool = False) -> jax.Array:
    """pool: (L, NB, *block); idx: (n,) int32 -> (L, n, *block).

    Grid step (l, i) DMAs layer l of block ``idx[i]`` into out[l, i]:
    the device half of swap-out.  The result is COMPACT -- one
    device->host copy of it moves ``n * swap-block`` bytes, so transfer
    cost scales with blocks held, never pool size (paper Table 1 row
    'Swapping' done in software).
    """
    L, n = pool.shape[0], idx.shape[0]
    blk = pool.shape[2:]
    ones = (1, 1) + blk
    zeros = tuple(0 for _ in blk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, n),
        in_specs=[pl.BlockSpec(ones, lambda l, i, s: (l, s[i]) + zeros)],
        out_specs=pl.BlockSpec(ones, lambda l, i, s: (l, i) + zeros),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, n) + blk, pool.dtype),
        interpret=interpret,
    )(idx, pool)


def scatter_blocks(pool: jax.Array, idx: jax.Array, payload: jax.Array,
                   *, interpret: bool = False) -> jax.Array:
    """pool: (L, NB, *block); idx: (n,); payload: (L, n, *block).

    Grid step (l, i) DMAs payload[l, i] into pool position ``idx[i]`` --
    the device half of swap-in, and the inverse of ``gather_blocks``.
    Together they are the transfer plane's d2h/h2d executors: one plan
    entry moves a whole block across the L axis, and a batched plan (the
    multi-plan coalesced form) is a single launch over the concatenated
    id vector.  ``idx`` entries must be distinct (fresh allocations are).
    """
    L, n = pool.shape[0], idx.shape[0]
    blk = pool.shape[2:]
    ones = (1, 1) + blk
    zeros = tuple(0 for _ in blk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, n),
        in_specs=[pl.BlockSpec(ones, lambda l, i, s: (l, i) + zeros),
                  pl.BlockSpec(ones, lambda l, i, s: (l, s[i]) + zeros)],
        out_specs=pl.BlockSpec(ones, lambda l, i, s: (l, s[i]) + zeros),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        interpret=interpret,
        input_output_aliases={2: 0},
    )(idx, payload, pool)


def copy_pool_blocks(pool: jax.Array, src: jax.Array, dst: jax.Array,
                     *, interpret: bool = False) -> jax.Array:
    """pool: (L, NB, *block); copy block src[i] -> dst[i] on ALL layers.

    The layer-stacked twin of ``block_copy``: one (src, dst) plan entry
    moves a whole KV block across the L axis.  Used to fulfil COW when a
    sequence first writes into a shared block (``fork_for_write``).
    src/dst must be disjoint as sets (the allocator guarantees it: dst
    ids come fresh off the free list).
    """
    L, n = pool.shape[0], src.shape[0]
    blk = pool.shape[2:]
    ones = (1, 1) + blk
    zeros = tuple(0 for _ in blk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, n),
        in_specs=[pl.BlockSpec(ones, lambda l, i, s, d: (l, s[i]) + zeros)],
        out_specs=pl.BlockSpec(ones, lambda l, i, s, d: (l, d[i]) + zeros),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        interpret=interpret,
        input_output_aliases={2: 0},
    )(src, dst, pool)


def _row_spec(n_in: int):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_in,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )


def gather_rows(pool: jax.Array, rows: jax.Array, start: jax.Array, n: int,
                *, interpret: bool = False) -> jax.Array:
    """pool: (NB, R, 128); rows: (B,) int32; start: int32 scalar ->
    ``pool[rows, start:start + n]``, (B, n, 128).

    One HBM-to-HBM DMA a row, all in flight at once: the read of one
    layer's recurrent state for every slot of the layered state decode
    (``Mamba2LM.decode_step_in_pool``).  ``start`` and ``n`` must be
    multiples of 8, so each piece is a contiguous run of (8, 128) tiles.
    Rows may repeat.
    """
    return pl.pallas_call(
        _row_gather_kernel,
        grid_spec=_row_spec(1),
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], n, pool.shape[2]),
                                       pool.dtype),
        interpret=interpret,
        name="state_row_gather",
    )(rows, jnp.reshape(start, (1,)).astype(jnp.int32), pool)


def scatter_rows(pool: jax.Array, rows: jax.Array, start: jax.Array,
                 upd: jax.Array, *, interpret: bool = False) -> jax.Array:
    """The inverse of ``gather_rows``: ``pool.at[rows, start:start +
    n].set(upd)`` for upd (B, n, 128), one DMA a row into the pool, which
    the output aliases, so nothing else of the pool moves.  The copies
    of a repeated row (slots padded to the sink) race, and leave that
    piece undefined; on the interpreter the last one lands."""
    return pl.pallas_call(
        _row_scatter_kernel,
        grid_spec=_row_spec(2),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        interpret=interpret,
        input_output_aliases={3: 0},
        name="state_row_scatter",
    )(rows, jnp.reshape(start, (1,)).astype(jnp.int32), upd, pool)
