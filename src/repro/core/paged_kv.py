"""Paged KV cache: the paper's block-allocated memory applied to serving.

A decoding sequence's KV cache is the canonical "large, growing array"
that virtual memory used to make contiguous.  Here it is stored the
paper's way: fixed-size blocks of ``block_tokens`` tokens drawn from a
shared pool, addressed through a per-sequence **block table** (a depth-1
tree; ``TreeArray`` provides deeper tables when max_blocks_per_seq
exceeds one table block -- see ``block_table.py``).

Pools are stacked over layers (leading L axis) so the per-layer slice
threads through ``lax.scan`` over the model's layers.  One block id is
valid across all layers/heads -- the pool's trailing dims carry
(kv_heads, head_dim), which also gives the natural sharding:

    (L, num_blocks[data], block_tokens, kv_heads[model], head_dim)

Standard (k,v) pools and MLA latent pools (single compressed c_kv stream,
DeepSeek-V2/MiniCPM3) are both supported; MLA's latent blocks are ~4x
smaller per token -- the paper's "choose your own block quantum" argument
in action.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.mem import Arena, Mapping, NULL_BLOCK, OutOfBlocksError


@dataclasses.dataclass(frozen=True)
class PagedKVConfig:
    num_layers: int
    kv_heads: int          # 0 for MLA latent mode
    head_dim: int          # per-head dim; for MLA: latent_dim = kv_lora + rope
    block_tokens: int = 64
    num_blocks: int = 1024
    max_blocks_per_seq: int = 16
    latent: bool = False   # MLA: single stream, no separate V pool
    # split-latent mode (latent TP): k_pool holds the kv_lora stream
    # (head_dim = kv_lora, shardable over 'model'), v_pool holds the
    # shared rope keys of width latent_rope.
    latent_rope: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    # data-parallel pool groups: the pool's block dim is split into
    # dp_groups contiguous ranges co-sharded with the batch, and block
    # tables hold GROUP-LOCAL ids.  This makes every table gather/scatter
    # structurally local (a batched gather), so GSPMD never needs to move
    # pool blocks across the data axis.
    dp_groups: int = 1

    @property
    def max_seq_len(self) -> int:
        return self.max_blocks_per_seq * self.block_tokens

    def token_shape(self) -> Tuple[int, ...]:
        return (self.head_dim,) if self.latent else (self.kv_heads, self.head_dim)

    @property
    def packed(self) -> bool:
        """A single latent stream is stored ``tokens_per_row`` tokens to
        a pool row: ``(L, NB, BT // K, K * W)``.  MLA's latent (kv_lora +
        rope, 288 for MiniCPM3) is no multiple of the TPU's 128 lanes, so
        the chip lays a ``(L, NB, BT, W)`` pool out block-minor, and every
        in-place write and block copy would first relay the whole pool
        out (a second, padded copy of it).  Rows of ``K * W`` lanes keep
        the natural layout, and a block stays one contiguous tile run."""
        return self.latent and not self.latent_rope

    @property
    def tokens_per_row(self) -> int:
        """K: the fewest tokens (a divisor of ``block_tokens``) whose
        latents fill a whole number of 128-lane rows; the whole block
        where none does.  1 for every pool that is not ``packed``."""
        if not self.packed:
            return 1
        for k in range(1, self.block_tokens + 1):
            if self.block_tokens % k == 0 and k * self.head_dim % 128 == 0:
                return k
        return self.block_tokens

    def pool_shape(self) -> Tuple[int, ...]:
        if self.packed:
            k = self.tokens_per_row
            return (self.num_layers, self.num_blocks, self.block_tokens // k,
                    k * self.head_dim)
        return (self.num_layers, self.num_blocks, self.block_tokens,
                *self.token_shape())

    def bytes_per_token_per_layer(self) -> int:
        streams = 1 if self.latent else 2
        per = int(np.prod(self.token_shape()))
        return streams * per * jnp.dtype(self.dtype).itemsize

    def swap_nbytes_per_block(self) -> int:
        """Device<->host bytes to move ONE block (all layers, all streams).

        This is the unit the serving swap path is held to: a preempted
        sequence holding n blocks moves exactly n * this many bytes --
        never a function of num_blocks (pool size).
        """
        per = int(np.prod(self.token_shape()))   # k (or latent) stream
        width = per if self.latent else 2 * per
        if self.latent and self.latent_rope:
            width += self.latent_rope            # shared rope-key stream
        return (self.num_layers * self.block_tokens * width
                * jnp.dtype(self.dtype).itemsize)


def pool_wide_ids(ids: jax.Array, dp_groups: int,
                  num_blocks: int) -> jax.Array:
    """Group-local block ids (B, ...) as ids into the whole pool: row b
    belongs to data-parallel group ``b // (B // dp_groups)``."""
    if dp_groups <= 1:
        return ids
    B = ids.shape[0]
    first = (jnp.arange(B) // (B // dp_groups)) * (num_blocks // dp_groups)
    return ids + first.reshape((B,) + (1,) * (ids.ndim - 1))


def write_packed_tokens(pool: jax.Array, kv_new: jax.Array,
                        phys: jax.Array, off: jax.Array) -> jax.Array:
    """Write one token per sequence into every layer of a packed pool
    (``PagedKVConfig.packed``): ``kv_new`` (L, B, W) lands at block
    ``phys[b]``, offset ``off[b]``.  One update per layer and sequence,
    each one token wide, keeps the donated pool in place; wider updates
    (all layers at once, a scatter) make XLA relay the pool out first."""
    L, B, W = kv_new.shape
    k = pool.shape[-1] // W
    val = kv_new.astype(pool.dtype)

    def put(b, pool):
        row, col = off[b] // k, off[b] % k * W
        for layer in range(L):
            tok = jax.lax.dynamic_slice(val, (layer, b, 0), (1, 1, W))
            pool = jax.lax.dynamic_update_slice(
                pool, tok[:, :, None], (layer, phys[b], row, col))
        return pool
    return jax.lax.fori_loop(0, B, put, pool)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """Functional paged KV state threaded through decode steps."""

    k_pool: jax.Array            # (L, NB, BT, KVH, HD); MLA (L, NB, BT/K, K*LAT)
    v_pool: Optional[jax.Array]  # None in latent (MLA) mode
    block_tables: jax.Array      # (B, max_blocks_per_seq) int32
    seq_lens: jax.Array          # (B,) int32 -- tokens already cached
    config: PagedKVConfig = dataclasses.field(metadata=dict(static=True))

    def tree_flatten(self):
        return (self.k_pool, self.v_pool, self.block_tables, self.seq_lens), self.config

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch, aux)

    # -- constructors --------------------------------------------------
    @classmethod
    def create(cls, config: PagedKVConfig, batch: int) -> "PagedKVCache":
        k = jnp.zeros(config.pool_shape(), config.dtype)
        if config.latent:
            v = (jnp.zeros((*config.pool_shape()[:-1], config.latent_rope),
                           config.dtype) if config.latent_rope else None)
        else:
            v = jnp.zeros(config.pool_shape(), config.dtype)
        tables = jnp.full((batch, config.max_blocks_per_seq), NULL_BLOCK, jnp.int32)
        lens = jnp.zeros((batch,), jnp.int32)
        return cls(k, v, tables, lens, config)

    @classmethod
    def specs(cls, config: PagedKVConfig, batch: int) -> "PagedKVCache":
        """ShapeDtypeStruct stand-in for compiling without allocating."""
        sds = jax.ShapeDtypeStruct
        k = sds(config.pool_shape(), config.dtype)
        if config.latent:
            v = (sds((*config.pool_shape()[:-1], config.latent_rope),
                     config.dtype) if config.latent_rope else None)
        else:
            v = sds(config.pool_shape(), config.dtype)
        tables = sds((batch, config.max_blocks_per_seq), jnp.int32)
        lens = sds((batch,), jnp.int32)
        return cls(k, v, tables, lens, config)

    @property
    def batch(self) -> int:
        return self.block_tables.shape[0]

    # -- addressing ------------------------------------------------------
    def _addr(self, pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Logical position -> (physical block id per seq, offset)."""
        blk_no = pos // self.config.block_tokens
        off = pos % self.config.block_tokens
        b = jnp.arange(self.batch)
        phys = self.block_tables[b, blk_no]
        return phys, off

    # -- writes ---------------------------------------------------------
    def append_token(self, k_new: jax.Array,
                     v_new: Optional[jax.Array]) -> "PagedKVCache":
        """Write one new token's KV for ALL layers at position seq_lens.

        k_new: (L, B, KVH, HD) (or (L, B, LAT) latent).  Returns cache
        with seq_lens advanced by 1.
        """
        phys, off = self._addr(self.seq_lens)
        phys = pool_wide_ids(phys, self.config.dp_groups, self.k_pool.shape[1])
        if self.config.packed:
            return dataclasses.replace(
                self, k_pool=write_packed_tokens(self.k_pool, k_new, phys, off),
                seq_lens=self.seq_lens + 1)
        k_pool = self.k_pool.at[:, phys, off].set(
            k_new.astype(self.config.dtype))
        v_pool = self.v_pool
        if v_new is not None:
            v_pool = self.v_pool.at[:, phys, off].set(v_new.astype(self.config.dtype))
        return dataclasses.replace(
            self, k_pool=k_pool, v_pool=v_pool, seq_lens=self.seq_lens + 1)

    def write_layer_token(self, layer_kv, layer: jax.Array):
        """Per-layer single-token write, for use inside lax.scan bodies.

        layer_kv: (k (B,KVH,HD), v or None).  Positions taken from
        seq_lens (NOT advanced here -- call ``advance`` once per step).
        Returns updated per-layer pool slices to be re-stacked by scan.
        """
        raise NotImplementedError("use pool slices via scan xs; see models/")

    def _scatter_blocks(self, pool, tbl, payload):
        """pool (L, NB, BT, ...) .at[:, tbl].set(payload) with dp-group
        local block ids when dp_groups > 1 (see PagedKVConfig)."""
        dp = self.config.dp_groups
        if self.config.packed:
            B, nblk = tbl.shape
            tbl = pool_wide_ids(tbl, dp, pool.shape[1])

            def put(i, pool):   # one whole block, all layers, in place
                b, j = i // nblk, i % nblk
                blk = jax.lax.dynamic_slice_in_dim(
                    jax.lax.dynamic_index_in_dim(payload, b, 1, False),
                    j, 1, axis=1)
                return jax.lax.dynamic_update_slice(pool, blk,
                                                    (0, tbl[b, j], 0, 0))
            return jax.lax.fori_loop(0, B * nblk, put, pool)
        if dp <= 1:
            return pool.at[:, tbl].set(payload)
        L, NB = pool.shape[:2]
        B = tbl.shape[0]
        pg = pool.reshape(L, dp, NB // dp, *pool.shape[2:])
        tg = tbl.reshape(dp, B // dp, tbl.shape[1])
        pay = payload.reshape(payload.shape[0], dp, B // dp,
                              *payload.shape[2:])
        out = jax.vmap(lambda pl, tb, pp: pl.at[:, tb].set(pp),
                       in_axes=(1, 0, 1), out_axes=1)(pg, tg, pay)
        return out.reshape(pool.shape)

    def write_prefill(self, k: jax.Array, v: Optional[jax.Array],
                      lengths: jax.Array) -> "PagedKVCache":
        """Bulk-write prompts.  k: (L, B, S, KVH, HD); positions 0..S-1.

        Tokens beyond ``lengths[b]`` are written too (harmless -- masked
        by seq_lens at read time), keeping the write dense/regular.
        """
        L, B, S = k.shape[:3]
        bt = self.config.block_tokens
        assert S % bt == 0, "prefill length must be block-aligned"
        nblk = S // bt
        tbl = jnp.maximum(self.block_tables[:, :nblk], 0)       # (B, nblk)
        kb = k.reshape(L, B, nblk, *self.config.pool_shape()[2:]
                       ).astype(self.config.dtype)
        k_pool = self._scatter_blocks(self.k_pool, tbl, kb)
        v_pool = self.v_pool
        if v is not None:
            vb = v.reshape(L, B, nblk, bt, *v.shape[3:]).astype(self.config.dtype)
            v_pool = self._scatter_blocks(self.v_pool, tbl, vb)
        return dataclasses.replace(self, k_pool=k_pool, v_pool=v_pool,
                                   seq_lens=lengths.astype(jnp.int32))

    def advance(self, n: int = 1) -> "PagedKVCache":
        return dataclasses.replace(self, seq_lens=self.seq_lens + n)

    # -- reads ----------------------------------------------------------
    def gather_layer(self, layer_k: jax.Array, layer_v: Optional[jax.Array]):
        """Materialize (B, S_max, ...) views of one layer's pool slices.

        This is the *reference* read path (the Pallas paged_attention
        kernel streams blocks instead).  Invalid table entries are
        clipped; callers mask by seq_lens.
        """
        tbl = jnp.maximum(self.block_tables, 0)  # clip NULL
        k = layer_k[tbl]            # (B, nblk, BT, ...)
        k = k.reshape(k.shape[0], -1, *self.config.token_shape())
        if layer_v is None:
            return k, None
        v = layer_v[tbl]
        v = v.reshape(v.shape[0], -1, *v.shape[3:])
        return k, v


class PagedKVManager:
    """Host-side allocator policy for the cache -- a thin Arena client.

    The manager used to own its own ``BlockAllocator`` and dict-of-lists
    tables; it is now a facade over ``repro.mem``: one ``Mapping`` per
    live sequence drawn from a shared ``Arena`` pool class, so the KV
    cache, TreeArrays, BlockStacks and the host swap tier all account
    against ONE address space.  The manager still deals ONLY in block
    ids at its boundary -- payload transfers (swap-out/in at block
    granularity, COW block copies) are the caller's job, so that bytes
    moved always scale with blocks held, never with pool size (see
    ``serve/swap.py`` and ``kernels/block_copy.py``).
    """

    def __init__(self, config: PagedKVConfig, arena: Optional[Arena] = None,
                 pool_class: str = "kv"):
        self.config = config
        self.arena = arena if arena is not None else Arena()
        self.pool_class = self.arena.register_class(
            pool_class, num_blocks=config.num_blocks,
            block_nbytes=config.swap_nbytes_per_block(),
            dp_groups=config.dp_groups)
        self._maps: dict[int, Mapping] = {}

    # -- compat views over the Arena -----------------------------------
    @property
    def allocator(self):
        """The pool class's raw allocator (legacy/test escape hatch)."""
        return self.arena.allocator(self.pool_class)

    @property
    def tables(self) -> dict:
        """seq_id -> block-id list of every DEVICE-resident sequence."""
        return {sid: m.block_ids() for sid, m in self._maps.items()
                if m.placement == "device"}

    @property
    def swapped(self) -> dict:
        """seq_id -> blocks held at swap-out (host-tier residency)."""
        return self.arena.host_counts(self.pool_class)

    def mapping(self, seq_id: int) -> Mapping:
        return self._maps[seq_id]

    def has_seq(self, seq_id: int) -> bool:
        """Device-resident? (O(1) -- prefer over the ``tables`` view,
        which materializes every live table on each access)."""
        m = self._maps.get(seq_id)
        return m is not None and m.placement == "device"

    def block_ids(self, seq_id: int) -> List[int]:
        return self._maps[seq_id].block_ids()

    # -- admission/extension ------------------------------------------
    def blocks_needed(self, tokens: int) -> int:
        bt = self.config.block_tokens
        return (tokens + bt - 1) // bt

    @property
    def free_blocks(self) -> int:
        """Leases currently grantable -- the scheduler's admission view."""
        return self.arena.num_free(self.pool_class)

    def can_admit(self, tokens: int) -> bool:
        return self.free_blocks >= self.blocks_needed(tokens)

    def admit(self, seq_id: int, tokens: int,
              tenant: str = "default") -> List[int]:
        need = self.blocks_needed(tokens)
        if need > self.free_blocks:
            # atomic: don't leave an empty mapping behind on failure
            raise OutOfBlocksError(
                f"requested {need} blocks, only {self.free_blocks} free")
        m = self.arena.mapping(self.pool_class, seq_id, tenant=tenant)
        self._maps[seq_id] = m
        return m.ensure_capacity(need)

    def extend(self, seq_id: int, new_total_tokens: int) -> List[int]:
        """Ensure capacity for new_total_tokens; returns newly added ids.

        Allocates under pressure: on exhaustion the Arena's reclaimer
        (LIFO preemption when serving) evicts victims; if the victim is
        this sequence itself, ``LeaseRevokedError`` propagates.
        """
        return self._maps[seq_id].ensure_capacity(
            self.blocks_needed(new_total_tokens))

    def release(self, seq_id: int) -> None:
        self._maps.pop(seq_id).free()

    def adopt(self, seq_id: int, mapping: Mapping) -> None:
        """Register an existing Arena mapping under this manager (the
        restart path: ``Arena.restore`` rebuilds host-resident mappings
        and the engine re-adopts them so preempted sequences resume)."""
        if mapping.pool_class != self.pool_class:
            raise ValueError(
                f"adopt of mapping in pool class {mapping.pool_class!r}; "
                f"this manager allocates in {self.pool_class!r}")
        if seq_id in self._maps:
            raise ValueError(f"sequence {seq_id} already tracked")
        self._maps[seq_id] = mapping

    def disown(self, seq_id: int) -> Mapping:
        """Stop tracking ``seq_id`` WITHOUT freeing its mapping -- the
        inverse of ``adopt``.  The disaggregation handoff: a prefill
        worker disowns the finished sequence so ``export_mapping`` can
        gather its blocks into a bundle and release them."""
        return self._maps.pop(seq_id)

    def reserve_sink(self):
        """Pin one block (never handed to a sequence).

        The engine points masked prefill-table entries at this 'sink'
        block so padded rows and COW-aliased prefixes have a harmless
        scatter target.  Returns the pinned ``Lease`` -- read
        ``lease.block`` for the current physical id (compaction may
        relocate it).
        """
        return self.arena.pin(self.pool_class, owner="sink")

    def reserve_block(self) -> int:
        """Legacy form of ``reserve_sink``: the pinned id as an int."""
        return self.reserve_sink().block

    # -- COW prefix sharing ---------------------------------------------
    def fork(self, parent_id: int, child_id: int, shared_tokens: int,
             tenant: Optional[str] = None) -> List[int]:
        """COW: child aliases EVERY parent block covering shared_tokens.

        A trailing partially-filled block is aliased too; the first
        divergent write into it goes through ``ensure_writable`` which
        fulfils the copy-on-write (paper Table 1 row 'Copy-on-Write').
        Callers that only want fully-shared blocks pass shared_tokens
        rounded down to a block multiple.
        """
        bt = self.config.block_tokens
        nshared = -(-shared_tokens // bt)
        parent = self._maps[parent_id]
        if nshared > len(parent):
            raise ValueError(
                f"fork of {shared_tokens} tokens needs {nshared} blocks, "
                f"parent holds {len(parent)}")
        child = parent.fork(child_id, nshared, tenant=tenant)
        self._maps[child_id] = child
        return child.block_ids()

    def ensure_writable(self, seq_id: int,
                        token_pos: int) -> Optional[Tuple[int, int]]:
        """COW write barrier for the block covering ``token_pos``.

        If that block is shared (refcount > 1) the sequence gets a fresh
        private block in its table and the fulfilment copy is ENQUEUED
        on the Arena's transfer plane (the fresh block stays in-flight
        until the engine dispatches the queue); ``(src, dst)`` is
        returned for copy-traffic accounting, None when the block is
        already exclusively owned.  The fresh block is a deferred claim
        allocated under pressure (see ``Mapping.ensure_writable``).
        """
        return self._maps[seq_id].ensure_writable(
            token_pos // self.config.block_tokens)

    # -- swapping ---------------------------------------------------------
    def swap_out(self, seq_id: int) -> List[int]:
        """Migrate a preempted sequence to the host tier; return the
        vacated device ids.

        The payload move is a d2h plan on the Arena's transfer plane:
        the vacated ids stay held until its gather is dispatched, and
        the host copy lands at the next fence (``serve/swap.py`` keeps
        the byte ledger).
        """
        return self._maps[seq_id].migrate("host")

    def swap_in(self, seq_id: int) -> List[int]:
        """Migrate back: reallocate (anywhere!) and return the new block
        ids, with the scatter of the saved payload enqueued as an h2d
        plan.

        The new physical blocks need not match the old ones -- block
        tables absorb the relocation, which is the paper's 'Relocation /
        Migration' row implemented in software.
        """
        return self._maps[seq_id].migrate("device")

    # -- speculative swap-in (prefetch) ---------------------------------
    def prefetch(self, seq_id: int) -> List[int]:
        """Speculatively swap a preempted sequence back in on the
        BACKGROUND h2d lane: fresh blocks are allocated and the scatter
        enqueued, but host residency and payload stay intact until
        ``commit_prefetch`` -- so the speculation costs nothing to
        cancel (``Mapping.prefetch``)."""
        return self._maps[seq_id].prefetch()

    def is_prefetched(self, seq_id: int) -> bool:
        m = self._maps.get(seq_id)
        return m is not None and m.prefetched

    def prefetched_ids(self) -> List[int]:
        """Sequences with an uncommitted speculative swap-in (the
        pressure path's cheapest reclaim victims)."""
        return [sid for sid, m in self._maps.items() if m.prefetched]

    def commit_prefetch(self, seq_id: int) -> Tuple[List[int], bool]:
        """Promote the speculation to the real resume; returns
        ``(new_ids, served_from_completed_prefetch)``."""
        return self._maps[seq_id].commit_prefetch()

    def cancel_prefetch(self, seq_id: int) -> None:
        """Withdraw the speculation (candidate evicted/freed or memory
        tightened): blocks release, host state stays resumable."""
        self._maps[seq_id].cancel_prefetch()

    @property
    def speculative_blocks(self) -> int:
        """Device blocks held by uncommitted prefetches.  Admission
        counts these as FREE (they cancel instantly under pressure), so
        scheduling decisions are identical with and without
        speculation."""
        return sum(m.spec_blocks for m in self._maps.values())

    def device_table(self, seq_id: int) -> np.ndarray:
        return self._maps[seq_id].packed_table(self.config.max_blocks_per_seq)

    @property
    def utilization(self) -> float:
        return (self.arena.num_used(self.pool_class)
                / self.arena.num_blocks(self.pool_class))
