"""Architecture registry: heterogeneous cache disciplines, one Arena.

The paper's thesis is that a software memory manager over fixed-size
blocks can serve every "large, growing array" a workload throws at it.
This module is where the serving stack cashes that claim for MODEL
ARCHITECTURES: each supported family maps to a ``CacheStrategy`` that
decides what its decode-time state IS (growing paged KV, a fixed-size
recurrent state, or both) and which Arena pool classes back it.  The
``Engine`` holds exactly one strategy and never inspects the model --
``resolve(model)`` is the only dispatch point.

Three disciplines:

* ``PagedKVStrategy`` -- transformers (dense/MoE/MLA/VLM): the
  per-token growing KV cache behind block tables, with COW prefix
  sharing, suffix-only prefill, swap and compaction.  This is the
  pre-registry engine behavior, extracted behind the interface.
* ``ConstantStateStrategy`` -- SSM / linear-attention models (mamba2):
  ONE fixed-size state block per sequence, allocated at admission and
  never grown.  Zero watermark pressure (its footprint is EXACT, so
  admission reserves no growth headroom for it), trivially swappable
  (one block moves the whole sequence), no prefix sharing (the
  recurrent state depends on the entire prefix).
* ``CompositeStrategy`` -- hybrids (zamba2): a growing paged-KV class
  for the shared-attention streams AND a constant-state class for the
  Mamba2 backbone, admitted/swapped/released together.  Whisper's
  registry row composes paged self-attention KV with a read-only
  cross-attention segment (``ReadOnlySegment``) deposited once at
  encode time and COW-shared by every decode beam; full engine serving
  of whisper is not wired yet and its builder says so loudly.

Per-pool-class accounting (``ArenaStats.per_class`` with per-tenant
quota/usage) is surfaced by ``Arena.stats()``; the scheduler's
admission, preemption and the transfer plane's per-engine holds all
route through the strategy's view (``footprint`` / ``free_by_class`` /
``growing_classes`` / ``quota_headroom``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.paged_kv import PagedKVCache, PagedKVManager
from repro.mem import Arena, Mapping, NULL_BLOCK, OutOfBlocksError
from repro.serve.swap import HostBlockStore

__all__ = [
    "SupportedArchitecture", "ARCHITECTURES", "resolve", "build_strategy",
    "CacheStrategy", "PagedKVStrategy", "ConstantStateStrategy",
    "CompositeStrategy", "ConstantStateManager", "ReadOnlySegment",
]


# ---------------------------------------------------------------------------
# constant-state pool manager
# ---------------------------------------------------------------------------
class ConstantStateManager:
    """Fixed-size per-sequence state blocks over one Arena pool class.

    The SSM/linear-attention analogue of ``PagedKVManager``: every
    sequence owns exactly ONE block of ``state_elems`` float32 elements
    (the flattened recurrent state), allocated at admission and never
    grown.  The device stream is a ``(num_blocks, *block)`` pool
    registered with the transfer plane as one flat stream
    (``layered=False``), so swap-out/swap-in/prefetch/compaction all ride
    the same plans and kernels as paged KV -- one block per sequence just
    makes every move trivially sized.  ``block`` is ``(1, state_elems)``
    unless the model lays its state out in rows (``state_block``, e.g.
    ``(E / 128, 128)`` for Mamba-2); either way a block holds the same
    elements in the same order, so payloads and snapshots carry the same
    bytes.
    """

    def __init__(self, arena: Arena, pool_class: str, state_elems: int,
                 num_blocks: int, block: Optional[Tuple[int, ...]] = None):
        if state_elems <= 0:
            raise ValueError("state_elems must be positive")
        # (NB, 1, E), not (NB, E): on TPU a 2-D array's (8, 128) tiles
        # interleave 8 rows, so one block would not be contiguous and
        # block_copy could not move it as one DMA
        block = tuple(block) if block is not None else (1, state_elems)
        if math.prod(block) != state_elems:
            raise ValueError(f"block {block} does not hold {state_elems} "
                             f"elements")
        self.arena = arena
        self.state_elems = state_elems
        self.pool_class = arena.register_class(
            pool_class, num_blocks=num_blocks,
            block_shape=(state_elems,), dtype=np.float32)
        self.pool = jnp.zeros((num_blocks, *block), jnp.float32)
        self._maps: Dict[int, Mapping] = {}
        arena.transfers.register_executor(
            self.pool_class, self._streams, self._set_streams,
            layered=False)

    # -- transfer-plane executor (flat single stream) --
    def _streams(self):
        return [self.pool]

    def _set_streams(self, streams) -> None:
        self.pool = streams[0]

    # -- views --
    @property
    def allocator(self):
        return self.arena.allocator(self.pool_class)

    @property
    def free_blocks(self) -> int:
        return self.arena.num_free(self.pool_class)

    @property
    def swapped(self) -> dict:
        return self.arena.host_counts(self.pool_class)

    @property
    def utilization(self) -> float:
        return (self.arena.num_used(self.pool_class)
                / self.arena.num_blocks(self.pool_class))

    def mapping(self, seq_id: int) -> Mapping:
        return self._maps[seq_id]

    def has_seq(self, seq_id: int) -> bool:
        m = self._maps.get(seq_id)
        return m is not None and m.placement == "device"

    def row(self, seq_id: int) -> int:
        """Physical pool row of the sequence's (single) state block."""
        return self._maps[seq_id].block_ids()[0]

    def blocks_needed(self, tokens: int) -> int:
        """Constant: one block regardless of sequence length -- the
        exactness that zeroes the admission watermark for this class."""
        return 1

    # -- lifecycle --
    def admit(self, seq_id: int, tokens: int = 0,
              tenant: str = "default") -> List[int]:
        if self.free_blocks < 1:
            raise OutOfBlocksError(
                f"constant-state pool {self.pool_class!r} exhausted")
        m = self.arena.mapping(self.pool_class, seq_id, tenant=tenant)
        self._maps[seq_id] = m
        return m.ensure_capacity(1)

    def release(self, seq_id: int) -> None:
        self._maps.pop(seq_id).free()

    def adopt(self, seq_id: int, mapping: Mapping) -> None:
        if mapping.pool_class != self.pool_class:
            raise ValueError(
                f"adopt of mapping in pool class {mapping.pool_class!r}; "
                f"this manager allocates in {self.pool_class!r}")
        if seq_id in self._maps:
            raise ValueError(f"sequence {seq_id} already tracked")
        self._maps[seq_id] = mapping

    def disown(self, seq_id: int) -> Mapping:
        """Inverse of ``adopt``: stop tracking without freeing (the
        disaggregation handoff's export side)."""
        return self._maps.pop(seq_id)

    def reserve_sink(self):
        """Pin one row as the scatter target for empty decode slots."""
        return self.arena.pin(self.pool_class, owner="sink")

    # -- swapping / speculation (generic Mapping verbs) --
    def swap_out(self, seq_id: int) -> List[int]:
        return self._maps[seq_id].migrate("host")

    def swap_in(self, seq_id: int) -> List[int]:
        return self._maps[seq_id].migrate("device")

    def prefetch(self, seq_id: int) -> List[int]:
        return self._maps[seq_id].prefetch()

    def is_prefetched(self, seq_id: int) -> bool:
        m = self._maps.get(seq_id)
        return m is not None and m.prefetched

    def prefetched_ids(self) -> List[int]:
        return [sid for sid, m in self._maps.items() if m.prefetched]

    def commit_prefetch(self, seq_id: int) -> Tuple[List[int], bool]:
        return self._maps[seq_id].commit_prefetch()

    def cancel_prefetch(self, seq_id: int) -> None:
        self._maps[seq_id].cancel_prefetch()

    @property
    def speculative_blocks(self) -> int:
        return sum(m.spec_blocks for m in self._maps.values())


# ---------------------------------------------------------------------------
# read-only segment (whisper cross-attention KV)
# ---------------------------------------------------------------------------
class ReadOnlySegment:
    """Deposit-once block segment, COW-shared by every reader.

    Whisper's cross-attention KV is computed ONCE at encode time and
    then only ever read by decode beams: a growing discipline is wrong
    (it never grows) and a private copy per beam is waste.  The segment
    is a Mapping whose blocks are written exactly once at deposit;
    ``share`` hands a beam a full alias (pure refcount traffic, no
    bytes), and there is deliberately NO write barrier -- calling
    ``ensure_writable`` on a read-only segment is a bug, not a COW.
    Swap/migrate verbs stay available (the segment relocates like any
    other mapping).
    """

    def __init__(self, arena: Arena, pool_class: str):
        self.arena = arena
        self.pool_class = pool_class
        self._segments: Dict[object, Mapping] = {}
        self._readers: Dict[object, Mapping] = {}

    def deposit(self, owner, nblocks: int) -> List[int]:
        """Allocate the segment's blocks (encode writes them once)."""
        if owner in self._segments:
            raise ValueError(f"segment {owner!r} already deposited")
        m = self.arena.mapping(self.pool_class, owner)
        self._segments[owner] = m
        return m.ensure_capacity(nblocks)

    def share(self, owner, reader) -> List[int]:
        """Alias the FULL segment to ``reader`` -- refcounts only."""
        seg = self._segments[owner]
        child = seg.fork(reader, len(seg))
        self._readers[reader] = child
        return child.block_ids()

    def block_ids(self, owner) -> List[int]:
        m = self._segments.get(owner) or self._readers[owner]
        return m.block_ids()

    def ensure_writable(self, owner, idx: int):
        raise TypeError(
            f"segment {owner!r} is read-only: cross-attention KV is "
            f"deposited once at encode time; a write barrier here means "
            f"a decode path is trying to mutate shared encoder output")

    def drop_reader(self, reader) -> None:
        self._readers.pop(reader).free()

    def release(self, owner) -> None:
        """Free the segment itself (readers keep their aliases alive)."""
        self._segments.pop(owner).free()

    def migrate(self, owner, to: str) -> List[int]:
        return self._segments[owner].migrate(to)


# ---------------------------------------------------------------------------
# strategy interface
# ---------------------------------------------------------------------------
class CacheStrategy:
    """What a model family's decode-time state is, and how it is served.

    One instance per Engine; owns the Arena pool classes, device
    streams, managers and swap ledgers for its discipline, and is the
    scheduler's admission view (``footprint``/``free_by_class``/
    ``growing_classes``/``quota_headroom`` select the per-pool-class
    vector path in ``Scheduler.plan_admissions``).
    """

    #: full Arena pool-class names this strategy allocates in
    pool_classes: List[str]
    #: subset of pool_classes whose footprint can grow after admission
    #: (the watermark applies only to these)
    growing_classes: frozenset
    supports_prefix_sharing = False
    supports_suffix_prefill = False
    #: the decode reads and writes each layer's state in place
    layered = False

    # -- admission view (scheduler vector path) --
    def footprint(self, req) -> Dict[str, int]:
        """Worst-case per-pool-class block demand of admitting ``req``."""
        raise NotImplementedError

    def free_by_class(self) -> Dict[str, int]:
        """Grantable leases per class, crediting uncommitted prefetches
        as free (they cancel instantly under pressure, keeping the
        speculative schedule decision-identical)."""
        raise NotImplementedError

    def quota_headroom(self, tenant: str) -> Dict[str, int]:
        """Remaining per-class block budget for ``tenant`` -- only
        classes with a registered quota appear; absent = unlimited."""
        room = {}
        for cls in self.pool_classes:
            q = self.arena.tenant_quota(cls, tenant)
            if q is not None:
                used = self.arena.blocks_by_tenant(cls).get(tenant, 0)
                room[cls] = q - used
        return room

    # -- lifecycle --
    def admit(self, rid: int, prompt_tokens: int, tenant: str) -> None:
        raise NotImplementedError

    def fork(self, parent: int, child: int, shared_tokens: int,
             tenant: str) -> None:
        raise NotImplementedError(
            f"{type(self).__name__} does not share prefixes")

    def extend(self, rid: int, total_tokens: int) -> List[int]:
        """Grow to cover ``total_tokens``; [] for constant disciplines."""
        raise NotImplementedError

    def ensure_writable(self, rid: int, token_pos: int):
        """COW write barrier; None when nothing was shared."""
        raise NotImplementedError

    def release(self, rid: int) -> None:
        raise NotImplementedError

    def has_seq(self, rid: int) -> bool:
        raise NotImplementedError

    # -- swap / speculation --
    def swap_out(self, rid: int) -> None:
        raise NotImplementedError

    def swap_in(self, rid: int) -> None:
        raise NotImplementedError

    def is_prefetched(self, rid: int) -> bool:
        return False

    def commit_prefetch(self, rid: int) -> Tuple[List[int], bool]:
        raise NotImplementedError

    def cancel_prefetch(self, rid: int) -> None:
        raise NotImplementedError

    def prefetched_ids(self) -> List[int]:
        return []

    def prefetch_viable(self, rid: int, watermark: int) -> bool:
        """May ``rid``'s swap-in be speculated right now? (headroom,
        residency and in-transit guards -- see Engine._maybe_prefetch)"""
        return False

    def prefetch(self, rid: int) -> None:
        raise NotImplementedError

    # -- per-step mechanism: delta sync, then one fused decode --
    def _init_resident(self) -> None:
        """Per-instance dirty-tracking state for the delta-sync path
        (strategies call this from __init__; no super().__init__)."""
        self._dirty: set = set()        # slots whose mapping changed
        self._resident: set = set()     # slots synced as live last step
        self._all_dirty = True          # first sync scatters every slot

    def mark_dirty(self, slot: int) -> None:
        """Mapping-mutation hook: ``slot``'s device rows must re-scatter
        at the next sync (growth, COW fulfilment, fork, resume, swap-in,
        adopt_device all route here via the engine)."""
        self._dirty.add(slot)

    def mark_all_dirty(self) -> None:
        """Every slot's device rows are stale -- physical ids moved under
        the tables (``compact()`` lease rewrite), including the sink the
        empty slots point at."""
        self._all_dirty = True

    def _take_dirty(self, running) -> set:
        """Slots to scatter this sync: dirty live slots plus departures
        (slots that WERE live and must be reset to the sink, or their
        stale tables would clobber reallocated blocks)."""
        run = set(running)
        if self._all_dirty:
            upd = set(range(self.slots))
            self._all_dirty = False
        else:
            upd = (self._dirty & run) | (self._resident - run)
        self._dirty.clear()
        self._resident = run
        return upd

    @staticmethod
    def _bucket(n: int, slots: int) -> int:
        """Power-of-two width for the update arrays, so repeats hit a
        warm jit trace (pad entries index ``slots`` -> scatter-dropped)."""
        return min(1 << (n - 1).bit_length(), slots) if n else 0

    @property
    def prefill_quantum(self) -> int:
        """What a padded prefill rounds its longest row up to."""
        return self._pad

    def prefill_width(self, n: int) -> int:
        """Padded length of a prefill whose longest row holds ``n``
        tokens."""
        q = self.prefill_quantum
        return -(-n // q) * q

    @property
    def table_tokens(self) -> Optional[int]:
        """Tokens one decode gathers through the block tables: every
        slot's whole table, live or not.  None for a discipline with no
        paged cache."""
        cache = getattr(self, "cache", None)
        if cache is None:
            return None
        return (self.slots * cache.config.max_blocks_per_seq
                * cache.config.block_tokens)

    def sync_device_state(self, running) -> Tuple[int, int]:
        """The read barrier (every running mapping must be settled):
        stage only the dirty slots' rows for ``decode_resident``;
        returns (rows_updated, bytes_staged)."""
        raise NotImplementedError

    def decode_resident(self, params, tokens):
        """Fused step tail: delta-scatter + state step + argmax in one
        jitted, buffer-donated callable; returns the DEVICE (B,)
        next-token array (the only thing that crosses to host)."""
        raise NotImplementedError

    def prefill(self, params, batch) -> Tuple[np.ndarray, int]:
        """ONE padded prefill for ``[(slot, req, shared), ...]``;
        returns (next-token per row, prompt tokens billed)."""
        raise NotImplementedError

    def prefill_suffix(self, params, batch) -> Tuple[np.ndarray, int, int]:
        raise NotImplementedError(
            f"{type(self).__name__} does not suffix-prefill")

    # -- compaction --
    def should_compact(self, *, min_free_frac: float,
                       frag_threshold: float) -> bool:
        return any(self.arena.should_compact(c, min_free_frac=min_free_frac,
                                             frag_threshold=frag_threshold)
                   for c in self.pool_classes)

    def compact_now(self) -> int:
        moved = 0
        for c in self.pool_classes:
            src, _ = self.arena.compact(c)
            moved += len(src)
        if moved:
            # leases were rewritten under the tables: every resident
            # row (including the empty slots' sink pointer) is stale
            self.mark_all_dirty()
        return moved

    # -- restart / teardown / audit --
    def adopt_restored(self, rid: int) -> None:
        raise NotImplementedError

    def adopt_device(self, rid: int) -> None:
        """Adopt a DEVICE-resident mapping restored from a live-migration
        snapshot (``Arena.restore`` with device payloads) or scattered by
        ``adopt_payload`` -- the sequence resumes decoding with zero
        swap-in traffic.  ``adopt_restored`` stays the host-resident
        restart path."""
        raise NotImplementedError

    def release_arena(self) -> None:
        raise NotImplementedError

    def check_consistency(self, running: Dict[int, object]) -> None:
        raise NotImplementedError


class PagedKVStrategy(CacheStrategy):
    """Growing per-token KV behind block tables (transformers).

    The pre-registry engine mechanism, extracted: COW prefix sharing,
    suffix-only prefill, padded batched prefill through a pinned sink
    block, per-step table sync, swap and speculative prefetch.
    """

    supports_prefix_sharing = True

    def __init__(self, model, *, arena: Arena, slots: int, max_seq: int,
                 num_blocks: int, dp_groups: int = 1, pool_prefix: str = ""):
        self.model = model
        self.arena = arena
        self.slots = slots
        kvcfg = model.kv_config(max_seq=max_seq, num_blocks=num_blocks,
                                batch=slots, dp_groups=dp_groups)
        self.cache = PagedKVCache.create(kvcfg, slots)
        self.mgr = PagedKVManager(kvcfg, arena=arena,
                                  pool_class=pool_prefix + "kv")
        self._sink = self.mgr.reserve_sink()
        # resident tables must START all-sink / length-0: the created
        # cache fills tables with NULL (-1), and jax scatter WRAPS
        # negative indices -- an untouched empty slot would aim every
        # padded decode write at the pool's last block.
        self.cache = dataclasses.replace(
            self.cache,
            block_tables=jnp.full_like(self.cache.block_tables, self.sink),
            seq_lens=jnp.zeros_like(self.cache.seq_lens))
        self.store = HostBlockStore(arena, self.mgr.pool_class)
        self.pool_classes = [self.mgr.pool_class]
        self.growing_classes = frozenset(self.pool_classes)
        self.supports_suffix_prefill = getattr(
            model, "supports_suffix_prefill", False)
        self._init_resident()
        self._upd = None
        arena.transfers.register_executor(
            self.mgr.pool_class, self._streams, self._set_streams)

    # -- transfer-plane executor --
    def _streams(self):
        c = self.cache
        return [c.k_pool] + ([c.v_pool] if c.v_pool is not None else [])

    def _set_streams(self, streams) -> None:
        k, *rest = streams
        self.cache = dataclasses.replace(
            self.cache, k_pool=k, v_pool=rest[0] if rest else None)

    @property
    def sink(self) -> int:
        return self._sink.block

    @property
    def block_tokens(self) -> int:
        return self.cache.config.block_tokens

    @property
    def prefill_quantum(self) -> int:
        return self.block_tokens

    @property
    def utilization(self) -> float:
        return self.mgr.utilization

    @property
    def swapped(self) -> dict:
        return self.mgr.swapped

    # -- admission view --
    def footprint(self, req) -> Dict[str, int]:
        return {self.mgr.pool_class: self.mgr.blocks_needed(req.max_tokens)}

    def free_by_class(self) -> Dict[str, int]:
        return {self.mgr.pool_class: (self.mgr.free_blocks
                                      + self.mgr.speculative_blocks)}

    # -- lifecycle --
    def admit(self, rid, prompt_tokens, tenant):
        self.mgr.admit(rid, prompt_tokens, tenant=tenant)

    def fork(self, parent, child, shared_tokens, tenant):
        self.mgr.fork(parent, child, shared_tokens, tenant=tenant)

    def extend(self, rid, total_tokens):
        return self.mgr.extend(rid, total_tokens)

    def ensure_writable(self, rid, token_pos):
        return self.mgr.ensure_writable(rid, token_pos)

    def release(self, rid):
        self.mgr.release(rid)

    def has_seq(self, rid):
        return self.mgr.has_seq(rid)

    # -- swap / speculation --
    def swap_out(self, rid):
        self.mgr.swap_out(rid)

    def swap_in(self, rid):
        self.mgr.swap_in(rid)

    def is_prefetched(self, rid):
        return self.mgr.is_prefetched(rid)

    def commit_prefetch(self, rid):
        return self.mgr.commit_prefetch(rid)

    def cancel_prefetch(self, rid):
        self.mgr.cancel_prefetch(rid)

    def prefetched_ids(self):
        return self.mgr.prefetched_ids()

    def prefetch_viable(self, rid, watermark):
        if self.mgr.is_prefetched(rid) or rid not in self.mgr.swapped:
            return False
        if self.store.in_transit(rid):
            return False               # wait for the d2h fence first
        need = self.mgr.swapped[rid]
        if need == 0:
            return False
        return self.mgr.free_blocks - need >= watermark

    def prefetch(self, rid):
        self.mgr.prefetch(rid)

    # -- per-step mechanism --
    def sync_device_state(self, running):
        """Delta read barrier: the device table is the cached translation
        structure; only slots whose mapping changed since the last step
        re-scatter.  Live-migration write tracking stays per-step (the
        coming decode appends at ``tokens_held`` regardless of table
        churn), read off the host mapping without building any table."""
        bt = self.cache.config.block_tokens
        writes = []
        for slot, req in running.items():
            self.mgr.mapping(req.rid).assert_settled()
            writes.append(int(self.mgr.block_ids(req.rid)
                              [req.tokens_held // bt]))
        self.mgr.allocator.note_write(writes)
        upd = self._take_dirty(running)
        mb = self.cache.config.max_blocks_per_seq
        W = self._bucket(len(upd), self.slots)
        upd_slots = np.full(W, self.slots, np.int32)   # pad -> dropped
        upd_tables = np.full((W, mb), self.sink, np.int32)
        upd_lens = np.zeros(W, np.int32)
        for i, slot in enumerate(sorted(upd)):
            upd_slots[i] = slot
            if slot in running:
                req = running[slot]
                upd_tables[i] = self.mgr.device_table(req.rid)
                upd_lens[i] = req.tokens_held
            # departed slots reset to all-sink / length 0: their stale
            # tables would aim padded writes at reallocated blocks
        self._upd = (upd_slots, upd_tables, upd_lens)
        nbytes = (upd_slots.nbytes + upd_tables.nbytes + upd_lens.nbytes
                  if W else 0)
        return len(upd), nbytes

    def decode_resident(self, params, tokens):
        upd_slots, upd_tables, upd_lens = self._upd
        nxt, self.cache = self.model.decode_fused(
            params, tokens, self.cache, jnp.asarray(upd_slots),
            jnp.asarray(upd_tables), jnp.asarray(upd_lens))
        return nxt

    def prefill(self, params, batch):
        """Rows padded to the longest block-aligned prompt; per-row
        prefill tables redirect padding AND COW-aliased prefix blocks to
        the sink, so writes land only in privately owned blocks."""
        cfg = self.cache.config
        bt = cfg.block_tokens
        lens = [req.tokens_held for _, req, _ in batch]
        S = self.prefill_width(max(lens))
        toks = np.zeros((len(batch), S), np.int64)
        tables = np.full((len(batch), cfg.max_blocks_per_seq), self.sink,
                         np.int32)
        for row, (slot, req, shared) in enumerate(batch):
            toks[row, : lens[row]] = np.concatenate(
                [np.asarray(req.prompt, np.int64),
                 np.asarray(req.generated, np.int64)])
            tbl = self.mgr.device_table(req.rid)
            keep = tbl != NULL_BLOCK
            keep[: -(-shared // bt) if shared else 0] = False
            tables[row, keep] = tbl[keep]
        view = PagedKVCache(self.cache.k_pool, self.cache.v_pool,
                            jnp.asarray(tables),
                            jnp.zeros((len(batch),), jnp.int32), cfg)
        last, view = self.model.prefill(
            params, {"tokens": jnp.asarray(toks)}, view,
            jnp.asarray(lens, jnp.int32))
        nxt = np.asarray(jnp.argmax(last, axis=-1))   # forces completion
        self.cache = dataclasses.replace(self.cache, k_pool=view.k_pool,
                                         v_pool=view.v_pool)
        return nxt, sum(lens)

    def prefill_suffix(self, params, batch):
        """Suffix-only prefill for forked children: each row runs the
        forward pass over just its un-cached suffix, attending through
        its FULL table (sharing saves FLOPs, not just bytes); KV writes
        route through a per-row write table (sink for aliased blocks and
        padding).  Padded width buckets to a power-of-two block count so
        repeats hit a warm jit trace."""
        cfg = self.cache.config
        bt = cfg.block_tokens
        lens = [req.tokens_held for _, req, _ in batch]
        starts = [shared if shared < lens[row]
                  else ((lens[row] - 1) // bt) * bt
                  for row, (_, _, shared) in enumerate(batch)]
        nblk = max(-(-(lens[r] - starts[r]) // bt) for r in range(len(batch)))
        nblk = min(1 << (nblk - 1).bit_length(), cfg.max_blocks_per_seq)
        S = nblk * bt
        toks = np.zeros((len(batch), S), np.int64)
        tables = np.full((len(batch), cfg.max_blocks_per_seq), self.sink,
                         np.int32)
        wtables = np.full((len(batch), nblk), self.sink, np.int32)
        for row, (slot, req, shared) in enumerate(batch):
            full = np.concatenate([np.asarray(req.prompt, np.int64),
                                   np.asarray(req.generated, np.int64)])
            toks[row, : lens[row] - starts[row]] = full[starts[row]:]
            tbl = self.mgr.device_table(req.rid)
            keep = tbl != NULL_BLOCK
            tables[row, keep] = tbl[keep]
            n_alias = -(-shared // bt)
            for j in range(nblk):
                a = starts[row] // bt + j
                if (a >= n_alias and a < len(tbl) and tbl[a] != NULL_BLOCK
                        and a * bt < lens[row]):
                    wtables[row, j] = tbl[a]
        view = PagedKVCache(self.cache.k_pool, self.cache.v_pool,
                            jnp.asarray(tables),
                            jnp.zeros((len(batch),), jnp.int32), cfg)
        suffix_tokens = sum(lens[r] - starts[r] for r in range(len(batch)))
        last, view = self.model.prefill_suffix(
            params, jnp.asarray(toks), view,
            jnp.asarray(lens, jnp.int32), jnp.asarray(starts, jnp.int32),
            jnp.asarray(wtables))
        nxt = np.asarray(jnp.argmax(last, axis=-1))   # forces completion
        self.cache = dataclasses.replace(self.cache, k_pool=view.k_pool,
                                         v_pool=view.v_pool)
        return nxt, suffix_tokens, sum(starts)

    # -- restart / teardown / audit --
    def adopt_restored(self, rid) -> None:
        m = self.arena.find_mapping(self.mgr.pool_class, rid)
        if m is None or m.placement != "host":
            raise ValueError(
                f"no restored host-resident mapping for rid {rid}; "
                f"run Arena.restore first (device-resident sequences do "
                f"not survive a restart -- re-submit them)")
        self.mgr.adopt(rid, m)

    def adopt_device(self, rid) -> None:
        m = self.arena.find_mapping(self.mgr.pool_class, rid)
        if m is None or m.placement != "device":
            raise ValueError(
                f"no device-resident mapping for rid {rid}; restore a "
                f"device snapshot (live migration) or adopt_payload a "
                f"handoff bundle first")
        self.mgr.adopt(rid, m)

    def release_arena(self) -> None:
        self.arena.transfers.unregister_executor(self.mgr.pool_class)
        self.arena.transfers.remove_observer(
            f"swap-ledger:{self.mgr.pool_class}")

    def check_consistency(self, running) -> None:
        alloc = self.mgr.allocator
        assert (alloc.num_used + alloc.num_free + alloc.num_held
                == alloc.num_blocks)
        assert alloc.refcount(self.sink) == 1
        bt = self.block_tokens
        lens = np.asarray(self.cache.seq_lens)
        for slot, req in running.items():
            tbl = self.mgr.block_ids(req.rid)
            assert len(tbl) * bt >= req.tokens_held
            assert all(alloc.is_allocated(b) for b in tbl)
            assert lens[slot] == req.tokens_held, (slot, lens[slot],
                                                   req.tokens_held)
        if not self._all_dirty:
            # resident shadow vs host truth: a missed mark_dirty hook
            # surfaces HERE, not as a silent wrong-block read
            dev = np.asarray(self.cache.block_tables)
            for slot, req in running.items():
                if slot in self._dirty:
                    continue            # scatter staged for next sync
                want = self.mgr.device_table(req.rid)
                assert np.array_equal(dev[slot], want), (
                    f"slot {slot}: resident table diverged from mapping "
                    f"truth (missed dirty mark?)")
        transfers = self.arena.transfers
        transit = set(transfers.in_transit(self.mgr.pool_class))
        assert len(self.store) + len(transit) == len(self.mgr.swapped)
        for rid in self.mgr.swapped:
            assert rid in self.store or rid in transit
        pending_dst = transfers.in_flight_blocks(self.mgr.pool_class)
        for rid in self.mgr.tables:
            for lease in self.mgr.mapping(rid).leases:
                if lease.in_flight:
                    assert lease.block in pending_dst, (
                        f"rid {rid}: lease {lease!r} flagged in-flight "
                        f"but no pending plan targets it")
        for rid in self.mgr.prefetched_ids():
            m = self.mgr.mapping(rid)
            assert rid in self.store, (
                f"rid {rid}: prefetched but its host payload is gone")
            for lease in m._spec:
                if lease.in_flight:
                    assert lease.block in pending_dst, (
                        f"rid {rid}: speculative lease {lease!r} flagged "
                        f"in-flight but no pending plan targets it")
        self.arena.check_registry(self.mgr.pool_class)


def _state_step(model, p, tokens, pool, rows):
    """Decode the states of pool blocks ``rows`` and write the new states
    back -> (logits, pool).  Written back every step: the pool stays
    authoritative, so a later swap-out gather always reads the current
    state."""
    if getattr(model, "state_block", None) is not None:
        return model.decode_step_in_pool(p, tokens, pool, rows)
    state = model.rows_to_state(pool[rows, 0])
    logits, new_state = model.decode_step(p, tokens, state)
    return logits, pool.at[rows, 0].set(model.state_to_rows(new_state))


def constant_state_decode_fn(model):
    """The constant-state discipline's fused decode: one jitted,
    pool-donated trace of row delta-scatter -> decode step with the state
    read and written back (``_state_step``) -> argmax.  The row index
    vector stays latched on device between steps."""

    def constant_state_decode(p, tokens, pool, rows, upd_slots, upd_rows):
        rows = rows.at[upd_slots].set(upd_rows, mode="drop")
        logits, pool = _state_step(model, p, tokens, pool, rows)
        return jnp.argmax(logits, axis=-1), pool, rows

    return jax.jit(constant_state_decode, donate_argnums=(2,))


class ConstantStateStrategy(CacheStrategy):
    """Fixed-size recurrent state, one block per sequence (SSM models).

    The pool IS the authoritative device state: every decode reads each
    running slot's state, steps the model, and writes the new state
    back -- so a swap-out gather at any step boundary reads the current
    state, and a resume is one block's scatter.  Footprint is EXACT (1
    block, never grows): admission applies no watermark to this class,
    and preemption of one sequence always frees exactly what the next
    admission of its kind needs.

    A model that says where each layer's state lives in a block
    (``state_block``, with ``decode_step_in_pool``) gets a layered pool:
    its decode reads and writes each layer's state in place inside the
    layer scan (``layered`` True).  Any other gets the flat ``(NB, 1,
    E)`` pool, whose decode gathers whole rows, relays them into the
    model's state (``rows_to_state``) and scatters them back.
    """

    def __init__(self, model, *, arena: Arena, slots: int, max_seq: int,
                 num_blocks: int, dp_groups: int = 1, pool_prefix: str = ""):
        if dp_groups > 1:
            raise NotImplementedError(
                "constant-state serving is single-pool-group for now")
        self.model = model
        self.arena = arena
        self.slots = slots
        block = getattr(model, "state_block", None)
        self.layered = block is not None
        self.mgr = ConstantStateManager(arena, pool_prefix + "state",
                                        model.state_elems, num_blocks,
                                        block=block)
        self._sink = self.mgr.reserve_sink()
        self.store = HostBlockStore(arena, self.mgr.pool_class)
        self.pool_classes = [self.mgr.pool_class]
        self.growing_classes = frozenset()      # footprint is exact
        # padded prefill must keep the SSD chunk divisibility invariant
        self._pad = max(1, getattr(model.cfg.ssm, "chunk", 1))
        self._init_resident()
        self._upd = None
        self._rows_dev = None           # device-resident row indices
        self._fused = None              # cached fused decode jit
        self._prefill = None            # cached prefill jit

    @property
    def sink(self) -> int:
        return self._sink.block

    @property
    def block_tokens(self) -> int:
        """No paged table: prefix granularity is irrelevant (the
        recurrent state folds the whole prefix), but the engine's
        bookkeeping wants a positive quantum."""
        return 1

    @property
    def utilization(self) -> float:
        return self.mgr.utilization

    @property
    def swapped(self) -> dict:
        return self.mgr.swapped

    # -- admission view --
    def footprint(self, req) -> Dict[str, int]:
        return {self.mgr.pool_class: 1}

    def free_by_class(self) -> Dict[str, int]:
        return {self.mgr.pool_class: (self.mgr.free_blocks
                                      + self.mgr.speculative_blocks)}

    # -- lifecycle --
    def admit(self, rid, prompt_tokens, tenant):
        self.mgr.admit(rid, prompt_tokens, tenant=tenant)

    def extend(self, rid, total_tokens):
        return []                       # constant: zero growth, ever

    def ensure_writable(self, rid, token_pos):
        return None                     # nothing is ever COW-shared

    def release(self, rid):
        self.mgr.release(rid)

    def has_seq(self, rid):
        return self.mgr.has_seq(rid)

    # -- swap / speculation --
    def swap_out(self, rid):
        self.mgr.swap_out(rid)

    def swap_in(self, rid):
        self.mgr.swap_in(rid)

    def is_prefetched(self, rid):
        return self.mgr.is_prefetched(rid)

    def commit_prefetch(self, rid):
        return self.mgr.commit_prefetch(rid)

    def cancel_prefetch(self, rid):
        self.mgr.cancel_prefetch(rid)

    def prefetched_ids(self):
        return self.mgr.prefetched_ids()

    def prefetch_viable(self, rid, watermark):
        if self.mgr.is_prefetched(rid) or rid not in self.mgr.swapped:
            return False
        if self.store.in_transit(rid):
            return False
        return self.mgr.free_blocks - 1 >= watermark

    def prefetch(self, rid):
        self.mgr.prefetch(rid)

    # -- per-step mechanism --
    def sync_device_state(self, running):
        for slot, req in running.items():
            self.mgr.mapping(req.rid).assert_settled()
        # every decode scatters fresh state into every running row
        self.mgr.allocator.note_write(
            [int(self.mgr.row(req.rid)) for req in running.values()])
        upd = self._take_dirty(running)
        W = self._bucket(len(upd), self.slots)
        upd_slots = np.full(W, self.slots, np.int32)   # pad -> dropped
        upd_rows = np.full(W, self.sink, np.int32)
        for i, slot in enumerate(sorted(upd)):
            upd_slots[i] = slot
            if slot in running:
                upd_rows[i] = self.mgr.row(running[slot].rid)
        self._upd = (upd_slots, upd_rows)
        return len(upd), (upd_slots.nbytes + upd_rows.nbytes if W else 0)

    def _fused_fn(self):
        if self._fused is None:
            self._fused = constant_state_decode_fn(self.model)
        return self._fused

    def decode_resident(self, params, tokens):
        if self._rows_dev is None:
            self._rows_dev = jnp.full((self.slots,), self.sink, jnp.int32)
        upd_slots, upd_rows = self._upd
        nxt, self.mgr.pool, self._rows_dev = self._fused_fn()(
            params, tokens, self.mgr.pool, self._rows_dev,
            jnp.asarray(upd_slots), jnp.asarray(upd_rows))
        return nxt

    def _prefill_fn(self):
        """One jitted, pool-donated program per (rows, width): zero state
        -> padded prefill -> argmax of each row's last logits -> state
        scatter into the pool blocks, in place, in the pool's layout."""
        if self._prefill is None:
            model = self.model
            to_blocks = (model.state_to_blocks if self.layered
                         else lambda st: model.state_to_rows(st)[:, None])

            def constant_state_prefill(p, toks, lengths, pool, idx):
                state0 = model.init_state(toks.shape[0])
                last, state = model.prefill(p, {"tokens": toks}, state0,
                                            lengths)
                pool = pool.at[idx].set(to_blocks(state))
                return jnp.argmax(last, axis=-1), pool

            self._prefill = jax.jit(constant_state_prefill,
                                    donate_argnums=(3,))
        return self._prefill

    def prefill(self, params, batch):
        """Padded batched prefill from zero state; ``lengths`` masks the
        right padding out of the SSM scan exactly, so this is
        token-identical to per-sequence prefill."""
        lens = np.asarray([req.tokens_held for _, req, _ in batch], np.int32)
        toks = np.zeros((len(batch), self.prefill_width(int(lens.max()))),
                        np.int32)
        for row, (_, req, _) in enumerate(batch):
            toks[row, : lens[row]] = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.generated, np.int32)])
        idx = np.asarray([self.mgr.row(req.rid) for _, req, _ in batch],
                         np.int32)
        nxt, self.mgr.pool = self._prefill_fn()(params, toks, lens,
                                                self.mgr.pool, idx)
        return np.asarray(nxt), int(lens.sum())

    # -- restart / teardown / audit --
    def adopt_restored(self, rid) -> None:
        m = self.arena.find_mapping(self.mgr.pool_class, rid)
        if m is None or m.placement != "host":
            raise ValueError(
                f"no restored host-resident mapping for rid {rid}; "
                f"run Arena.restore first (device-resident sequences do "
                f"not survive a restart -- re-submit them)")
        self.mgr.adopt(rid, m)

    def adopt_device(self, rid) -> None:
        m = self.arena.find_mapping(self.mgr.pool_class, rid)
        if m is None or m.placement != "device":
            raise ValueError(
                f"no device-resident mapping for rid {rid}; restore a "
                f"device snapshot (live migration) or adopt_payload a "
                f"handoff bundle first")
        self.mgr.adopt(rid, m)

    def release_arena(self) -> None:
        self.arena.transfers.unregister_executor(self.mgr.pool_class)
        self.arena.transfers.remove_observer(
            f"swap-ledger:{self.mgr.pool_class}")

    def check_consistency(self, running) -> None:
        alloc = self.mgr.allocator
        assert (alloc.num_used + alloc.num_free + alloc.num_held
                == alloc.num_blocks)
        assert alloc.refcount(self.sink) == 1
        for slot, req in running.items():
            m = self.mgr.mapping(req.rid)
            assert len(m) == 1 and m.placement == "device"
            assert alloc.is_allocated(m.block_ids()[0])
        if not self._all_dirty and self._rows_dev is not None:
            dev = np.asarray(self._rows_dev)
            for slot, req in running.items():
                if slot in self._dirty:
                    continue
                assert dev[slot] == self.mgr.row(req.rid), (
                    f"slot {slot}: resident state row diverged from "
                    f"mapping truth (missed dirty mark?)")
        transfers = self.arena.transfers
        transit = set(transfers.in_transit(self.mgr.pool_class))
        assert len(self.store) + len(transit) == len(self.mgr.swapped)
        for rid in self.mgr.swapped:
            assert rid in self.store or rid in transit
        self.arena.check_registry(self.mgr.pool_class)


class CompositeStrategy(CacheStrategy):
    """Hybrid: a growing paged-KV class AND a constant-state class,
    admitted, swapped, preempted and released together (zamba2).

    The watermark applies only to the KV class; the state side's
    footprint is exact.  Prefix sharing is off: the recurrent state
    depends on the entire prefix, so aliasing KV blocks alone would
    serve the wrong state.  Speculative prefetch is off for the same
    compound reason (a half-arrived sequence is unusable) -- demand
    swap-in moves both classes' plans in one dispatch.
    """

    def __init__(self, model, *, arena: Arena, slots: int, max_seq: int,
                 num_blocks: int, dp_groups: int = 1, pool_prefix: str = "",
                 state_blocks: Optional[int] = None):
        if dp_groups > 1:
            raise NotImplementedError(
                "hybrid serving is single-pool-group for now")
        self.model = model
        self.arena = arena
        self.slots = slots
        kvcfg = model.kv_config(max_seq=max_seq, num_blocks=num_blocks,
                                batch=slots, dp_groups=dp_groups)
        self.cache = PagedKVCache.create(kvcfg, slots)
        self.mgr = PagedKVManager(kvcfg, arena=arena,
                                  pool_class=pool_prefix + "kv")
        self._kv_sink = self.mgr.reserve_sink()
        # device rows: resident slots + one in-flight resume + sink
        self.state_mgr = ConstantStateManager(
            arena, pool_prefix + "state", model.state_elems,
            state_blocks if state_blocks is not None else 2 * slots + 2)
        self._state_sink = self.state_mgr.reserve_sink()
        self.store = HostBlockStore(arena, self.mgr.pool_class)
        self.state_store = HostBlockStore(arena, self.state_mgr.pool_class)
        self.pool_classes = [self.mgr.pool_class, self.state_mgr.pool_class]
        self.growing_classes = frozenset([self.mgr.pool_class])
        bt = kvcfg.block_tokens
        chunk = max(1, getattr(model.cfg.ssm, "chunk", 1))
        self._pad = bt * chunk // math.gcd(bt, chunk)
        # resident tables start all-sink / length-0 (see PagedKVStrategy)
        self.cache = dataclasses.replace(
            self.cache,
            block_tables=jnp.full_like(self.cache.block_tables, self.sink),
            seq_lens=jnp.zeros_like(self.cache.seq_lens))
        self._init_resident()
        self._upd = None
        self._rows_dev = None
        self._fused = None
        arena.transfers.register_executor(
            self.mgr.pool_class, self._streams, self._set_streams)

    def _streams(self):
        c = self.cache
        return [c.k_pool] + ([c.v_pool] if c.v_pool is not None else [])

    def _set_streams(self, streams) -> None:
        k, *rest = streams
        self.cache = dataclasses.replace(
            self.cache, k_pool=k, v_pool=rest[0] if rest else None)

    @property
    def sink(self) -> int:
        return self._kv_sink.block

    @property
    def state_sink(self) -> int:
        return self._state_sink.block

    @property
    def block_tokens(self) -> int:
        return self.cache.config.block_tokens

    @property
    def utilization(self) -> float:
        return self.mgr.utilization

    @property
    def swapped(self) -> dict:
        return self.mgr.swapped       # state residency mirrors kv 1:1

    # -- admission view --
    def footprint(self, req) -> Dict[str, int]:
        return {self.mgr.pool_class: self.mgr.blocks_needed(req.max_tokens),
                self.state_mgr.pool_class: 1}

    def free_by_class(self) -> Dict[str, int]:
        return {self.mgr.pool_class: self.mgr.free_blocks,
                self.state_mgr.pool_class: self.state_mgr.free_blocks}

    # -- lifecycle (both classes, always together) --
    def admit(self, rid, prompt_tokens, tenant):
        self.mgr.admit(rid, prompt_tokens, tenant=tenant)
        try:
            self.state_mgr.admit(rid, tenant=tenant)
        except OutOfBlocksError:
            self.mgr.release(rid)     # atomic: no half-admitted hybrid
            raise

    def extend(self, rid, total_tokens):
        return self.mgr.extend(rid, total_tokens)

    def ensure_writable(self, rid, token_pos):
        return None                   # no prefix sharing -> never shared

    def release(self, rid):
        self.mgr.release(rid)
        self.state_mgr.release(rid)

    def has_seq(self, rid):
        return self.mgr.has_seq(rid)

    # -- swap (both classes ride the same dispatch) --
    def swap_out(self, rid):
        self.mgr.swap_out(rid)
        self.state_mgr.swap_out(rid)

    def swap_in(self, rid):
        self.mgr.swap_in(rid)
        self.state_mgr.swap_in(rid)

    # -- per-step mechanism --
    def sync_device_state(self, running):
        """Both disciplines' deltas ride ONE update-slot vector: a slot
        is dirty for its KV table and its state row together (admission,
        swap and release move both classes atomically)."""
        bt = self.cache.config.block_tokens
        kv_writes, st_writes = [], []
        for slot, req in running.items():
            self.mgr.mapping(req.rid).assert_settled()
            self.state_mgr.mapping(req.rid).assert_settled()
            kv_writes.append(int(self.mgr.block_ids(req.rid)
                                 [req.tokens_held // bt]))
            st_writes.append(int(self.state_mgr.row(req.rid)))
        self.mgr.allocator.note_write(kv_writes)
        self.state_mgr.allocator.note_write(st_writes)
        upd = self._take_dirty(running)
        mb = self.cache.config.max_blocks_per_seq
        W = self._bucket(len(upd), self.slots)
        upd_slots = np.full(W, self.slots, np.int32)   # pad -> dropped
        upd_tables = np.full((W, mb), self.sink, np.int32)
        upd_lens = np.zeros(W, np.int32)
        upd_rows = np.full(W, self.state_sink, np.int32)
        for i, slot in enumerate(sorted(upd)):
            upd_slots[i] = slot
            if slot in running:
                req = running[slot]
                upd_tables[i] = self.mgr.device_table(req.rid)
                upd_lens[i] = req.tokens_held
                upd_rows[i] = self.state_mgr.row(req.rid)
        self._upd = (upd_slots, upd_tables, upd_lens, upd_rows)
        nbytes = (upd_slots.nbytes + upd_tables.nbytes + upd_lens.nbytes
                  + upd_rows.nbytes if W else 0)
        return len(upd), nbytes

    def _fused_fn(self):
        """One jitted trace for the hybrid tail: table/len/row
        delta-scatter -> state gather -> decode (KV append inside) ->
        state scatter-back -> argmax; the KV cache and state pool are
        both donated."""
        if self._fused is None:
            from repro.models.zamba2 import ZambaState
            model = self.model

            def composite_decode(p, tokens, cache, pool, rows, upd_slots,
                                 upd_tables, upd_lens, upd_rows):
                tables = cache.block_tables.at[upd_slots].set(
                    upd_tables, mode="drop")
                lens = cache.seq_lens.at[upd_slots].set(upd_lens,
                                                        mode="drop")
                rows = rows.at[upd_slots].set(upd_rows, mode="drop")
                cache = dataclasses.replace(cache, block_tables=tables,
                                            seq_lens=lens)
                conv, ssd = model.rows_to_state(pool[rows, 0])
                logits, st = model.decode_step(
                    p, tokens, ZambaState(conv, ssd, cache))
                pool = pool.at[rows, 0].set(
                    model.state_to_rows(st.conv, st.ssd))
                return jnp.argmax(logits, axis=-1), st.kv, pool, rows

            self._fused = jax.jit(composite_decode, donate_argnums=(2, 3))
        return self._fused

    def decode_resident(self, params, tokens):
        if self._rows_dev is None:
            self._rows_dev = jnp.full((self.slots,), self.state_sink,
                                      jnp.int32)
        upd_slots, upd_tables, upd_lens, upd_rows = self._upd
        nxt, self.cache, self.state_mgr.pool, self._rows_dev = (
            self._fused_fn()(params, tokens, self.cache,
                             self.state_mgr.pool, self._rows_dev,
                             jnp.asarray(upd_slots),
                             jnp.asarray(upd_tables),
                             jnp.asarray(upd_lens),
                             jnp.asarray(upd_rows)))
        return nxt

    def prefill(self, params, batch):
        """One padded call writes BOTH disciplines: paged KV lands in
        each row's private blocks (padding scatters to the kv sink) and
        the recurrent state rows scatter into the state pool."""
        from repro.models.zamba2 import ZambaState
        cfg = self.cache.config
        lens = [req.tokens_held for _, req, _ in batch]
        S = self.prefill_width(max(lens))
        toks = np.zeros((len(batch), S), np.int64)
        tables = np.full((len(batch), cfg.max_blocks_per_seq), self.sink,
                         np.int32)
        for row, (_, req, _) in enumerate(batch):
            toks[row, : lens[row]] = np.concatenate(
                [np.asarray(req.prompt, np.int64),
                 np.asarray(req.generated, np.int64)])
            tbl = self.mgr.device_table(req.rid)
            keep = tbl != NULL_BLOCK
            tables[row, keep] = tbl[keep]
        view = PagedKVCache(self.cache.k_pool, self.cache.v_pool,
                            jnp.asarray(tables),
                            jnp.zeros((len(batch),), jnp.int32), cfg)
        conv, ssd = self.model.init_recurrent(len(batch))
        last, state = self.model.prefill(
            params, {"tokens": jnp.asarray(toks)},
            ZambaState(conv, ssd, view), jnp.asarray(lens, jnp.int32))
        nxt = np.asarray(jnp.argmax(last, axis=-1))   # forces completion
        self.cache = dataclasses.replace(self.cache,
                                         k_pool=state.kv.k_pool,
                                         v_pool=state.kv.v_pool)
        idx = jnp.asarray([self.state_mgr.row(req.rid)
                           for _, req, _ in batch], jnp.int32)
        self.state_mgr.pool = self.state_mgr.pool.at[idx, 0].set(
            self.model.state_to_rows(state.conv, state.ssd))
        return nxt, sum(lens)

    # -- restart / teardown / audit --
    def adopt_restored(self, rid) -> None:
        for mgr in (self.mgr, self.state_mgr):
            m = self.arena.find_mapping(mgr.pool_class, rid)
            if m is None or m.placement != "host":
                raise ValueError(
                    f"no restored host-resident {mgr.pool_class!r} "
                    f"mapping for rid {rid}; run Arena.restore first")
        self.mgr.adopt(rid, self.arena.find_mapping(self.mgr.pool_class,
                                                    rid))
        self.state_mgr.adopt(
            rid, self.arena.find_mapping(self.state_mgr.pool_class, rid))

    def adopt_device(self, rid) -> None:
        for mgr in (self.mgr, self.state_mgr):
            m = self.arena.find_mapping(mgr.pool_class, rid)
            if m is None or m.placement != "device":
                raise ValueError(
                    f"no device-resident {mgr.pool_class!r} mapping for "
                    f"rid {rid}; restore a device snapshot first")
        self.mgr.adopt(rid, self.arena.find_mapping(self.mgr.pool_class,
                                                    rid))
        self.state_mgr.adopt(
            rid, self.arena.find_mapping(self.state_mgr.pool_class, rid))

    def release_arena(self) -> None:
        for cls in self.pool_classes:
            self.arena.transfers.unregister_executor(cls)
            self.arena.transfers.remove_observer(f"swap-ledger:{cls}")

    def check_consistency(self, running) -> None:
        for mgr, sink in ((self.mgr, self.sink),
                          (self.state_mgr, self.state_sink)):
            alloc = mgr.allocator
            assert (alloc.num_used + alloc.num_free + alloc.num_held
                    == alloc.num_blocks)
            assert alloc.refcount(sink) == 1
            self.arena.check_registry(mgr.pool_class)
        bt = self.block_tokens
        lens = np.asarray(self.cache.seq_lens)
        for slot, req in running.items():
            tbl = self.mgr.block_ids(req.rid)
            assert len(tbl) * bt >= req.tokens_held
            assert lens[slot] == req.tokens_held
            assert len(self.state_mgr.mapping(req.rid)) == 1
        if not self._all_dirty:
            dev = np.asarray(self.cache.block_tables)
            rows_dev = (np.asarray(self._rows_dev)
                        if self._rows_dev is not None else None)
            for slot, req in running.items():
                if slot in self._dirty:
                    continue
                want = self.mgr.device_table(req.rid)
                assert np.array_equal(dev[slot], want), (
                    f"slot {slot}: resident KV table diverged")
                if rows_dev is not None:
                    assert rows_dev[slot] == self.state_mgr.row(req.rid), (
                        f"slot {slot}: resident state row diverged")
        transfers = self.arena.transfers
        for mgr, store in ((self.mgr, self.store),
                           (self.state_mgr, self.state_store)):
            transit = set(transfers.in_transit(mgr.pool_class))
            assert len(store) + len(transit) == len(mgr.swapped)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SupportedArchitecture:
    """One registry row: model family -> cache discipline -> pools."""
    key: str                 # family or ssm kind this row matches
    strategy: type           # CacheStrategy subclass
    pool_suffixes: Tuple[str, ...]   # class names (pool_prefix prepended)
    description: str
    served: bool = True      # False: recognized but not engine-servable


ARCHITECTURES: Tuple[SupportedArchitecture, ...] = (
    SupportedArchitecture(
        "dense", PagedKVStrategy, ("kv",),
        "decoder transformers (dense/MoE/MLA/VLM): growing paged KV, "
        "COW prefix sharing, suffix prefill"),
    SupportedArchitecture(
        "mamba2", ConstantStateStrategy, ("state",),
        "pure SSM (mamba2): one constant state block per sequence, "
        "exact footprint, zero watermark pressure"),
    SupportedArchitecture(
        "hybrid", CompositeStrategy, ("kv", "state"),
        "zamba2 hybrid: paged KV for the shared-attention streams + "
        "constant state for the Mamba2 backbone"),
    SupportedArchitecture(
        "audio", CompositeStrategy, ("kv", "xattn"),
        "whisper: paged self-attention KV + read-only cross-attention "
        "segment (deposit once at encode, COW-share to decode beams)",
        served=False),
    SupportedArchitecture(
        "rwkv6", ConstantStateStrategy, ("state",),
        "RWKV6: one constant state block per sequence (shift vectors + "
        "wkv matrix state), length-masked padded prefill"),
)


def resolve(model) -> SupportedArchitecture:
    """Registry lookup from the model's config -- the ONLY dispatch
    point between model family and cache discipline (the engine itself
    has no isinstance-on-model cases left)."""
    cfg = model.cfg
    key = cfg.family
    if getattr(cfg, "ssm", None) is not None and key not in ("hybrid",):
        key = cfg.ssm.kind
    for row in ARCHITECTURES:
        if row.key == key:
            return row
    # plain decoder families (dense/moe/vlm/...) all serve paged KV
    if hasattr(model, "kv_config"):
        return ARCHITECTURES[0]
    raise NotImplementedError(
        f"no cache strategy registered for model family {cfg.family!r}")


def build_strategy(model, *, arena: Arena, slots: int, max_seq: int,
                   num_blocks: int, dp_groups: int = 1,
                   pool_prefix: str = "",
                   state_blocks: Optional[int] = None) -> CacheStrategy:
    """Resolve and construct the model's strategy over ``arena``."""
    row = resolve(model)
    if not row.served:
        raise NotImplementedError(
            f"architecture {row.key!r} is registered but not servable: "
            f"{row.description}")
    kw = dict(arena=arena, slots=slots, max_seq=max_seq,
              num_blocks=num_blocks, dp_groups=dp_groups,
              pool_prefix=pool_prefix)
    if row.strategy is CompositeStrategy:
        kw["state_blocks"] = state_blocks
    return row.strategy(model, **kw)
