"""The unified software address space (repro.mem): Arena/Lease/Mapping.

Three layers of pins:

  * the grep-enforced API rule: NOTHING outside ``src/repro/mem``
    constructs ``BlockAllocator``/``BlockPool`` directly -- every client
    allocates through one shared ``Arena``;
  * unit semantics: typed leases (exclusive/COW-shared/pinned), mapping
    verbs (``fork`` / ``ensure_writable`` / ``migrate``), pressure-time
    reclaim (the LIFO-preemption fallback as Arena policy), compaction
    lease rewrite, and the leak invariant ``assert_quiescent``;
  * regressions: ``OutOfBlocksError`` mid fork+extend must not leak or
    corrupt, exhaustion without a reclaimer must leave state untouched.
"""

import re
from pathlib import Path

import numpy as np
import pytest

from repro.mem import (COW_SHARED, EXCLUSIVE, PINNED, Arena,
                       LeaseRevokedError, OutOfBlocksError)

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# the API rule, grep-enforced
# ---------------------------------------------------------------------------
def test_no_direct_allocator_construction_outside_mem():
    """Zero direct BlockAllocator/BlockPool construction outside
    src/repro/mem: the Arena is the only allocator factory."""
    pattern = re.compile(
        r"\b(?:BlockAllocator|BlockPool)\s*\(|\bBlockPool\.create\s*\(")
    mem_dir = REPO / "src" / "repro" / "mem"
    offenders = []
    for root in ("src/repro", "examples"):
        for path in sorted((REPO / root).rglob("*.py")):
            if mem_dir in path.parents:
                continue
            for i, line in enumerate(path.read_text().splitlines(), 1):
                if pattern.search(line):
                    offenders.append(
                        f"{path.relative_to(REPO)}:{i}: {line.strip()}")
    assert not offenders, (
        "direct BlockAllocator/BlockPool construction outside repro.mem "
        "(allocate through a shared Arena instead):\n" + "\n".join(offenders))


# ---------------------------------------------------------------------------
# leases
# ---------------------------------------------------------------------------
def _arena(n=8, cls="kv"):
    a = Arena()
    a.register_class(cls, num_blocks=n, block_nbytes=64)
    return a


def test_register_class_idempotent_and_loud_on_conflict():
    a = _arena()
    assert a.register_class("kv", num_blocks=8, block_nbytes=64) == "kv"
    with pytest.raises(ValueError):
        a.register_class("kv", num_blocks=16, block_nbytes=64)
    with pytest.raises(KeyError):
        a.num_free("unregistered")


def test_lease_kinds_and_refcounts():
    a = _arena()
    [lease] = a.lease_blocks("kv", owner=0)
    assert lease.kind == EXCLUSIVE and not lease.shared
    alias = lease.share(owner=1)
    assert lease.kind == COW_SHARED == alias.kind
    assert a.refcount("kv", lease.block) == 2
    alias.release()
    assert lease.kind == EXCLUSIVE
    lease.release()
    with pytest.raises(ValueError):
        lease.release()                     # double release is loud
    a.assert_quiescent()


def test_pinned_lease_survives_quiescence():
    a = _arena()
    sink = a.pin("kv", owner="sink")
    assert sink.kind == PINNED
    with pytest.raises(ValueError):
        sink.share(owner=1)                 # pinned blocks never alias
    a.assert_quiescent()                    # pinned is not a leak
    a.unpin(sink)
    assert a.num_used("kv") == 0


# ---------------------------------------------------------------------------
# mapping verbs
# ---------------------------------------------------------------------------
def test_mapping_fork_and_write_barrier():
    a = _arena()
    parent = a.mapping("kv", owner=0)
    parent.ensure_capacity(3)
    used = a.num_used("kv")
    child = parent.fork(owner=1, nblocks=2)     # pure refcount traffic
    assert a.num_used("kv") == used
    assert child.block_ids() == parent.block_ids()[:2]
    assert parent.locality() == 1.0             # fresh allocs are adjacent

    plan = child.ensure_writable(1)             # divergent write -> copy
    src, dst = plan
    assert src == parent.block_ids()[1] and dst == child.block_ids()[1]
    assert dst not in parent.block_ids()
    assert child.ensure_writable(1) is None     # now exclusive
    child.free()
    parent.free()
    a.assert_quiescent()


def test_mapping_migrate_roundtrip_relocates():
    a = _arena()
    m = a.mapping("kv", owner=7)
    m.ensure_capacity(3)
    # stranger occupies the vacated ids so re-materialization relocates
    old = m.migrate("host")
    assert m.placement == "host" and len(m) == 3
    assert a.host_counts("kv") == {7: 3}
    stranger = a.mapping("kv", owner=8)
    stranger.ensure_capacity(2)
    new = m.migrate("device")
    assert m.placement == "device" and len(new) == 3
    assert set(new) & set(stranger.block_ids()) == set()
    assert new != old                           # tables absorb relocation
    m.free()
    stranger.free()
    a.assert_quiescent()


def test_fork_oob_during_extend_regression():
    """OutOfBlocksError between fork() and the child's extension must
    leave the address space consistent: the child holds only its shared
    prefix, the parent is untouched, and releasing both drains to zero."""
    a = _arena(n=4)
    parent = a.mapping("kv", owner=0)
    parent.ensure_capacity(3)                   # 3 of 4 used
    filler = a.mapping("kv", owner=9)
    filler.ensure_capacity(1)                   # pool now full
    child = parent.fork(owner=1, nblocks=3)     # shares: needs no blocks
    with pytest.raises(OutOfBlocksError):
        child.ensure_capacity(4)                # +1 block: exhausted
    # nothing leaked, nothing corrupted
    assert child.block_ids() == parent.block_ids()
    assert all(a.refcount("kv", b) == 2 for b in parent.block_ids())
    with pytest.raises(OutOfBlocksError):
        child.ensure_writable(0)                # COW target also exhausted
    assert child.block_ids() == parent.block_ids()   # barrier rolled back
    child.free()
    assert all(a.refcount("kv", b) == 1 for b in parent.block_ids())
    parent.free()
    filler.free()
    a.assert_quiescent()


# ---------------------------------------------------------------------------
# pressure protocol: the LIFO-preemption fallback as Arena policy
# ---------------------------------------------------------------------------
def test_pressure_reclaims_victims_until_fit():
    a = _arena(n=4)
    victim = a.mapping("kv", owner="victim")
    victim.ensure_capacity(3)
    reclaimed = []

    def reclaimer(requester):
        reclaimed.append(requester)
        victim.migrate("host")                  # frees 3 blocks
        return "victim"

    a.set_reclaimer(reclaimer)
    m = a.mapping("kv", owner="req")
    m.ensure_capacity(3)                        # 3 > 1 free -> reclaim
    assert reclaimed == ["req"]
    assert a.host_counts("kv") == {"victim": 3}
    victim.free()
    m.free()
    a.assert_quiescent()


def test_pressure_self_reclaim_raises_lease_revoked():
    a = _arena(n=2)
    m = a.mapping("kv", owner="self")
    m.ensure_capacity(2)

    def reclaimer(requester):
        m.migrate("host")                       # the requester itself
        return "self"

    a.set_reclaimer(reclaimer)
    with pytest.raises(LeaseRevokedError):
        m.ensure_capacity(3)
    assert m.placement == "host"                # already swapped out
    # LeaseRevokedError IS an OutOfBlocksError for legacy catch sites
    assert issubclass(LeaseRevokedError, OutOfBlocksError)
    m.free()
    a.assert_quiescent()


def test_no_reclaimer_means_plain_oob():
    a = _arena(n=2)
    m = a.mapping("kv", owner=0)
    with pytest.raises(OutOfBlocksError):
        m.ensure_capacity(3)
    assert len(m) == 0 and a.num_used("kv") == 0    # atomic failure
    m.free()
    a.assert_quiescent()


# ---------------------------------------------------------------------------
# compaction: the ROADMAP defrag pass
# ---------------------------------------------------------------------------
def test_compact_rewrites_leases_to_dense_prefix():
    a = _arena(n=16)
    keep = a.mapping("kv", owner="keep")
    keep.ensure_capacity(2)
    hole = a.mapping("kv", owner="hole")
    hole.ensure_capacity(4)
    tail = a.mapping("kv", owner="tail")
    tail.ensure_capacity(3)
    shared = tail.fork(owner="alias", nblocks=2)
    hole.free()                                 # 4-block hole mid-pool
    assert a.fragmentation("kv") > 0
    assert a.should_compact("kv", min_free_frac=0.25, frag_threshold=0.1)

    before = {"keep": keep.block_ids(), "tail": tail.block_ids()}
    src, dst = a.compact("kv")
    assert len(src) > 0 and set(src).isdisjoint(set(dst))
    assert a.fragmentation("kv") == 0.0
    # live blocks now form the dense prefix
    used = a.allocator("kv").used_ids()
    assert list(used) == list(range(len(used)))
    # every mapping rewritten in place; aliasing preserved
    assert tail.block_ids()[:2] == shared.block_ids()
    remap = dict(zip(src.tolist(), dst.tolist()))
    for name, m in (("keep", keep), ("tail", tail)):
        assert m.block_ids() == [remap.get(b, b) for b in before[name]]
    assert a.compactions == 1 and a.blocks_compacted == len(src)
    for m in (shared, tail, keep):
        m.free()
    a.assert_quiescent()


def test_compact_refuses_untracked_blocks():
    a = _arena(n=8)
    m = a.mapping("kv", owner=0)
    m.ensure_capacity(1)
    m2 = a.mapping("kv", owner=1)
    m2.ensure_capacity(2)
    m.free()
    a.allocator("kv").alloc()                   # raw escape hatch
    with pytest.raises(RuntimeError):
        a.compact("kv")


# ---------------------------------------------------------------------------
# stats surface
# ---------------------------------------------------------------------------
def test_arena_stats_surface():
    a = _arena(n=8)
    a.register_class("meta", num_blocks=4, block_nbytes=8)
    sink = a.pin("kv", owner="sink")
    m = a.mapping("kv", owner=3)
    m.ensure_capacity(2)
    child = m.fork(owner=4, nblocks=1)
    swapped = a.mapping("kv", owner=5)
    swapped.ensure_capacity(2)
    swapped.migrate("host")

    st = a.stats()
    kv = st["kv"]
    assert kv.num_blocks == 8 and kv.num_used == 3 and kv.pinned == 1
    assert kv.blocks_by_owner == {"sink": 1, "3": 2, "4": 1}
    assert kv.host_blocks_by_owner == {"5": 2} and kv.host_blocks == 2
    # refcount histogram: 5 free, 2 at refcount 1 (sink + private), 1
    # shared at refcount 2
    assert kv.refcount_histogram[0] == 5
    assert kv.refcount_histogram[1] == 2
    assert kv.refcount_histogram[2] == 1
    assert kv.mappings_by_kind == {"flat": 3}
    assert st["meta"].num_used == 0
    d = st.to_dict()
    assert d["classes"]["kv"]["num_used"] == 3
    for obj in (child, m, swapped):
        obj.free()
    a.unpin(sink)
    a.assert_quiescent()


def test_assert_quiescent_catches_leaks():
    a = _arena()
    m = a.mapping("kv", owner=0)
    m.ensure_capacity(1)
    with pytest.raises(AssertionError):
        a.assert_quiescent()
    m.migrate("host")
    with pytest.raises(AssertionError):
        a.assert_quiescent()                    # host tier counts too
    m.free()
    a.assert_quiescent()
