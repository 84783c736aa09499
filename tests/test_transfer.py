"""The asynchronous transfer plane (repro.mem.transfer).

Four layers of pins:

  * the grep-enforced API rule: NOTHING outside ``mem/transfer.py``
    (and the kernel definitions themselves) calls the block-copy
    kernels or the host tier's payload verbs -- every movement rides
    the Arena's ``TransferQueue``;
  * unit semantics: fences/epochs, eager (synchronous-fallback) mode,
    multi-plan coalescing with dependency breaks, metadata-only arenas,
    allocator holds on unfenced DMA sources;
  * the ORDERING property: any interleaving of enqueued plans, fences
    and (barriered) device writes yields block contents identical to
    the fully synchronous ``drain()`` schedule;
  * the read barrier: an unfenced read of an ``in_flight`` lease raises
    ``UnfencedReadError``.

Plus the checkpoint-on-arena roundtrip (``snapshot``/``restore``).
"""

import dataclasses
import re
from pathlib import Path

import numpy as np
import pytest
import jax.numpy as jnp

from repro.mem import (Arena, BACKGROUND, D2D, D2H, H2D, IN_FLIGHT,
                       URGENT, OutOfBlocksError, UnfencedReadError)
from _hypothesis_compat import given, settings, strategies as st

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# the API rule, grep-enforced
# ---------------------------------------------------------------------------
def test_no_direct_transfer_calls_outside_transfer_plane():
    """Zero direct block-copy-kernel / host-transfer calls outside
    mem/transfer.py: all four movement producers (migrate, swap, COW
    copy, compact) route through the TransferQueue."""
    kernel_call = re.compile(
        r"\b(?:gather_blocks|scatter_blocks|copy_pool_blocks|block_copy)"
        r"\s*\(")
    host_verb = re.compile(r"\bhost_(?:deposit|take|peek|discard)\s*\(")
    kernels_dir = REPO / "src" / "repro" / "kernels"
    mem_dir = REPO / "src" / "repro" / "mem"
    offenders = []
    for root in ("src/repro", "examples"):
        for path in sorted((REPO / root).rglob("*.py")):
            if kernels_dir in path.parents:
                continue                      # kernel definitions/wrappers
            in_mem = mem_dir in path.parents
            if in_mem and path.name == "transfer.py":
                continue                      # the one permitted executor
            for i, line in enumerate(path.read_text().splitlines(), 1):
                if kernel_call.search(line):
                    offenders.append(
                        f"{path.relative_to(REPO)}:{i}: {line.strip()}")
                # the host tier's own module may manage its payload dict;
                # everything outside repro.mem must go through plans
                if not in_mem and host_verb.search(line):
                    offenders.append(
                        f"{path.relative_to(REPO)}:{i}: {line.strip()}")
    assert not offenders, (
        "direct transfer calls outside the transfer plane (enqueue a "
        "TransferPlan on Arena.transfers instead):\n" + "\n".join(offenders))


# ---------------------------------------------------------------------------
# harness: an arena with a registered executor over real device streams
# ---------------------------------------------------------------------------
CLS = "kv"


def make_executor_arena(n=12, layers=1, blk=2, streams=1):
    a = Arena()
    a.register_class(CLS, num_blocks=n,
                     block_nbytes=layers * blk * 4 * streams)
    cell = {"streams": [jnp.zeros((layers, n, blk), jnp.float32)
                        for _ in range(streams)]}
    a.transfers.register_executor(
        CLS, lambda: list(cell["streams"]),
        lambda s: cell.update(streams=list(s)))
    return a, cell


def write_blocks(a, cell, mapping, value):
    """A device write through the engine's schedule: dispatch first
    (settles everything the write could race: pending d2d copies into
    or out of these blocks), then write."""
    a.transfers.dispatch()
    mapping.assert_settled()
    ids = jnp.asarray(mapping.block_ids(), jnp.int32)
    cell["streams"] = [s.at[:, ids].set(value) for s in cell["streams"]]


def contents(cell, ids):
    return [np.asarray(s)[:, np.asarray(ids, np.int32)]
            for s in cell["streams"]]


# ---------------------------------------------------------------------------
# fences / eager mode / holds
# ---------------------------------------------------------------------------
def test_empty_dispatch_phases_are_skipped():
    """Dispatch-count pin (first bite of the ROADMAP overlap gap: the
    step loop used to run ~49 fixpoint dispatches for 2 actual
    transfers at smoke scale).  A dispatch / fence / drain phase with
    nothing eligible must skip the fixpoint entirely and count
    NOTHING: the counters measure scheduling work, not step-loop
    calls."""
    a, cell = make_executor_arena()
    q = a.transfers
    # an idle step loop's worth of empty phases: all skipped
    for _ in range(25):
        q.dispatch()
        q.dispatch(lanes=(URGENT,))
        q.dispatch(lanes=(BACKGROUND,))
        q.complete_dispatched()
        q.drain()
    assert (q.stats.dispatches, q.stats.fences, q.stats.drains) == (0, 0, 0)

    # two real transfers cost exactly one phase each, no matter how
    # many no-op phases the loop schedules around them
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(2)
    write_blocks(a, cell, m, 5.0)      # its dispatch() is empty: skipped
    m.migrate("host")
    q.dispatch(lanes=(BACKGROUND,))    # wrong lane: still nothing to do
    assert q.stats.dispatches == 0
    q.dispatch()                       # launches the d2h gather
    q.dispatch()                       # nothing newly pending: skipped
    assert q.stats.dispatches == 1
    q.complete_dispatched()            # lands the host copy
    q.complete_dispatched()            # nothing dispatched: skipped
    assert q.stats.fences == 1
    assert a.host_contains(CLS, 0)
    m.migrate("device")                # enqueues the h2d scatter
    q.drain()
    q.drain()                          # plane empty again: skipped
    assert q.stats.drains == 1
    m.free()
    a.assert_quiescent()


def test_nonempty_dispatch_is_single_walk():
    """Extends the empty-phase pin above to NON-empty steps (the PR 7
    de-Pythonized step loop): N independent plans dispatched in one
    phase cost exactly N per-plan visits (``python_launches``) -- one
    walk per engine, not one walk per plan per fixpoint round, and no
    trailing no-progress verification round -- and
    ``dispatches_per_step`` reports dispatch phases per compute mark."""
    a, cell = make_executor_arena(n=16)
    q = a.transfers
    maps = []
    for i in range(4):
        m = a.mapping(CLS, owner=i)
        m.ensure_capacity(2)
        write_blocks(a, cell, m, float(i + 1))
        maps.append(m)
    base = q.stats.python_launches
    for m in maps:
        m.migrate("host")
    q.dispatch()                   # one walk batches 4 independent gathers
    assert q.stats.python_launches - base == 4
    assert q.stats.dispatches == 1
    q.complete_dispatched()
    assert all(a.host_contains(CLS, i) for i in range(4))
    # the derived per-step rate follows the compute-mark clock
    q.note_compute()
    q.note_compute()
    assert q.stats.dispatches_per_step == pytest.approx(
        q.stats.dispatches / 2)
    assert q.stats.to_dict()["python_launches"] == q.stats.python_launches
    for m in maps:
        m.migrate("device")
    q.drain()
    for m in maps:
        m.free()
    a.assert_quiescent()


def test_fence_epochs_and_drain():
    a, cell = make_executor_arena()
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(2)
    write_blocks(a, cell, m, 7.0)
    m.migrate("host")
    f = a.transfers.fence()
    assert not f.done and a.transfers.pending == 1
    assert not a.host_contains(CLS, 0)        # payload still in flight
    f.wait()
    assert f.done and a.transfers.pending == 0
    assert a.host_contains(CLS, 0)            # deposited at the fence
    m.free()
    a.assert_quiescent()


def test_eager_mode_is_synchronous():
    a, cell = make_executor_arena()
    a.transfers.eager = True
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(2)
    write_blocks(a, cell, m, 3.0)
    m.migrate("host")
    assert a.transfers.pending == 0           # drained inside enqueue
    assert a.host_contains(CLS, 0)
    m.migrate("device")
    assert a.transfers.pending == 0
    np.testing.assert_array_equal(contents(cell, m.block_ids())[0],
                                  np.full((1, 2, 2), 3.0, np.float32))
    m.free()
    a.assert_quiescent()


def test_swap_out_holds_sources_until_dispatch():
    """Vacated d2h sources are unallocatable until the gather launches;
    an allocation that needs them DISPATCHES the plane (non-blocking
    hold release -- the host copy stays overlapped, never a forced
    synchronous drain on the pressure path)."""
    a, cell = make_executor_arena(n=4)
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(3)
    write_blocks(a, cell, m, 5.0)
    old = m.migrate("host")
    alloc = a.allocator(CLS)
    assert alloc.num_held == 3 and a.num_free(CLS) == 1
    assert alloc.num_used + alloc.num_free + alloc.num_held == 4
    # per-engine hold attribution: the d2h queue owns all three, and
    # the ArenaStats surface reports the same split
    assert alloc.held_by_engine() == {"d2h": 3}
    assert a.stats()[CLS].held_by_engine == {"d2h": 3}
    # needs 3 blocks; only 1 unheld -> the arena dispatches the plane
    m2 = a.mapping(CLS, owner=1)
    m2.ensure_capacity(3)
    assert alloc.num_held == 0
    # the gather launched (ids reusable) but the host copy is still in
    # transit: allocation pressure does not force the synchronous path
    assert not a.host_contains(CLS, 0)
    assert 0 in a.transfers.in_transit(CLS)
    a.transfers.drain()
    assert a.host_contains(CLS, 0)
    np.testing.assert_array_equal(
        a._host_payload[(CLS, 0)][0][0],
        np.full((1, 3, 2), 5.0, np.float32)[:, :len(old)])
    m2.free()
    m.free()
    a.assert_quiescent()


def test_metadata_only_arena_completes_plans_inline():
    """No executor registered: plans complete immediately as
    residency-only moves (pure-policy arenas keep working)."""
    a = Arena()
    a.register_class("meta", num_blocks=4, block_nbytes=8)
    m = a.mapping("meta", owner=0)
    m.ensure_capacity(2)
    m.migrate("host")
    assert a.transfers.pending == 0
    assert a.transfers.stats.enqueued["d2h"] == 1
    assert a.transfers.stats.completed["d2h"] == 1
    m.migrate("device")
    assert a.transfers.pending == 0
    m.free()
    a.assert_quiescent()


# ---------------------------------------------------------------------------
# coalescing: the batched multi-plan launch, and its dependency break
# ---------------------------------------------------------------------------
def test_coalesced_copies_respect_dependencies():
    a, cell = make_executor_arena(n=6)
    cell["streams"] = [cell["streams"][0].at[:, 0].set(9.0)]
    # chain: 0 -> 1, then 1 -> 2 (reads the first copy's destination)
    a.transfers.enqueue_copy(CLS, [0], [1])
    a.transfers.enqueue_copy(CLS, [1], [2])
    # independent pair: may share the chain tail's launch
    a.transfers.enqueue_copy(CLS, [0], [3])
    a.transfers.drain()
    got = np.asarray(cell["streams"][0])
    for b in (1, 2, 3):
        np.testing.assert_array_equal(got[:, b],
                                      np.full((1, 2), 9.0, np.float32))
    st_ = a.transfers.stats
    assert st_.coalesced == 1                  # [1->2, 0->3] shared a launch
    assert st_.completed["d2d"] == 3


def test_multi_plan_gather_single_launch():
    """Two swap-outs enqueued back-to-back ride ONE device gather."""
    a, cell = make_executor_arena(n=8)
    m1 = a.mapping(CLS, owner=1)
    m1.ensure_capacity(2)
    write_blocks(a, cell, m1, 1.0)
    m2 = a.mapping(CLS, owner=2)
    m2.ensure_capacity(3)
    write_blocks(a, cell, m2, 2.0)
    launches_before = a.transfers.stats.launches
    m1.migrate("host")
    m2.migrate("host")
    a.transfers.dispatch()                     # one gather for both plans
    gather_launches = (a.transfers.stats.launches - launches_before)
    assert gather_launches == 1
    assert a.transfers.stats.coalesced >= 1
    a.transfers.complete_dispatched()
    k1 = a._host_payload[(CLS, 1)][0][0]
    k2 = a._host_payload[(CLS, 2)][0][0]
    np.testing.assert_array_equal(k1, np.full((1, 2, 2), 1.0, np.float32))
    np.testing.assert_array_equal(k2, np.full((1, 3, 2), 2.0, np.float32))
    m1.free()
    m2.free()
    a.assert_quiescent()


# ---------------------------------------------------------------------------
# multi-queue: cross-queue fences, the d2h reorder window, prefetch
# ---------------------------------------------------------------------------
def test_cross_queue_dependency_check_both_ways():
    """The enqueue-time dependency check: a d2h gather reading a block a
    pending d2d copy WRITES depends on the copy (launch strength); one
    that only shares READS does not.  This is the check that gates the
    reorder-window coalescing."""
    a, cell = make_executor_arena(n=8)
    m = a.mapping(CLS, owner=1)
    m.ensure_capacity(2)                       # blocks 0, 1
    write_blocks(a, cell, m, 1.0)
    a.transfers.enqueue_copy(CLS, [0], [2])    # d2d: writes block 2
    # FAILS the check: swap-out whose gather reads the copy's dst
    m2 = a.mapping(CLS, owner=2)
    m2.leases.append(a.lease_blocks(CLS, 2, 1)[0])
    # (hand-build a src overlap without device state: direct enqueue)
    f = a.transfers.enqueue_swap_out(CLS, "dep", [2])
    [dep_plan] = [p for p in a.transfers.engines[D2H]._pending
                  if p.owner == "dep"]
    assert dep_plan.deps == {D2D: 0}           # must wait for the copy
    # PASSES the check: swap-out reading only the copy's SOURCE
    a.transfers.enqueue_swap_out(CLS, "indep", [1])
    [ind_plan] = [p for p in a.transfers.engines[D2H]._pending
                  if p.owner == "indep"]
    assert ind_plan.deps == {}                 # read-read: no ordering
    a.transfers.drain()
    a.host_discard(CLS, "dep")
    a.host_discard(CLS, "indep")
    m2.leases.pop().release()
    m.free()
    a.assert_quiescent()


def test_d2h_reorder_window_coalesces_across_dependency():
    """Satellite pin: two INDEPENDENT swap-outs enqueued on either side
    of a d2d copy share one gather launch (the reorder window -- the
    old single-FIFO plane could only batch consecutive plans), while a
    swap-out that depends on the copy's destination is held back and
    reads the POST-copy payload."""
    a, cell = make_executor_arena(n=12)
    m1 = a.mapping(CLS, owner=1)
    m1.ensure_capacity(2)
    write_blocks(a, cell, m1, 1.0)
    parent = a.mapping(CLS, owner=3)
    parent.ensure_capacity(1)
    write_blocks(a, cell, parent, 9.0)
    m2 = a.mapping(CLS, owner=2)
    m2.ensure_capacity(2)
    write_blocks(a, cell, m2, 2.0)

    m1.migrate("host")                          # d2h A (independent)
    child = parent.fork(owner=4, nblocks=1)     # d2d X: COW copy into a
    assert child.ensure_writable(0) is not None  # fresh block
    cow_dst = child.leases[0].block
    child_swap = child.migrate("host")          # d2h B: reads X's dst
    assert cow_dst in child_swap
    m2.migrate("host")                          # d2h C (independent)

    launches_before = a.transfers.stats.launches
    a.transfers.dispatch()
    # A and C coalesced into ONE gather past the blocked B; X executed;
    # B launched separately once its dependency settled
    assert a.transfers.stats.reordered >= 1
    gather_launches = a.transfers.stats.launches - launches_before
    assert gather_launches == 3                # [A+C] + [X] + [B]
    a.transfers.complete_dispatched()
    # B's payload is the POST-copy content (the dependency held)
    np.testing.assert_array_equal(
        a._host_payload[(CLS, 4)][0][0],
        np.full((1, 1, 2), 9.0, np.float32))
    for m in (child, m1, m2):
        m.free()
    parent.free()
    a.assert_quiescent()


def test_swap_in_waits_for_same_owner_swap_out_fence():
    """Cross-queue COMPLETE-strength fence: an h2d swap-in enqueued
    while the owner's d2h swap-out is still unfenced lands the payload
    first (preempt + immediate resume), in any dispatch order."""
    a, cell = make_executor_arena(n=6)
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(2)
    write_blocks(a, cell, m, 5.0)
    m.migrate("host")                          # d2h pending
    m.migrate("device")                        # h2d with fdep on the d2h
    [plan] = a.transfers.engines[H2D]._pending
    assert plan.fdeps == {D2H: 0}
    a.transfers.dispatch()
    np.testing.assert_array_equal(contents(cell, m.block_ids())[0],
                                  np.full((1, 2, 2), 5.0, np.float32))
    m.free()
    a.assert_quiescent()


def test_prefetch_rides_background_lane_and_commits():
    """Speculative swap-in: payload is PEEKED (host copy stays
    authoritative), the plan rides the background lane, and committing
    after completion is pure bookkeeping -- with the overlap attributed
    to the h2d engine, NOT the d2h double buffer (the stats bugfix)."""
    a, cell = make_executor_arena(n=8)
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(2)
    write_blocks(a, cell, m, 4.0)
    m.migrate("host")
    a.transfers.drain()
    ids = m.prefetch()
    assert m.prefetched and m.placement == "host"
    assert a.host_contains(CLS, 0)             # payload NOT consumed
    assert all(l.in_flight for l in m._spec)
    assert a.transfers.queue_depths()[H2D][BACKGROUND] == 1
    a.transfers.dispatch()                     # scatter executes
    assert a.host_contains(CLS, 0)             # still only peeked
    a.transfers.note_compute()                 # a decode runs in between
    got_ids, completed = m.commit_prefetch()
    assert completed and got_ids == ids
    assert m.placement == "device" and not a.host_contains(CLS, 0)
    st_ = a.transfers.stats
    assert st_.prefetch_enqueued == 1 and st_.prefetch_committed == 1
    assert st_.overlapped["h2d"] == 1          # attributed to h2d...
    assert st_.overlapped["d2h"] == 0          # ...not the d2h buffer
    np.testing.assert_array_equal(contents(cell, m.block_ids())[0],
                                  np.full((1, 2, 2), 4.0, np.float32))
    m.free()
    a.assert_quiescent()


def test_cancelled_prefetch_releases_leases_and_holds():
    """Satellite regression: cancelling a prefetch releases its
    in-flight leases (and any holds) and never executes the scatter;
    the payload stays resumable, and the vacated ids' next tenant is
    not clobbered by a stale speculative scatter."""
    a, cell = make_executor_arena(n=6)
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(2)
    write_blocks(a, cell, m, 7.0)
    m.migrate("host")                          # d2h pending, 2 holds
    ids = m.prefetch()                         # spec plan, fdep on d2h
    spec_leases = list(m._spec)
    assert all(l.in_flight for l in spec_leases)
    free_before = a.num_free(CLS)
    m.cancel_prefetch()
    assert not m.prefetched
    assert a.num_free(CLS) == free_before + len(ids)
    assert not any(l.in_flight for l in spec_leases)    # flags cleared
    assert not any(l.live for l in spec_leases)         # leases released
    assert a.transfers.stats.prefetch_cancelled == 1
    assert a.transfers.stats.completed["h2d"] == 0      # never scattered
    # the d2h swap-out (and its holds) is untouched by the cancel
    assert 0 in a.transfers.in_transit(CLS)
    # a new tenant reuses the cancelled ids; draining must not replay
    # the withdrawn scatter over it
    m2 = a.mapping(CLS, owner=1)
    m2.ensure_capacity(2)
    write_blocks(a, cell, m2, 3.0)
    a.transfers.drain()
    np.testing.assert_array_equal(contents(cell, m2.block_ids())[0],
                                  np.full((1, 2, 2), 3.0, np.float32))
    # and the candidate still resumes from its intact host payload
    m.migrate("device")
    a.transfers.drain()
    np.testing.assert_array_equal(contents(cell, m.block_ids())[0],
                                  np.full((1, 2, 2), 7.0, np.float32))
    m2.free()
    m.free()
    a.assert_quiescent()


def test_metadata_only_prefetch_commit_does_not_count_overlap():
    """Regression: a metadata-only arena completes the speculative plan
    inline at enqueue -- committing it must not count a spurious
    ``overlapped[h2d]`` (nothing ever launched, no compute ran)."""
    a = Arena()
    a.register_class("meta", num_blocks=4, block_nbytes=8)
    m = a.mapping("meta", owner=0)
    m.ensure_capacity(2)
    m.migrate("host")
    m.prefetch()
    ids, completed = m.commit_prefetch()
    assert completed and len(ids) == 2
    assert a.transfers.stats.overlapped["h2d"] == 0
    m.free()
    a.assert_quiescent()


def test_free_while_prefetched_cancels_speculation():
    """Freeing a prefetched mapping withdraws the speculation and tears
    down host residency + payload together (no leaks)."""
    a, cell = make_executor_arena(n=6)
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(2)
    write_blocks(a, cell, m, 2.0)
    m.migrate("host")
    a.transfers.drain()
    m.prefetch()
    m.free()
    a.assert_quiescent()


# ---------------------------------------------------------------------------
# the read barrier: unfenced reads of in-flight leases raise
# ---------------------------------------------------------------------------
def test_unfenced_read_of_in_flight_lease_raises():
    a, cell = make_executor_arena()
    parent = a.mapping(CLS, owner=0)
    parent.ensure_capacity(2)
    write_blocks(a, cell, parent, 4.0)
    child = parent.fork(owner=1, nblocks=2)
    plan = child.ensure_writable(1)            # enqueues the COW copy
    assert plan is not None
    lease = child.leases[1]
    assert lease.in_flight and lease.kind == IN_FLIGHT
    with pytest.raises(UnfencedReadError):
        child.assert_settled()                 # the copy has not landed
    parent.assert_settled()                    # parent is untouched
    a.transfers.dispatch()                     # the engine's read barrier
    assert not lease.in_flight
    child.assert_settled()
    np.testing.assert_array_equal(contents(cell, [lease.block])[0],
                                  np.full((1, 1, 2), 4.0, np.float32))
    child.free()
    parent.free()
    a.assert_quiescent()


def test_free_while_swap_in_pending_does_not_clobber_next_tenant():
    """Regression: freeing a device mapping whose swap-in scatter is
    still pending must settle the plan first -- otherwise the ids
    return to the free list, a new tenant writes them, and the stale
    scatter clobbers the new data at the next dispatch."""
    a, cell = make_executor_arena(n=4)
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(2)
    write_blocks(a, cell, m, 7.0)
    m.migrate("host")
    m.migrate("device")                        # h2d scatter pending
    m.free()                                   # cancel mid-resume
    a.assert_quiescent()
    m2 = a.mapping(CLS, owner=1)
    m2.ensure_capacity(2)                      # reuses the vacated ids
    ids = jnp.asarray(m2.block_ids(), jnp.int32)
    cell["streams"] = [s.at[:, ids].set(3.0) for s in cell["streams"]]
    a.transfers.drain()                        # must NOT replay 7.0 here
    np.testing.assert_array_equal(contents(cell, m2.block_ids())[0],
                                  np.full((1, 2, 2), 3.0, np.float32))
    m2.free()
    a.assert_quiescent()


def test_quiescence_requires_fenced_plane():
    a, cell = make_executor_arena()
    m = a.mapping(CLS, owner=0)
    m.ensure_capacity(1)
    m.migrate("host")
    with pytest.raises(AssertionError):
        a.assert_quiescent()                   # unfenced d2h plan
    a.transfers.drain()
    m.free()
    a.assert_quiescent()


# ---------------------------------------------------------------------------
# ORDERING property: any multi-queue interleaving (including speculative
# prefetch and its cancellation) == the synchronous drain() schedule
# ---------------------------------------------------------------------------
GROW, PREEMPT, RESUME, COW, FENCE, PREFETCH, CANCELPF = range(7)


def _avail(a):
    return a.num_free(CLS) + a.allocator(CLS).num_held


def _run_schedule(ops, eager):
    a, cell = make_executor_arena(n=10)
    a.transfers.eager = eager
    maps = []
    next_owner = [0]
    fill = [1.0]

    def new_owner():
        next_owner[0] += 1
        return next_owner[0]

    for code, arg in ops:
        live = [m for m in maps if not m.freed]
        device = [m for m in live if m.placement == "device"]
        host = [m for m in live if m.placement == "host"]
        if code == GROW and _avail(a) >= 2:
            m = a.mapping(CLS, owner=new_owner())
            m.ensure_capacity(1 + arg % 2)
            maps.append(m)
            write_blocks(a, cell, m, fill[0])
            fill[0] += 1
        elif code == PREEMPT and device:
            device[arg % len(device)].migrate("host")
        elif code == RESUME and host:
            target = host[arg % len(host)]
            if _avail(a) >= len(target):
                target.migrate("device")
        elif code == COW and device and _avail(a) >= 1:
            parent = device[arg % len(device)]
            child = parent.fork(owner=new_owner(), nblocks=1)
            maps.append(child)
            child.ensure_writable(0)
            write_blocks(a, cell, child, fill[0])
            fill[0] += 1
        elif code == FENCE:
            a.transfers.drain()
        elif code == PREFETCH:
            idle = [m for m in host if not m.prefetched and len(m) > 0]
            if idle:
                target = idle[arg % len(idle)]
                if _avail(a) >= len(target):
                    target.prefetch()
        elif code == CANCELPF:
            spec = [m for m in host if m.prefetched]
            if spec:
                spec[arg % len(spec)].cancel_prefetch()
    a.transfers.drain()
    state = {}
    for m in maps:
        if m.freed:
            continue
        if m.placement == "device":
            state[m.owner] = ("device", contents(cell, m.block_ids()))
        else:
            payload, nbytes = a._host_payload[(CLS, m.owner)]
            state[m.owner] = ("host", [np.asarray(p) for p in payload],
                              nbytes)
    return state


@settings(max_examples=20)
@given(st.lists(st.tuples(st.integers(0, 6), st.integers(0, 7)),
                min_size=0, max_size=24))
def test_any_interleaving_matches_synchronous_drain(ops):
    """Block contents and host payloads after an arbitrary mix of
    grows, preemptions, resumes, COW barriers, speculative prefetches,
    prefetch cancellations, device writes and fences are identical
    between the overlapped multi-queue schedule and the eager
    (drain-per-enqueue) schedule."""
    deferred = _run_schedule(ops, eager=False)
    eager = _run_schedule(ops, eager=True)
    assert deferred.keys() == eager.keys()
    for owner in deferred:
        d, e = deferred[owner], eager[owner]
        assert d[0] == e[0], (owner, d[0], e[0])
        for da, ea in zip(d[1], e[1]):
            np.testing.assert_array_equal(da, ea)
        if d[0] == "host":
            assert d[2] == e[2]


# ---------------------------------------------------------------------------
# checkpoint-on-arena: snapshot/restore of host tier + mappings
# ---------------------------------------------------------------------------
def test_snapshot_restore_roundtrip(tmp_path):
    a, cell = make_executor_arena(n=8)
    m = a.mapping(CLS, owner=5)
    m.ensure_capacity(2)
    write_blocks(a, cell, m, 6.0)
    m.migrate("host")
    dev = a.mapping(CLS, owner="live")
    dev.ensure_capacity(1)
    path = str(tmp_path / "arena.npz")
    a.snapshot(path)                           # drains in-flight payloads

    b = Arena()
    restored = b.restore(path)
    assert (CLS, 5) in restored
    mm = restored[(CLS, 5)]
    assert mm.placement == "host" and len(mm) == 2
    assert b.host_counts(CLS) == {5: 2}
    # device-resident mappings do NOT survive a restart by design
    assert b.find_mapping(CLS, "live") is None
    # payload bytes roundtrip exactly (uint8 view through the npz)
    pa, na = a._host_payload[(CLS, 5)]
    pb, nb = b._host_payload[(CLS, 5)]
    assert na == nb
    for x, y in zip(pa, pb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # and the restored mapping re-materializes through a new executor
    cell2 = {"streams": [jnp.zeros((1, 8, 2), jnp.float32)]}
    b.transfers.register_executor(
        CLS, lambda: list(cell2["streams"]),
        lambda s: cell2.update(streams=list(s)))
    new_ids = mm.migrate("device")
    b.transfers.drain()
    np.testing.assert_array_equal(
        np.asarray(cell2["streams"][0])[:, np.asarray(new_ids)],
        np.full((1, 2, 2), 6.0, np.float32))
    mm.free()
    b.assert_quiescent()


def test_restore_rejects_spec_mismatch(tmp_path):
    a, _ = make_executor_arena(n=8)
    path = str(tmp_path / "arena.npz")
    a.snapshot(path)
    b = Arena()
    b.register_class(CLS, num_blocks=16, block_nbytes=8)   # different spec
    with pytest.raises(ValueError):
        b.restore(path)


# ---------------------------------------------------------------------------
# per-dp-group accounting (ArenaStats measurement surface)
# ---------------------------------------------------------------------------
def test_per_dp_group_block_accounting():
    a = Arena()
    a.register_class("kvg", num_blocks=8, block_nbytes=16, dp_groups=2)
    m = a.mapping("kvg", owner=0)
    m.ensure_capacity(3)                       # ids 0,1,2 -> group 0
    st_ = a.stats()["kvg"]
    assert st_.groups == [{"group": 0, "used": 3, "free": 1},
                          {"group": 1, "used": 0, "free": 4}]
    # re-registration with a different grouping is loud
    with pytest.raises(ValueError):
        a.register_class("kvg", num_blocks=8, block_nbytes=16, dp_groups=4)
    m.free()
    a.assert_quiescent()
