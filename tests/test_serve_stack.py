"""Layered serving stack: swap transfer-size pins, COW divergence, and
the scripted mixed workload (queueing + forced preemption + forked
prompts) against the single-request greedy reference.

The swap-size tests mirror ``test_cost_model.py``'s pool-size-
independence pin: the paper's claim only holds if management traffic
scales with what a sequence HOLDS, never with how big the pool is.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.paged_kv import PagedKVConfig, PagedKVManager
from repro.models.api import build_model
from repro.serve.engine import Engine, Request
from repro.serve.scheduler import (PoolGroupMismatchError, Scheduler,
                                   slot_group)
from repro.serve.swap import HostBlockStore
from conftest import assert_engine_quiescent, greedy_reference


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gemma_2b").reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# swap transfer size: proportional to blocks held, independent of pool size
# ---------------------------------------------------------------------------
def _swap_bytes_for(model, params, num_blocks, rng):
    eng = Engine(model, params, slots=2, max_seq=64,
                 num_blocks=num_blocks, eos_id=-1, prefill_budget=None)
    pr = rng.randint(2, 100, size=13)          # 2 blocks of prompt (bt=8)
    eng.submit(Request(rid=0, prompt=pr, max_new=8))
    for _ in range(4):
        eng.step()
    blocks_held = len(eng.mgr.tables[0])
    eng.preempt_latest()
    eng.sync_transfers()     # fence: the d2h plan's host copy lands here
    return blocks_held, eng.store.stats.last_swap_out_bytes, eng.cache.config


@pytest.mark.parametrize("num_blocks", [16, 64, 256])
def test_swap_out_bytes_scale_with_blocks_held(setup, num_blocks):
    cfg, model, params = setup
    held, nbytes, kvcfg = _swap_bytes_for(model, params, num_blocks,
                                          np.random.RandomState(7))
    # exact proportionality: blocks * (layers * streams * block bytes)
    assert nbytes == held * kvcfg.swap_nbytes_per_block()
    # and the pool-sized alternative would have been this much bigger:
    assert nbytes * (num_blocks / held) == pytest.approx(
        num_blocks * kvcfg.swap_nbytes_per_block())


def test_swap_out_bytes_independent_of_pool_size(setup):
    """Same sequence, 16x bigger pool -> byte-identical swap traffic."""
    cfg, model, params = setup
    held_a, bytes_a, _ = _swap_bytes_for(model, params, 16,
                                         np.random.RandomState(7))
    held_b, bytes_b, _ = _swap_bytes_for(model, params, 256,
                                         np.random.RandomState(7))
    assert held_a == held_b
    assert bytes_a == bytes_b


# ---------------------------------------------------------------------------
# COW divergence at the pool level
# ---------------------------------------------------------------------------
def test_cow_fork_diverges_after_write_barrier(rng):
    """Forked child shares prefix blocks; after fork_for_write + device
    copy the two sequences hold independent tails with the common
    prefix preserved in both."""
    from repro.kernels import ops
    cfg = PagedKVConfig(num_layers=2, kv_heads=2, head_dim=4,
                        block_tokens=8, num_blocks=12,
                        max_blocks_per_seq=4, dtype=jnp.float32)
    mgr = PagedKVManager(cfg)
    mgr.admit(0, 12)                       # parent: 12 tokens, 2 blocks
    k_pool = jnp.asarray(
        rng.randn(*cfg.pool_shape()).astype(np.float32))
    parent = list(mgr.tables[0])
    mgr.fork(0, 1, shared_tokens=12)       # tail block shared mid-fill
    assert [mgr.allocator.refcount(b) for b in parent] == [2, 2]

    # child writes at pos 12 -> COW barrier -> one device block copy
    src, dst = mgr.ensure_writable(1, token_pos=12)
    k_pool = ops.copy_pool_blocks(
        k_pool, jnp.asarray([src], jnp.int32), jnp.asarray([dst], jnp.int32))
    before = np.asarray(k_pool).copy()
    # divergent write: child's new token at pos 12 (block 1, offset 4)
    child_val = jnp.full((cfg.num_layers, cfg.kv_heads, cfg.head_dim), 9.0)
    k_pool = k_pool.at[:, dst, 4].set(child_val)

    after = np.asarray(k_pool)
    # parent's physical block untouched by the child's write
    np.testing.assert_array_equal(after[:, src], before[:, src])
    # common prefix (offsets 0..3 of the shared tail) preserved in copy
    np.testing.assert_array_equal(after[:, dst, :4], before[:, src, :4])
    # and the divergent token landed only in the child's block
    np.testing.assert_array_equal(after[:, dst, 4],
                                  np.asarray(child_val, np.float32))
    assert mgr.tables[0][1] == src and mgr.tables[1][1] == dst


# ---------------------------------------------------------------------------
# scheduler policy unit pins (no device)
# ---------------------------------------------------------------------------
class _Mem:
    """Minimal block-accounting stub for policy tests."""
    class _A:
        def __init__(self, free):
            self.num_free = free
    def __init__(self, free, bt=8):
        self.allocator = self._A(free)
        self.bt = bt
    def blocks_needed(self, tokens):
        return -(-tokens // self.bt)


def test_scheduler_watermark_holds_back_admissions():
    sched = Scheduler(watermark=2)
    a = Request(rid=0, prompt=np.arange(8), max_new=8)    # 2 blocks
    b = Request(rid=1, prompt=np.arange(8), max_new=8)
    sched.submit(a)
    sched.submit(b)
    plan = sched.plan_admissions(2, _Mem(free=5), num_running=0)
    # first admission ignores the watermark (progress guarantee), the
    # second would leave 5-2-2=1 < 2 free and is held back
    assert [r.rid for r in plan.admit] == [0]
    plan = sched.plan_admissions(2, _Mem(free=6), num_running=0)
    assert [r.rid for r in plan.admit] == [1]


def test_scheduler_prefill_budget_chunks_admissions():
    sched = Scheduler(prefill_budget=10)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=np.arange(8), max_new=4))
    plan = sched.plan_admissions(3, _Mem(free=64), num_running=0)
    # 8 tokens fit the budget; the next 8 would exceed the remaining 2
    assert [r.rid for r in plan.admit] == [0]
    plan = sched.plan_admissions(3, _Mem(free=64), num_running=1)
    assert [r.rid for r in plan.admit] == [1]


def test_scheduler_full_footprint_gate():
    """A request whose worst case cannot fit right now is not admitted,
    even though its prompt alone would fit (anti-livelock)."""
    sched = Scheduler()
    sched.submit(Request(rid=0, prompt=np.arange(8), max_new=56))  # 8 blocks
    plan = sched.plan_admissions(1, _Mem(free=4), num_running=0)
    assert not plan
    plan = sched.plan_admissions(1, _Mem(free=8), num_running=0)
    assert [r.rid for r in plan.admit] == [0]


def test_scheduler_adaptive_watermark():
    """With no static knob the watermark tracks the EWMA of observed
    blocks/step (times the lookahead horizon); the knob overrides."""
    sched = Scheduler()                        # adaptive by default
    assert sched.watermark == 0                # no growth observed yet
    for _ in range(60):
        sched.observe_growth(2)                # steady 2 blocks/step
    assert sched.watermark == 2 * sched.growth_horizon   # EWMA converged
    static = Scheduler(watermark=3)
    for _ in range(60):
        static.observe_growth(10)
    assert static.watermark == 3               # the knob still wins
    # the adaptive headroom actually holds back admissions: first
    # admission ignores the watermark (progress guarantee); the second
    # would leave 11-2-2=7 < 8 free and is deferred
    sched.submit(Request(rid=0, prompt=np.arange(8), max_new=8))  # 2 blocks
    sched.submit(Request(rid=1, prompt=np.arange(8), max_new=8))
    plan = sched.plan_admissions(2, _Mem(free=11), num_running=0)
    assert [r.rid for r in plan.admit] == [0]
    sched.submit(Request(rid=2, prompt=np.arange(8), max_new=8))
    plan = sched.plan_admissions(2, _Mem(free=11), num_running=1)
    # 11-2=9 >= 8 admits rid=1; 9-2=7 < 8 defers rid=2
    assert [r.rid for r in plan.admit] == [1]


def test_scheduler_adaptive_prefill_budget():
    """``prefill_budget="auto"`` (now the constructor DEFAULT) derives
    the per-step prompt-token budget from MEASURED latency EWMAs (the
    watermark pattern: adapt by default, knob overrides) -- sized so
    one step's prefill costs at most ``prefill_slack`` decode-steps of
    wall time.  Unlimited until both EWMAs have data (the first
    admission is never starved)."""
    sched = Scheduler()                            # "auto" is the default
    assert sched.prefill_budget is None        # no observations yet
    sched.observe_decode(0.1)
    assert sched.prefill_budget is None        # still missing prefill data
    sched.observe_prefill(100, 1.0)            # 10 ms / prompt token
    for _ in range(60):                        # converge both EWMAs
        sched.observe_decode(0.1)
        sched.observe_prefill(100, 1.0)
    # 4 decode-steps of slack * 0.1 s / 0.01 s-per-token = 40 tokens
    assert sched.prefill_budget == 4 * 10
    # and the derived budget actually chunks admissions
    for i in range(3):
        sched.submit(Request(rid=i, prompt=np.arange(32), max_new=4))
    plan = sched.plan_admissions(3, _Mem(free=64), num_running=1)
    assert [r.rid for r in plan.admit] == [0]  # 32 <= 40; next 32 > 8 left
    # the static knob still overrides the adaptive path entirely
    static = Scheduler(prefill_budget=10)
    static.observe_decode(5.0)
    static.observe_prefill(10, 0.001)
    assert static.prefill_budget == 10
    # explicit None opts out entirely -- the deterministic schedule the
    # equivalence pins run on -- no matter what is observed
    off = Scheduler(prefill_budget=None)
    off.observe_decode(0.1)
    off.observe_prefill(100, 1.0)
    assert off.prefill_budget is None
    with pytest.raises(ValueError):
        Scheduler(prefill_budget="fast")
    with pytest.raises(ValueError):
        Scheduler(prefill_budget=0)


def test_scheduler_resume_candidates_peek():
    """``resume_candidates`` exposes the top-k LIFO window without
    popping -- the surface the engine's speculative prefetch rides,
    most-likely-next first (the ordering is also the cancellation
    ranking under pressure)."""
    sched = Scheduler()
    assert sched.resume_candidates() == []
    a = Request(rid=0, prompt=np.arange(8), max_new=8)
    b = Request(rid=1, prompt=np.arange(8), max_new=8)
    c = Request(rid=2, prompt=np.arange(8), max_new=8)
    sched.on_preempt(a)
    assert [r.rid for r in sched.resume_candidates()] == [0]
    sched.on_preempt(b)
    sched.on_preempt(c)
    # top-k=2 window, LIFO top first; deeper entries stay invisible
    assert [r.rid for r in sched.resume_candidates()] == [2, 1]
    assert len(sched.preempted) == 3           # peek does not pop
    assert sched.resume_candidates()[0] is sched.preempted.peek()


def test_scheduler_rejects_cross_group_fork():
    """dp_groups > 1: block tables hold group-local ids, so a fork may
    only alias a parent in its own pool group -- anything else fails
    loudly at admission instead of silently corrupting tables."""
    # 4 slots over 2 groups: slots 0,1 -> group 0; slots 2,3 -> group 1
    assert [slot_group(s, 4, 2) for s in range(4)] == [0, 0, 1, 1]
    Scheduler.validate_fork(0, 1, 4, 2)        # same group: fine
    Scheduler.validate_fork(0, 3, 4, 1)        # dp_groups == 1: no-op
    with pytest.raises(PoolGroupMismatchError):
        Scheduler.validate_fork(0, 2, 4, 2)
    with pytest.raises(PoolGroupMismatchError):
        Scheduler.validate_fork(3, 0, 4, 2)


def test_engine_rejects_group_oblivious_dp_serving(setup):
    """dp_groups > 1 serving fails LOUDLY at construction: the Arena
    still hands out global ids while group-batched caches read tables
    as group-local -- running would corrupt the pool silently."""
    cfg, model, params = setup
    with pytest.raises(NotImplementedError):
        Engine(model, params, slots=2, max_seq=32, num_blocks=8,
               eos_id=-1, dp_groups=2)


def test_cow_barrier_under_pool_exhaustion(setup, rng):
    """Regression: the COW copy target is a deferred claim admission
    cannot reserve; when concurrent growth drains the pool first, the
    barrier must preempt (LIFO) instead of crashing Engine.step()."""
    cfg, model, params = setup
    eng = Engine(model, params, slots=4, max_seq=32, num_blocks=10,
                 eos_id=-1, prefill_budget=None)
    parent = rng.randint(2, 100, size=20)     # partial tail block (bt=8)
    eng.submit(Request(rid=0, prompt=parent, max_new=4))
    eng.submit(Request(rid=1, prompt=rng.randint(2, 100, size=14),
                       max_new=4))
    eng.submit(Request(rid=2, prompt=rng.randint(2, 100, size=14),
                       max_new=4))
    for _ in range(2):
        eng.step()
        eng.check_consistency()
    # child is the parent's 12-token prefix -> forks, allocates nothing
    eng.submit(Request(rid=3, prompt=parent[:12].copy(), max_new=4))
    done = eng.run(max_steps=300)             # must not raise
    assert len(done) == 4
    assert eng.prefix_hits >= 1
    for req in sorted(done, key=lambda r: r.rid):
        ref = greedy_reference(model, params, req.prompt, 4,
                               cfg.kv_block_tokens, max_seq=32)
        assert req.generated == ref, (req.rid, req.generated, ref)
    assert_engine_quiescent(eng)


# ---------------------------------------------------------------------------
# the transfer plane, engine-level: the overlapped schedule (dispatch at
# step N, fence at N+1) is token- AND byte-identical to drain()
# ---------------------------------------------------------------------------
def _drive_overlap_workload(model, params, overlap):
    eng = Engine(model, params, slots=2, max_seq=32, num_blocks=6,
                 eos_id=-1, prefill_budget=None,
                 overlap_transfers=overlap)
    rngl = np.random.RandomState(3)
    prompts = [rngl.randint(2, 100, size=n) for n in (8, 7, 6)]
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr, max_new=12))
    while (eng.sched.has_work or eng.running) and eng.steps < 400:
        eng.step()
        eng.check_consistency()
    eng.sync_transfers()
    toks = {r.rid: list(r.generated) for r in eng.done}
    st = eng.store.stats
    return eng, toks, (st.swap_outs, st.swap_ins,
                       st.swap_out_bytes, st.swap_in_bytes)


def test_overlapped_schedule_token_and_byte_identical(setup):
    """Growth-pressure preemptions under double-buffering: same tokens,
    same swap traffic as the synchronous drain() schedule -- and at
    least one host copy genuinely overlapped a decode step."""
    cfg, model, params = setup
    eng_async, toks_async, bytes_async = _drive_overlap_workload(
        model, params, overlap=True)
    eng_sync, toks_sync, bytes_sync = _drive_overlap_workload(
        model, params, overlap=False)
    assert len(toks_async) == 3
    assert toks_async == toks_sync
    assert bytes_async == bytes_sync
    assert eng_async.preemptions > 0            # pressure actually fired
    # the double-buffer win: a swap-out host copy fenced at step N+1 --
    # attributed to the d2h ENGINE (per-engine since the multi-queue
    # refactor: h2d prefetch overlap must not inflate this counter)
    assert eng_async.transfers.stats.overlapped["d2h"] >= 1
    assert all(v == 0 for v in
               eng_sync.transfers.stats.overlapped.values())
    assert_engine_quiescent(eng_async)
    assert_engine_quiescent(eng_sync)


# ---------------------------------------------------------------------------
# speculative swap-in prefetch: a LIFO resume served from a COMPLETED
# background-lane scatter, token-identical to the drain() schedule
# ---------------------------------------------------------------------------
def _drive_prefetch_workload(model, params, overlap):
    """Forced-preemption workload whose LIFO victim waits in the
    prefetch window: two long growers fill two slots, a short filler's
    completion admits a YOUNG victim, and the forced eviction at step
    34 leaves the victim's worst-case footprint blocked
    (free - wc < watermark) while its current blocks fit
    (free - cur >= watermark).  The background h2d scatter completes
    during the multi-step wait; the resume commits it."""
    eng = Engine(model, params, slots=3, max_seq=64, num_blocks=20,
                 eos_id=-1, watermark=2, prefill_budget=None,
                 overlap_transfers=overlap)
    rngl = np.random.RandomState(3)
    shapes = [(8, 48), (8, 48), (8, 8), (8, 40)]
    reqs = [Request(rid=i, prompt=rngl.randint(2, 100, size=pl),
                    max_new=mn) for i, (pl, mn) in enumerate(shapes)]
    for r in reqs:
        eng.submit(r)
    forced = False
    while (eng.sched.has_work or eng.running) and eng.steps < 400:
        eng.step()
        eng.check_consistency()
        if eng.steps == 34 and eng.running and not forced:
            eng.preempt_latest()
            forced = True
    eng.sync_transfers()
    assert forced
    return eng


def test_lifo_resume_served_from_completed_prefetch(setup):
    """Acceptance pin: on the forced-preemption workload, at least one
    LIFO resume is served from a COMPLETED speculative prefetch -- and
    the prefetching schedule stays per-request-token- and
    swap-byte-identical to the single-queue drain() fallback
    (speculation never changes a decision; step counts are not pinned
    -- tokens and bytes are the decision surface)."""
    cfg, model, params = setup
    eng = _drive_prefetch_workload(model, params, overlap=True)
    assert len(eng.done) == 4
    assert eng.preemptions >= 1
    assert eng.prefetches >= 1
    assert eng.prefetch_hits >= 1            # resume skipped the swap-in
    assert eng.prefetch_cancels == 0         # speculation was never wrong
    # the h2d scatter genuinely overlapped decode steps while waiting,
    # attributed to the h2d engine (the per-engine stats bugfix)
    assert eng.transfers.stats.overlapped["h2d"] >= 1
    # decision-identical to the synchronous single-queue schedule
    eng_sync = _drive_prefetch_workload(model, params, overlap=False)
    assert eng_sync.prefetches == 0          # prefetch off under drain()
    assert ({r.rid: list(r.generated) for r in eng.done}
            == {r.rid: list(r.generated) for r in eng_sync.done})
    st, st2 = eng.store.stats, eng_sync.store.stats
    assert (st.swap_out_bytes, st.swap_in_bytes) \
        == (st2.swap_out_bytes, st2.swap_in_bytes)
    # token-identical to the single-request greedy reference
    for req in sorted(eng.done, key=lambda r: r.rid):
        ref = greedy_reference(model, params, req.prompt, req.max_new,
                               cfg.kv_block_tokens)
        assert req.generated == ref, (req.rid, req.generated, ref)
    assert_engine_quiescent(eng)
    assert_engine_quiescent(eng_sync)


# ---------------------------------------------------------------------------
# the swap ledger's two-phase speculative accounting syncs through the
# queue's commit/abandon re-notifications -- no engine glue required
# ---------------------------------------------------------------------------
def test_ledger_syncs_on_direct_migrate_commit_and_cancel():
    """Regression: resuming a prefetched mapping through the PUBLIC
    ``migrate("device")`` path (not the engine's guarded commit) must
    still fold the parked speculative bytes into swap_ins -- and a
    cancelled executed prefetch must write them off as waste, never
    leave them parked to corrupt a later resume's accounting."""
    from repro.mem import Arena as _Arena

    def make(n=8):
        a = _Arena()
        a.register_class("kv", num_blocks=n, block_nbytes=8)
        cell = {"s": [jnp.zeros((1, n, 2), jnp.float32)]}
        a.transfers.register_executor(
            "kv", lambda: list(cell["s"]),
            lambda s: cell.update(s=list(s)))
        return a, HostBlockStore(a, "kv")

    # commit path: direct migrate("device") of a prefetched mapping
    a, store = make()
    m = a.mapping("kv", owner=0)
    m.ensure_capacity(2)
    m.migrate("host")
    a.transfers.drain()
    m.prefetch()
    a.transfers.dispatch()                      # scatter completes
    assert store.stats.swap_ins == 0            # parked, not yet demand
    m.migrate("device")                         # auto-commits
    assert store.stats.swap_ins == 1
    assert store.stats.prefetch_commits == 1
    assert store.stats.swap_in_bytes == store.stats.by_engine[
        "h2d-prefetch"]["bytes"]
    m.free()
    a.transfers.drain()
    a.assert_quiescent()

    # cancel path: executed speculation written off, later real resume
    # counted exactly once
    a2, store2 = make()
    m2 = a2.mapping("kv", owner=0)
    m2.ensure_capacity(2)
    m2.migrate("host")
    a2.transfers.drain()
    m2.prefetch()
    a2.transfers.dispatch()
    m2.cancel_prefetch()
    assert store2.stats.prefetch_cancels == 1
    assert store2.stats.prefetch_wasted_bytes > 0
    assert store2.stats.swap_ins == 0
    m2.migrate("device")                        # real (demand) swap-in
    a2.transfers.drain()
    assert store2.stats.swap_ins == 1
    assert store2.stats.prefetch_commits == 0
    m2.free()
    a2.assert_quiescent()


# ---------------------------------------------------------------------------
# checkpoint-on-arena: a restarted engine resumes a preempted sequence
# ---------------------------------------------------------------------------
def test_restart_resumes_decoding(setup, rng, tmp_path):
    cfg, model, params = setup
    eng = Engine(model, params, slots=2, max_seq=64, num_blocks=24,
                 eos_id=-1, prefill_budget=None)
    pr = rng.randint(2, 100, size=9)
    eng.submit(Request(rid=0, prompt=pr, max_new=8))
    for _ in range(4):
        eng.step()
    eng.preempt_latest()
    old = eng.sched.preempted.peek()
    assert old.rid == 0 and len(old.generated) > 0
    path = str(tmp_path / "arena.npz")
    eng.arena.snapshot(path)        # drains the in-transit swap payload

    # "restart": fresh process state -- new engine, new arena; the
    # serving layer re-creates the Request from its own durable queue
    eng2 = Engine(model, params, slots=2, max_seq=64, num_blocks=24,
                  eos_id=-1, prefill_budget=None)
    restored = eng2.arena.restore(path)
    assert ("kv", 0) in restored
    req = Request(rid=0, prompt=pr, max_new=8,
                  generated=list(old.generated),
                  pending_tok=old.pending_tok)
    eng2.restore_preempted(req)
    done = eng2.run(max_steps=200)
    assert len(done) == 1
    ref = greedy_reference(model, params, pr, 8, cfg.kv_block_tokens)
    assert done[0].generated == ref
    assert done[0].generated[: len(old.generated)] == list(old.generated)
    assert_engine_quiescent(eng2)


# ---------------------------------------------------------------------------
# the acceptance workload: mixed prompts, forced preemption, forked
# prompts, and at least one Arena compact() cycle mid-flight
# ---------------------------------------------------------------------------
def test_scripted_workload_token_identical(setup, rng):
    cfg, model, params = setup
    eng = Engine(model, params, slots=3, max_seq=64, num_blocks=20,
                 eos_id=-1, watermark=1, prefill_budget=None)
    base = rng.randint(2, cfg.vocab_size, size=16)
    reqs = [
        # rid=0 generates longest so it is still resident (a live fork
        # parent) when rid=3 is admitted into a freed slot
        Request(rid=0, prompt=base.copy(), max_new=10),
        Request(rid=1, prompt=rng.randint(2, cfg.vocab_size, size=9),
                max_new=6),
        Request(rid=2, prompt=base.copy(), max_new=6),          # forked
        Request(rid=3, prompt=np.concatenate(
            [base, rng.randint(2, cfg.vocab_size, size=5)]),    # shared prefix
                max_new=6),
        Request(rid=4, prompt=rng.randint(2, cfg.vocab_size, size=5),
                max_new=6),
    ]
    for r in reqs:
        eng.submit(r)
    forced = False
    while (eng.sched.has_work or eng.running) and eng.steps < 400:
        eng.step()
        eng.check_consistency()
        if eng.steps == 3 and eng.running and not forced:
            eng.preempt_latest()               # forced mid-flight preemption
            forced = True
        if forced and eng.arena.compactions == 0 \
                and eng.arena.fragmentation(eng.mgr.pool_class) > 0:
            # force one defrag cycle mid-flight: live blocks move to the
            # dense prefix, tables absorb the relocation
            moved = eng.compact_now()
            assert moved > 0
            assert eng.arena.fragmentation(eng.mgr.pool_class) == 0.0
            eng.check_consistency()
    assert len(eng.done) == 5
    assert forced and eng.store.stats.swap_outs >= 1
    assert eng.prefix_hits >= 2                # rid=2 and rid=3 forked
    assert eng.arena.compactions >= 1          # the defrag pass really ran
    # every swap-out moved exactly blocks_held * block bytes -- never more
    per_block = eng.cache.config.swap_nbytes_per_block()
    for seq_id, nblocks, nbytes in eng.store.stats.out_log:
        assert nbytes <= nblocks * per_block
        assert nbytes == nblocks * per_block
    # token-identical to the pre-refactor engine's verified reference
    for req in sorted(eng.done, key=lambda r: r.rid):
        ref = greedy_reference(model, params, req.prompt, req.max_new,
                               cfg.kv_block_tokens)
        assert req.generated == ref, (req.rid, req.generated, ref)
    assert_engine_quiescent(eng)


# ---------------------------------------------------------------------------
# suffix-only prefill: forked children recompute only the un-cached tail,
# attending through the COW-shared prefix blocks via the paged prefill
# kernel -- pinned token-identical to full recompute AND the greedy
# reference across fork depth, partial-tail aliasing, windowed/softcapped
# layers, and preemption round-trips
# ---------------------------------------------------------------------------
def _run_engine(eng, reqs, max_steps=400):
    for r in reqs:
        eng.submit(r)
    while (eng.sched.has_work or eng.running) and eng.steps < max_steps:
        eng.step()
        eng.check_consistency()
    eng.sync_transfers()
    return {r.rid: list(r.generated) for r in eng.done}


def _suffix_vs_full(model, params, prompts, max_new, **eng_kw):
    """Serve the same prompt set with suffix-only prefill on and off;
    returns (tokens by mode, engine by mode)."""
    toks, engines = {}, {}
    for flag in (True, False):
        eng = Engine(model, params, eos_id=-1, prefill_budget=None,
                     suffix_prefill=flag, **eng_kw)
        reqs = [Request(rid=i, prompt=p.copy(), max_new=m)
                for i, (p, m) in enumerate(zip(prompts, max_new))]
        toks[flag] = _run_engine(eng, reqs)
        engines[flag] = eng
    return toks, engines


def test_suffix_prefill_fork_depth_token_identical(setup, rng):
    """Fork chains two deep: the grandchild aliases blocks the child
    itself aliased from the root.  Depth-1 saves the root's 2 blocks
    (16 tokens), depth-2 saves the child's 3 (24): exactly 40 prefix
    tokens never recomputed."""
    cfg, model, params = setup
    base = rng.randint(2, cfg.vocab_size, size=16)        # 2 full blocks
    mid = np.concatenate([base, rng.randint(2, cfg.vocab_size, size=8)])
    top = np.concatenate([mid, rng.randint(2, cfg.vocab_size, size=5)])
    prompts, max_new = [base, mid, top], [10, 8, 6]
    toks, engines = _suffix_vs_full(model, params, prompts, max_new,
                                    slots=3, max_seq=64, num_blocks=24)
    assert len(toks[True]) == 3
    assert toks[True] == toks[False]
    for rid, pr in enumerate(prompts):
        ref = greedy_reference(model, params, pr, max_new[rid],
                               cfg.kv_block_tokens)
        assert toks[True][rid] == ref, (rid, toks[True][rid], ref)
    eng = engines[True]
    assert eng.prefix_hits >= 2
    assert eng.prefill_tokens_saved == 40
    assert engines[False].prefill_tokens_saved == 0
    assert eng.prefill_tokens < engines[False].prefill_tokens
    assert_engine_quiescent(eng)
    assert_engine_quiescent(engines[False])


def test_suffix_prefill_partial_tail_alias(setup, rng):
    """Partial-tail aliasing, both directions: a child fully contained
    in the parent shares the parent's half-filled tail block (its
    recomputed last block scatters to the sink; attention reads the
    aliased original), and a child EXTENDING a mid-block parent has its
    share rounded DOWN to the block boundary so its private tail is
    recomputed, never lost."""
    cfg, model, params = setup
    parent = rng.randint(2, cfg.vocab_size, size=20)      # tail mid-block
    inner = parent[:12].copy()                            # contained child
    longer = np.concatenate([parent,
                             rng.randint(2, cfg.vocab_size, size=6)])
    prompts, max_new = [parent, inner, longer], [10, 6, 6]
    toks, engines = _suffix_vs_full(model, params, prompts, max_new,
                                    slots=3, max_seq=64, num_blocks=24)
    assert len(toks[True]) == 3
    assert toks[True] == toks[False]
    for rid, pr in enumerate(prompts):
        ref = greedy_reference(model, params, pr, max_new[rid],
                               cfg.kv_block_tokens)
        assert toks[True][rid] == ref, (rid, toks[True][rid], ref)
    # inner shares 12 but recomputes the aliased tail block (saves 8);
    # longer's share of 20 rounds down to 16 (saves 16)
    assert engines[True].prefill_tokens_saved == 8 + 16
    assert_engine_quiescent(engines[True])


def test_suffix_prefill_sliding_window_softcap(rng):
    """Suffix attention through shared blocks under a sliding window
    PLUS logit softcap (gemma2-style layers): the window crosses the
    cached-prefix boundary, so windowed masking must be applied in
    ABSOLUTE positions inside the paged prefill kernel."""
    cfg = get_config("gemma2_27b").reduced()
    assert cfg.local_window and cfg.attn_softcap    # the shape under test
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    base = rng.randint(2, cfg.vocab_size, size=24)  # > window (16) tokens
    child = np.concatenate([base, rng.randint(2, cfg.vocab_size, size=7)])
    prompts, max_new = [base, child], [8, 8]
    toks, engines = _suffix_vs_full(model, params, prompts, max_new,
                                    slots=2, max_seq=64, num_blocks=24)
    assert len(toks[True]) == 2
    assert toks[True] == toks[False]
    for rid, pr in enumerate(prompts):
        ref = greedy_reference(model, params, pr, max_new[rid],
                               cfg.kv_block_tokens)
        assert toks[True][rid] == ref, (rid, toks[True][rid], ref)
    assert engines[True].prefill_tokens_saved == 24
    assert_engine_quiescent(engines[True])


def test_suffix_prefill_cow_exhaustion_resume(setup, rng):
    """The suffix path composes with the COW barrier under pool
    exhaustion AND a forced preemption round-trip: the forked child is
    swapped out mid-decode, resumed from the host tier, and still
    decodes token-identically to the greedy reference."""
    cfg, model, params = setup
    parent = rng.randint(2, cfg.vocab_size, size=20)
    prompts = [parent,
               rng.randint(2, cfg.vocab_size, size=14),
               parent[:12].copy()]                 # forked, suffix path
    max_new = [4, 4, 6]
    for flag in (True, False):
        eng = Engine(model, params, slots=4, max_seq=32, num_blocks=10,
                     eos_id=-1, prefill_budget=None, suffix_prefill=flag)
        reqs = [Request(rid=i, prompt=p.copy(), max_new=m)
                for i, (p, m) in enumerate(zip(prompts, max_new))]
        for r in reqs:
            eng.submit(r)
        forced = False
        while (eng.sched.has_work or eng.running) and eng.steps < 300:
            eng.step()
            eng.check_consistency()
            if eng.steps == 2 and eng.running and not forced:
                eng.preempt_latest()       # evict; resume via swap-in
                forced = True
        eng.sync_transfers()
        assert forced and len(eng.done) == 3
        if flag:
            assert eng.prefix_hits >= 1
            assert eng.prefill_tokens_saved > 0
        for req in sorted(eng.done, key=lambda r: r.rid):
            ref = greedy_reference(model, params, req.prompt, req.max_new,
                                   cfg.kv_block_tokens, max_seq=32)
            assert req.generated == ref, (flag, req.rid, req.generated, ref)
        assert_engine_quiescent(eng)
