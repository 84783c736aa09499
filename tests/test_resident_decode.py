"""Resident decode path vs the eager full-rebuild fallback.

The engine's default decode tail keeps block tables / state rows
device-persistent, scatters only the slots whose mapping changed
(delta sync) and runs ONE fused, buffer-donated callable per step
(table scatter + KV append + attention + argmax, next-token vector
latched on device).  ``resident_tables=False`` is the pinned fallback:
full host rebuild + separate upload every step.  These tests pin the
two paths token-identical across everything that mutates a mapping --
forced preemption, COW forks, external compaction, live migration, a
fork-heavy arrival trace -- for all three cache disciplines, and pin
the resident path's whole point: steady-state decode stops uploading.

``check_consistency()`` runs every step: in resident mode it audits the
device-side shadow (tables/rows vs the manager's truth) and would trip
on any mapping mutation that forgot to mark its slot dirty.
"""

import numpy as np
import pytest
import jax

from repro.configs.base import get_config
from repro.models.api import build_model
from repro.serve.engine import Engine, Request
from conftest import assert_engine_quiescent


@pytest.fixture(scope="module")
def families():
    """One tiny model per discipline: paged / constant / composite."""
    out = {}
    for key, name in (("dense", "gemma_2b"), ("ssm", "mamba2_370m"),
                      ("hybrid", "zamba2_2p7b")):
        cfg = get_config(name).reduced()
        model = build_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(hash(key) % 2**31))
        out[key] = (cfg, model, params)
    return out


def _engine(model, params, resident, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("num_blocks", 24)
    return Engine(model, params, eos_id=-1, prefill_budget=None,
                  resident_tables=resident, **kw)


def _prompts(cfg, seed, n=4, shared=True):
    rng = np.random.RandomState(seed)
    out = [rng.randint(2, cfg.vocab_size, size=int(rng.randint(6, 20)))
           for _ in range(n)]
    if shared and n >= 3:
        # consecutive shared-prefix pair so the child forks off a LIVE
        # parent (COW through the resident tables)
        out[2] = np.concatenate([out[1], rng.randint(2, cfg.vocab_size,
                                                     size=3)])
    return out


def _run(eng, prompts, *, max_new=6, preempt_at=3, compact=False,
         max_steps=400):
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr.copy(), max_new=max_new))
    forced = compacted = False
    while (eng.sched.has_work or eng.running) and eng.steps < max_steps:
        eng.step()
        eng.check_consistency()
        if eng.steps == preempt_at and eng.running and not forced:
            eng.preempt_latest()
            forced = True
        if (compact and forced and not compacted
                and eng.arena.fragmentation(eng.mgr.pool_class) > 0):
            assert eng.compact_now() > 0
            eng.check_consistency()
            compacted = True
    eng.sync_transfers()
    assert forced
    if compact:
        assert compacted
    return {r.rid: list(r.generated) for r in eng.done}


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
def test_resident_matches_eager_across_preemption(families, family, rng):
    """Per-request token identity, resident vs eager, across a forced
    preemption round-trip (and a COW fork for the paged discipline) --
    with strictly fewer host uploads on the resident path."""
    cfg, model, params = families[family]
    prompts = _prompts(cfg, seed=11, shared=(family == "dense"))
    got = {}
    uploads = {}
    for resident in (True, False):
        eng = _engine(model, params, resident)
        got[resident] = _run(eng, prompts)
        uploads[resident] = eng.stats["host_uploads"]
        assert len(eng.done) == len(prompts)
        assert eng.stats["resident_tables"] is resident
        assert_engine_quiescent(eng)
    assert got[True] == got[False]
    assert uploads[True] < uploads[False]


def test_resident_identity_across_external_compaction(families, rng):
    """A mid-flight ``compact_now()`` rewrites every lease under the
    resident tables' feet; the full-dirty scatter must absorb it
    token-identically (the per-step shadow audit would catch a missed
    invalidation)."""
    cfg, model, params = families["dense"]
    rng23 = np.random.RandomState(23)
    base = rng23.randint(2, cfg.vocab_size, size=16)
    # a long-lived fork parent + early releases leave holes in the pool
    # (the shape test_serve_stack's acceptance workload uses)
    prompts = [base.copy(),
               rng23.randint(2, cfg.vocab_size, size=9),
               base.copy(),
               np.concatenate([base,
                               rng23.randint(2, cfg.vocab_size, size=5)]),
               rng23.randint(2, cfg.vocab_size, size=5)]
    max_new = [10, 6, 6, 6, 6]
    got = {}
    for resident in (True, False):
        eng = _engine(model, params, resident, slots=3, num_blocks=20,
                      watermark=1)
        for i, pr in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=pr.copy(),
                               max_new=max_new[i]))
        forced = compacted = False
        while (eng.sched.has_work or eng.running) and eng.steps < 400:
            eng.step()
            eng.check_consistency()
            if eng.steps == 3 and eng.running and not forced:
                eng.preempt_latest()
                forced = True
            if (forced and not compacted
                    and eng.arena.fragmentation(eng.mgr.pool_class) > 0):
                assert eng.compact_now() > 0
                eng.check_consistency()
                compacted = True
        eng.sync_transfers()
        assert forced and compacted
        assert len(eng.done) == 5
        got[resident] = {r.rid: list(r.generated) for r in eng.done}
        assert eng.arena.compactions >= 1
        assert_engine_quiescent(eng)
    assert got[True] == got[False]


def test_constant_state_compaction_rides_block_copy(families):
    """``compact_now()`` on the flat ``(NB, 1, E)`` state pool moves
    whole sequence states with the ``block_copy`` kernel, and every
    sequence decodes exactly as it does without the move."""
    cfg, model, params = families["ssm"]
    prompts = _prompts(cfg, seed=11, n=3, shared=False)
    max_new = [2, 8, 8]          # the early finisher leaves a hole
    got = {}
    for compact in (False, True):
        eng = _engine(model, params, True, slots=3)
        for i, pr in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=pr.copy(), max_new=max_new[i]))
        moved = 0
        while (eng.sched.has_work or eng.running) and eng.steps < 100:
            eng.step()
            eng.check_consistency()
            if compact and eng.done and not moved:
                moved = eng.compact_now()
                assert moved > 0
                eng.check_consistency()
        launches = eng.stats["transfers"]["kernel_launches"]
        assert launches.get("block_copy", 0) >= int(compact), launches
        assert "copy_pool_blocks" not in launches
        got[compact] = {r.rid: list(r.generated) for r in eng.done}
        assert_engine_quiescent(eng)
    assert got[True] == got[False]


def test_resident_migrate_live_token_identity(families, tmp_path):
    """Live migration restores into a FRESH engine whose resident
    tables have never seen these requests: the adoption path must mark
    everything dirty and resume token-identical to an unmigrated
    resident control."""
    from repro.serve.disagg import migrate_live

    cfg, model, params = families["dense"]
    prompts = _prompts(cfg, seed=37)

    def drive_pre(eng):
        for i, pr in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=pr.copy(), max_new=6))
        for s in range(3):
            eng.step()
            eng.check_consistency()
            if s == 1 and eng.running:
                eng.preempt_latest()

    control = _engine(model, params, True)
    drive_pre(control)
    control.run(400)
    want = {r.rid: list(r.generated) for r in control.done}

    src = _engine(model, params, True)
    drive_pre(src)
    dst, _ = migrate_live(src, lambda: _engine(model, params, True),
                          str(tmp_path / "resident.npz"))
    while (dst.sched.has_work or dst.running) and dst.steps < 400:
        dst.step()
        dst.check_consistency()
    dst.sync_transfers()
    assert {r.rid: list(r.generated) for r in dst.done} == want
    assert_engine_quiescent(dst)


def test_resident_prefixheavy_trace_matches_eager(families):
    """Fork-heavy live traffic (the ``prefixheavy`` arrival trace):
    COW forks, suffix-only prefill and continuous admission all land on
    the delta-sync path; decodes must match the eager fallback
    per-request."""
    from repro.serve.traffic import make_trace

    cfg, model, params = families["dense"]
    got = {}
    for resident in (True, False):
        eng = _engine(model, params, resident, slots=3)
        source = make_trace("prefixheavy", 8, cfg.vocab_size, seed=3,
                            mean_gap=2.0, tenants=2, max_new=6,
                            prompt_cap=24)
        n = len(source)
        eng.serve(source, max_steps=10_000)
        eng.sync_transfers()
        assert len(eng.done) == n
        assert eng.stats["prefix_hits"] > 0          # forks really happened
        got[resident] = {r.rid: list(r.generated) for r in eng.done}
        assert_engine_quiescent(eng)
    assert got[True] == got[False]


def test_resident_steady_state_stops_uploading(families, rng):
    """The headline property: once admissions settle, decode steps stop
    shipping state to the device -- no table rows (outside block-growth
    steps) and no next-token vector (latched on device).  The eager
    fallback pays exactly two uploads every step."""
    cfg, model, params = families["dense"]
    pr = rng.randint(2, cfg.vocab_size, size=8)

    eng = _engine(model, params, True, slots=1)
    eng.submit(Request(rid=0, prompt=pr, max_new=24))
    deltas = []
    last = 0
    while (eng.sched.has_work or eng.running) and eng.steps < 100:
        eng.step()
        eng.check_consistency()
        deltas.append(eng.host_uploads - last)
        last = eng.host_uploads
    assert len(eng.done) == 1
    # after the placement step, upload-free steps dominate: only block-
    # growth steps scatter anything, and the token vector never leaves
    # the device again
    steady = deltas[1:]
    assert steady.count(0) > len(steady) // 2
    assert eng.stats["host_uploads_per_step"] < 1.0

    eng2 = _engine(model, params, False, slots=1)
    eng2.submit(Request(rid=0, prompt=pr, max_new=24))
    eng2.run(100)
    assert eng2.stats["host_uploads"] == 2 * eng2.steps
    assert list(eng2.done[0].generated) == list(eng.done[0].generated)


def test_report_renders_decode_path_section():
    """BENCH_serve.json rendering: populated section AND the n/a
    degradation contract for pre-resident snapshots."""
    from repro.report import fmt_decode_path_table

    doc = {"decode_path": {
               "resident": {"tokens_per_s": 547.6, "completed": 9,
                            "host_uploads_per_step": 0.667,
                            "table_sync_bytes": 760,
                            "table_rows_updated": 19},
               "eager": {"tokens_per_s": 439.9, "completed": 9,
                         "host_uploads_per_step": 2.0,
                         "table_sync_bytes": 2592,
                         "table_rows_updated": 72},
               "token_identical": True},
           "phase_time_s": {"dispatch": 0.03, "sync": 0.001,
                            "decode": 0.09, "retire": 0.001},
           "host_uploads_per_step": 0.7, "table_sync_bytes": 800}
    table = fmt_decode_path_table(doc)
    assert "| resident | 547.6 | 0.667 |" in table
    assert "| eager | 439.9 | 2.0 |" in table
    assert "token identical: True" in table
    assert "step-phase wall share" in table and "decode" in table

    old = fmt_decode_path_table({"tokens_per_s": 1.0})
    assert "n/a" in old and "pre-resident-path" in old
