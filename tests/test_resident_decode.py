"""The engine's one decode path, pinned against plain references.

The decode keeps block tables / state rows device-persistent, scatters
only the slots whose mapping changed (delta sync) and runs ONE fused,
buffer-donated callable per step (table scatter + KV append or state
update + argmax, next-token vector latched on device).

What is pinned against what:

* every request's tokens against ``greedy_reference`` (conftest): the
  model's own ``prefill`` + ``decode_step`` on one sequence, with no
  engine, Arena or pool -- across everything that mutates a mapping
  (forced preemption, COW forks, external compaction, live migration,
  a fork-heavy arrival trace) for the paged, constant-state (Mamba-2,
  RWKV6, Mamba-2 with a layered pool) and composite disciplines;
* the layered Mamba-2 pool against the flat ``(NB, 1, E)`` pool that
  RWKV6 and the Zamba2 hybrid are served from: tokens, and the pool's
  bytes after every step;
* the batched constant-state prefill against a per-sequence
  ``model.prefill``;
* the delta sync's point: steady-state decode stops uploading.

``check_consistency()`` runs every step: it audits the device-side
shadow (tables/rows vs the manager's truth) and would trip on any
mapping mutation that forgot to mark its slot dirty.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.models.api import build_model
from repro.models.mamba2_lm import Mamba2LM
from repro.serve.engine import Engine, Request
from conftest import assert_engine_quiescent, greedy_reference


@pytest.fixture(scope="module")
def families():
    """One tiny model per discipline: paged / constant / composite, and
    rwkv6 beside mamba2 on the constant-state discipline.  ``ssm_tiles``
    is mamba2 with the published model's 128-wide SSD state, so its state
    fills whole (8, 128) tiles and is served from the layered pool."""
    out = {}
    for key, name in (("dense", "gemma_2b"), ("ssm", "mamba2_370m"),
                      ("hybrid", "zamba2_2p7b"), ("rwkv6", "rwkv6_7b"),
                      ("ssm_tiles", "mamba2_370m")):
        cfg = get_config(name).reduced()
        if key == "ssm_tiles":
            cfg = dataclasses.replace(
                cfg, ssm=dataclasses.replace(cfg.ssm, state_dim=128))
        model = build_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(hash(key) % 2**31))
        out[key] = (cfg, model, params)
    return out


def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("num_blocks", 24)
    return Engine(model, params, eos_id=-1, prefill_budget=None, **kw)


def _assert_matches_reference(eng, model, params):
    """Every finished request's tokens equal its ``greedy_reference``."""
    q = eng.strategy.prefill_quantum
    for req in eng.done:
        want = greedy_reference(model, params, req.prompt, req.max_new, q,
                                max_seq=eng.max_seq)
        assert list(req.generated) == want, (req.rid, req.generated, want)


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


@pytest.mark.parametrize("family",
                         ["dense", "ssm", "hybrid", "rwkv6", "ssm_tiles"])
def test_decode_matches_reference_across_preemption(families, family):
    """Per-request tokens equal the single-sequence greedy reference
    across a forced preemption round-trip (and a COW fork for the paged
    discipline) -- with fewer host uploads than steps."""
    cfg, model, params = families[family]
    prompts = _prompts(cfg, seed=11, shared=(family == "dense"))
    eng = _engine(model, params)
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr.copy(), max_new=6))
    forced = False
    while (eng.sched.has_work or eng.running) and eng.steps < 400:
        eng.step()
        eng.check_consistency()
        if eng.steps == 3 and eng.running and not forced:
            eng.preempt_latest()
            forced = True
    eng.sync_transfers()
    assert forced and len(eng.done) == len(prompts)
    _assert_matches_reference(eng, model, params)
    assert eng.stats["host_uploads"] < eng.steps
    assert_engine_quiescent(eng)


def test_resident_identity_across_external_compaction(families, rng):
    """A mid-flight ``compact_now()`` rewrites every lease under the
    resident tables' feet; the full-dirty scatter must absorb it, so every
    request still decodes as the greedy reference does (the per-step
    shadow audit would catch a missed invalidation)."""
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
    eng = _engine(model, params, slots=3, num_blocks=20, watermark=1)
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr.copy(), max_new=max_new[i]))
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
    assert eng.arena.compactions >= 1
    _assert_matches_reference(eng, model, params)
    assert_engine_quiescent(eng)


def test_constant_state_compaction_rides_block_copy(families):
    """``compact_now()`` on the state pool moves whole sequence states
    with the ``block_copy`` kernel, and every sequence decodes as the
    greedy reference does, with the move and without it."""
    cfg, model, params = families["ssm"]
    prompts = _prompts(cfg, seed=11, n=3, shared=False)
    max_new = [2, 8, 8]          # the early finisher leaves a hole
    for compact in (False, True):
        eng = _engine(model, params, slots=3)
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
        assert len(eng.done) == len(prompts)
        _assert_matches_reference(eng, model, params)
        assert_engine_quiescent(eng)


class _FlatMamba2(Mamba2LM):
    """Mamba-2 that does not say where each layer's state lives in a
    block: served from the flat ``(NB, 1, E)`` pool."""
    state_block = None


def _pool_bits(eng):
    pool = np.asarray(eng.mgr.pool)
    return pool.reshape(pool.shape[0], -1).view(np.uint32)


def test_layered_state_decode_matches_flat(families):
    """The in-place, layer-by-layer decode against the flat row path,
    step for step: slots join and leave (sink-padded
    slots between), one sequence is swapped out and back in, one
    compaction ``block_copy`` runs.  The tokens are identical, and after
    every step so is the pool, byte for byte: live blocks, the sink and
    untouched blocks."""
    cfg, model, params = families["ssm_tiles"]
    assert model.state_elems % 1024 == 0
    engs = [_engine(m, params, slots=3, num_blocks=8)
            for m in (model, _FlatMamba2(cfg))]
    layered, flat = engs
    assert layered.strategy.layered and not flat.strategy.layered
    assert layered.mgr.pool.shape[1:] == model.state_block
    assert flat.mgr.pool.shape[1:] == (1, model.state_elems)
    max_new = (3, 9, 6, 4, 7)
    for eng in engs:
        prng = np.random.RandomState(17)
        for rid, n in enumerate((9, 14, 6, 11, 17)):
            eng.submit(Request(rid=rid, max_new=max_new[rid],
                               prompt=prng.randint(2, cfg.vocab_size,
                                                   size=n)))
    forced = compacted = padded = False
    while (layered.sched.has_work or layered.running) and layered.steps < 200:
        for eng in engs:
            eng.step()
            eng.check_consistency()
        padded |= 0 < len(layered.running) < layered.slots
        if layered.steps == 3 and not forced:
            for eng in engs:
                eng.preempt_latest()
            forced = True
        if (forced and not compacted and layered.arena.fragmentation(
                layered.mgr.pool_class) > 0):
            assert [eng.compact_now() for eng in engs][0] > 0
            compacted = True
        np.testing.assert_array_equal(_pool_bits(layered), _pool_bits(flat))
    for eng in engs:
        eng.sync_transfers()
        assert len(eng.done) == len(max_new)
        assert eng.stats["swap_outs"] >= 1 and eng.stats["swap_ins"] >= 1
        launches = eng.stats["transfers"]["kernel_launches"]
        assert launches.get("block_copy", 0) >= 1, launches
        assert_engine_quiescent(eng)
    assert forced and compacted and padded
    assert ({r.rid: list(r.generated) for r in layered.done}
            == {r.rid: list(r.generated) for r in flat.done})


@pytest.mark.parametrize("family", ["rwkv6", "hybrid"])
def test_other_constant_state_models_keep_the_flat_pool(families, family):
    """Only a model that says where each layer's state lives in a block
    gets the layered pool: RWKV6 and the Zamba2 hybrid keep ``(NB, 1,
    E)`` and the row-relaying decode."""
    cfg, model, params = families[family]
    eng = _engine(model, params)
    mgr = eng.strategy.state_mgr if family == "hybrid" else eng.mgr
    assert getattr(model, "state_block", None) is None
    assert not eng.strategy.layered
    assert mgr.pool.shape[1:] == (1, model.state_elems)


def test_resident_migrate_live_token_identity(families, tmp_path):
    """Live migration restores into a FRESH engine whose resident
    tables have never seen these requests: the adoption path must mark
    everything dirty, so every request resumes and decodes as the greedy
    reference does."""
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

    src = _engine(model, params)
    drive_pre(src)
    dst, _ = migrate_live(src, lambda: _engine(model, params),
                          str(tmp_path / "resident.npz"))
    while (dst.sched.has_work or dst.running) and dst.steps < 400:
        dst.step()
        dst.check_consistency()
    dst.sync_transfers()
    assert len(dst.done) == len(prompts)
    _assert_matches_reference(dst, model, params)
    assert_engine_quiescent(dst)


def test_resident_prefixheavy_trace_matches_eager(families):
    """Fork-heavy live traffic (the ``prefixheavy`` arrival trace):
    COW forks, suffix-only prefill and continuous admission all land on
    the delta-sync path; every request must decode as the greedy
    reference does."""
    from repro.serve.traffic import make_trace

    cfg, model, params = families["dense"]
    eng = _engine(model, params, slots=3)
    source = make_trace("prefixheavy", 8, cfg.vocab_size, seed=3,
                        mean_gap=2.0, tenants=2, max_new=6, prompt_cap=24)
    n = len(source)
    eng.serve(source, max_steps=10_000)
    eng.sync_transfers()
    assert len(eng.done) == n
    assert eng.stats["prefix_hits"] > 0          # forks really happened
    _assert_matches_reference(eng, model, params)
    assert_engine_quiescent(eng)


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid"])
def test_resident_steady_state_stops_uploading(families, family, rng):
    """The headline property: once admissions settle, decode steps stop
    shipping state to the device -- no table rows (outside block-growth
    steps) and no next-token vector (latched on device)."""
    cfg, model, params = families[family]
    pr = rng.randint(2, cfg.vocab_size, size=8)

    eng = _engine(model, params, slots=1)
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
    _assert_matches_reference(eng, model, params)


@pytest.mark.parametrize("family", ["ssm", "rwkv6", "ssm_tiles"])
def test_constant_state_batched_prefill_matches_per_sequence(families,
                                                             family):
    """The jitted, pool-donated prefill of rows whose lengths straddle two
    padded widths gives each row the next token and state row of its own
    per-sequence prefill, and leaves another live sequence's row and the
    sink row bit-identical."""
    cfg, model, params = families[family]
    eng = _engine(model, params, slots=4, num_blocks=8)
    strat = eng.strategy
    q = strat.prefill_quantum
    rng = np.random.RandomState(5)
    lens = (q - 3, q + 4, 2 * q - 1)          # widths q, 2q, 2q
    reqs = [Request(rid=i, prompt=rng.randint(2, cfg.vocab_size, size=n),
                    max_new=1) for i, n in enumerate(lens)]
    live = Request(rid=9, prompt=rng.randint(2, cfg.vocab_size, size=q),
                   max_new=1)
    for req in (live, *reqs):
        strat.admit(req.rid, req.tokens_held, req.tenant)
    strat.prefill(params, [(0, live, 0)])     # a live sequence's state

    def rows(ids):
        blocks = strat.mgr.pool[jnp.asarray(ids, jnp.int32)]
        return np.asarray(blocks).reshape(len(ids), -1)

    kept = [strat.mgr.row(live.rid), strat.sink]
    before = rows(kept)
    assert before[0].any()
    nxt, billed = strat.prefill(
        params, [(i, req, 0) for i, req in enumerate(reqs)])
    assert billed == sum(lens) and isinstance(nxt, np.ndarray)
    np.testing.assert_array_equal(rows(kept), before)
    got = rows([strat.mgr.row(req.rid) for req in reqs])
    for i, req in enumerate(reqs):
        toks = np.zeros((1, strat.prefill_width(lens[i])), np.int32)
        toks[0, :lens[i]] = req.prompt
        last, state = model.prefill(
            params, {"tokens": jnp.asarray(toks)}, model.init_state(1),
            jnp.asarray([lens[i]], jnp.int32))
        assert nxt[i] == int(jnp.argmax(last[0]))
        np.testing.assert_allclose(
            got[i], np.asarray(model.state_to_rows(state))[0],
            rtol=1e-5, atol=1e-5)
