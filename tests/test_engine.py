"""Serving engine: continuous batching, admission by blocks, preemption
and swap, COW fork -- against step-by-step single-request decoding.

Every pinned schedule here runs with ``prefill_budget=None``: the
engine's default is the adaptive ``"auto"`` budget, which derives
admission pacing from MEASURED wall time and is deliberately not
deterministic across runs (live-traffic coverage lives in
test_request_plane.py)."""

import pytest
import jax

from repro.configs.base import get_config
from repro.models.api import build_model
from repro.serve.engine import Engine, Request
from conftest import assert_engine_quiescent, greedy_reference


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gemma_2b").reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_matches_reference(setup, rng):
    cfg, model, params = setup
    eng = Engine(model, params, slots=2, max_seq=64, num_blocks=24,
                 eos_id=-1, prefill_budget=None)
    prompts = [rng.randint(2, cfg.vocab_size, size=n) for n in (5, 9, 3)]
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr, max_new=6))
    done = eng.run(max_steps=200)
    assert len(done) == 3
    for req in sorted(done, key=lambda r: r.rid):
        ref = greedy_reference(model, params, req.prompt, 6,
                               cfg.kv_block_tokens)
        assert req.generated == ref, (req.rid, req.generated, ref)
    assert_engine_quiescent(eng)


def test_engine_admission_pressure(setup, rng):
    """More requests than pool capacity: queueing + eventual completion,
    pool never over-committed."""
    cfg, model, params = setup
    eng = Engine(model, params, slots=2, max_seq=32, num_blocks=10,
                 eos_id=-1, prefill_budget=None)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=rng.randint(2, 100, size=6),
                           max_new=4))
    peak = 0
    while (eng.sched.has_work or eng.running) and eng.steps < 300:
        eng.step()
        eng.check_consistency()
        peak = max(peak, eng.mgr.allocator.num_used)
    assert len(eng.done) == 5
    assert peak <= 10
    assert_engine_quiescent(eng)


def test_engine_swap_out_in(setup, rng):
    cfg, model, params = setup
    eng = Engine(model, params, slots=2, max_seq=64, num_blocks=32,
                 eos_id=-1, prefill_budget=None)
    pr = rng.randint(2, 100, size=8)
    eng.submit(Request(rid=0, prompt=pr, max_new=8))
    for _ in range(3):
        eng.step()
    partial = list(eng.running.values())[0].generated[:]
    eng.preempt_latest()
    assert len(eng.preempted) == 1 and not eng.running
    done = eng.run(max_steps=100)
    assert len(done) == 1
    ref = greedy_reference(model, params, pr, 8, cfg.kv_block_tokens)
    assert done[0].generated == ref
    assert done[0].generated[: len(partial)] == partial
    assert eng.store.stats.swap_outs == 1 and eng.store.stats.swap_ins == 1
    assert_engine_quiescent(eng)


def test_engine_preempt_keys_on_admission_order(setup, rng):
    """LIFO preemption evicts the most recently ADMITTED request, not
    the largest rid: a request submitted early but resumed late is the
    first victim."""
    cfg, model, params = setup
    eng = Engine(model, params, slots=2, max_seq=64, num_blocks=32,
                 eos_id=-1, prefill_budget=None)
    eng.submit(Request(rid=0, prompt=rng.randint(2, 100, size=6),
                       max_new=8))
    eng.submit(Request(rid=1, prompt=rng.randint(2, 100, size=6),
                       max_new=8))
    eng.step()
    assert len(eng.running) == 2
    eng.preempt_latest()           # evicts rid=1 (admitted second)
    eng.step()                     # resumes rid=1 -> NOW newest by admission
    assert sorted(r.rid for r in eng.running.values()) == [0, 1]
    orders = {r.rid: r.admit_order for r in eng.running.values()}
    assert orders[1] > orders[0]
    eng.preempt_latest()
    assert {r.rid for r in eng.running.values()} == {0}
    done = eng.run(max_steps=200)
    assert len(done) == 2
    for req in done:
        ref = greedy_reference(model, params, req.prompt, 8,
                               cfg.kv_block_tokens)
        assert req.generated == ref
    assert_engine_quiescent(eng)


def test_engine_preempt_during_extend_consistent(setup, rng):
    """Regression: growth-pressure preemption mid-extend must leave
    running/seq_lens/tables consistent every step, and everything still
    completes token-identically."""
    cfg, model, params = setup
    # pool sized so concurrent growth forces extend-time preemption:
    # 2 slots x ceil(20/8)=3 blocks worst case + sink = 7 > 6
    eng = Engine(model, params, slots=2, max_seq=32, num_blocks=6,
                 eos_id=-1, prefill_budget=None)
    prompts = [rng.randint(2, 100, size=n) for n in (8, 7, 6)]
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr, max_new=12))
    while (eng.sched.has_work or eng.running) and eng.steps < 400:
        eng.step()
        eng.check_consistency()
    assert len(eng.done) == 3
    assert eng.preemptions > 0     # pressure actually fired
    for req in sorted(eng.done, key=lambda r: r.rid):
        ref = greedy_reference(model, params, req.prompt, 12,
                               cfg.kv_block_tokens, max_seq=32)
        assert req.generated == ref, (req.rid, req.generated, ref)
    assert_engine_quiescent(eng)


def test_engine_cow_fork(setup, rng):
    """A duplicate prompt forks instead of re-prefilling: prefix blocks
    shared (refcount 2), divergence resolved by the COW barrier, both
    outputs token-identical to the reference."""
    cfg, model, params = setup
    eng = Engine(model, params, slots=2, max_seq=64, num_blocks=32,
                 eos_id=-1, prefill_budget=None)
    pr = rng.randint(2, 100, size=16)   # 2 full blocks
    eng.submit(Request(rid=0, prompt=pr, max_new=4))
    eng.step()
    eng.submit(Request(rid=1, prompt=pr.copy(), max_new=4))
    eng.step()
    assert eng.prefix_hits == 1
    shared = eng.mgr.tables[1][:2]
    assert shared == eng.mgr.tables[0][:2]
    assert all(eng.mgr.allocator.refcount(b) == 2 for b in shared)
    done = eng.run(max_steps=100)
    assert len(done) == 2
    ref = greedy_reference(model, params, pr, 4, cfg.kv_block_tokens)
    for req in done:
        assert req.generated == ref
    assert_engine_quiescent(eng)
