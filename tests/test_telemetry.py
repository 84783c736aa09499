"""Spans and compile counters of the serving step loop
(``repro.serve.telemetry``): span totals, compile attribution, the
step loop's phase boundaries, the admission stamp, and the spans' place
on the host plane of a profiler trace."""

import dataclasses
import glob
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.api import build_model
from repro.serve import telemetry
from repro.serve.disagg import PrefillWorker
from repro.serve.engine import Engine, Request
from repro.serve.telemetry import Telemetry, compile_totals
from conftest import assert_engine_quiescent


@pytest.fixture
def ticks(monkeypatch):
    """A clock that advances by one at every reading."""
    counter = itertools.count()
    monkeypatch.setattr(telemetry, "_clock", lambda: float(next(counter)))


@pytest.fixture(scope="module")
def families():
    out = {}
    for key, name in (("paged", "gemma_2b"), ("ssm", "mamba2_370m"),
                      ("rwkv6", "rwkv6_7b"), ("mla", "minicpm3_4b"),
                      ("ssm_tiles", "mamba2_370m")):
        cfg = get_config(name).reduced()
        if key == "ssm_tiles":        # 128-wide SSD state: layered pool
            cfg = dataclasses.replace(
                cfg, ssm=dataclasses.replace(cfg.ssm, state_dim=128))
        model = build_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(7))
        out[key] = (cfg, model, params)
    return out


def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("num_blocks", 24)
    return Engine(model, params, eos_id=-1, prefill_budget=None, **kw)


def _request(cfg, rid, rng, n=8, max_new=4):
    return Request(rid=rid, prompt=rng.randint(2, cfg.vocab_size, size=n),
                   max_new=max_new)


# --------------------------------------------------------------------------
# span arithmetic
# --------------------------------------------------------------------------
def test_nested_spans_self_time_and_lead(ticks):
    """Nested spans sum their own seconds (a parent's include its
    children's), and a span opened with ``lead`` sums the time from its
    parent's start."""
    tel = Telemetry()
    tel.capture(True)
    with tel.span("outer"):               # t0 = 0
        with tel.span("a", lead=True):    # 1 .. 2, lead 1
            pass
        with tel.span("b"):               # 3 .. 6
            with tel.span("a", lead=True):    # 4 .. 5, lead 1
                pass
    records = tel.capture(False)          # outer ends at 7
    a, b, o = (tel.totals(n) for n in ("a", "b", "outer"))
    assert (o.count, o.seconds, o.lead_s) == (1, 7.0, 0.0)
    assert (b.count, b.seconds, b.lead_s) == (1, 3.0, 0.0)
    assert (a.count, a.seconds, a.lead_s) == (2, 2.0, 2.0)
    assert [(r["name"], r["t0"], r["t1"]) for r in records] == [
        ("a", 1.0, 2.0), ("a", 4.0, 5.0), ("b", 3.0, 6.0),
        ("outer", 0.0, 7.0)]
    assert tel.totals("never").count == 0


def test_attributes_kept_only_while_someone_reads_them():
    tel = Telemetry()
    with tel.span("x") as sp:
        sp.set(n=1)
    assert not sp.recording and sp.attrs == {}
    tel.capture(True)
    with tel.span("x") as sp:
        sp.set(n=2, k="v")
    assert sp.recording
    (rec,) = tel.capture(False)
    assert (rec["k"], rec["n"], rec["name"]) == ("v", 2, "x")
    with tel.span("x"):
        pass
    assert tel.records is None and tel.capture(False) == []
    assert tel.totals("x").count == 3


# --------------------------------------------------------------------------
# compiles
# --------------------------------------------------------------------------
def test_compiles_charged_to_innermost_span_or_outside():
    """A compile inside spans is charged to the innermost; one outside
    every span only to the process totals."""
    tel = Telemetry()
    n0, s0, _ = compile_totals()
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    tel.capture(True)
    with tel.span("serve.step"):
        with tel.span("serve.prefill"):
            f(jnp.ones(1237)).block_until_ready()   # a fresh shape
    prefill, step = tel.capture(False)
    inner = tel.totals("serve.prefill").compiles
    assert inner >= 1 and compile_totals()[1] > s0
    assert prefill["compiles"] == inner and "compiles" not in step
    assert tel.totals("serve.step").compiles == 0
    assert compile_totals()[0] - n0 == inner
    f(jnp.ones(1238)).block_until_ready()            # no span open
    outside = compile_totals()[0] - n0 - inner
    assert outside >= 1
    assert tel.totals("serve.prefill").compiles == inner
    assert tel.totals("serve.step").compiles == 0
    f(jnp.ones(1237)).block_until_ready()            # cached: no compile
    assert compile_totals()[0] - n0 == inner + outside


def test_engine_prefill_compiles_land_in_prefill_span(families):
    cfg, model, params = families["ssm"]
    eng = _engine(model, params, slots=2, num_blocks=4)
    rng = np.random.RandomState(0)
    eng.submit(_request(cfg, 0, rng, n=11))
    eng.run(max_steps=50)
    prefill = eng.telemetry.totals("serve.prefill")
    assert prefill.compiles >= 1 and prefill.count == 1
    assert eng.telemetry.totals("serve.step").count == eng.steps
    assert_engine_quiescent(eng)


@pytest.mark.parametrize("family", ["ssm", "rwkv6"])
def test_constant_state_prefill_compiles_once_per_shape(family, families):
    """The constant-state prefill is one jitted program per (rows,
    width): the first prefill at a shape compiles it, a second at that
    shape charges no compile to ``serve.prefill``."""
    cfg, model, params = families[family]
    eng = _engine(model, params, slots=2, num_blocks=4)
    rng = np.random.RandomState(0)
    q = eng.strategy.prefill_quantum
    eng.submit(_request(cfg, 0, rng, n=q + 3, max_new=2))
    eng.run(max_steps=50)
    first = eng.telemetry.totals("serve.prefill").compiles
    assert first >= 1
    eng.submit(_request(cfg, 1, rng, n=2 * q, max_new=2))  # same width
    eng.run(max_steps=50)
    prefill = eng.telemetry.totals("serve.prefill")
    assert prefill.count == 2 and prefill.compiles == first
    assert_engine_quiescent(eng)


# --------------------------------------------------------------------------
# the step loop's phases
# --------------------------------------------------------------------------
@pytest.mark.parametrize("family", ["paged", "ssm"])
def test_phase_time_keeps_the_step_boundaries(family, families, ticks):
    """``dispatch`` runs from a step's start to its sync, ``sync``,
    ``decode`` and ``retire`` are their spans, and a step that ends
    before a sync (nothing running) counts in no phase."""
    cfg, model, params = families[family]
    eng = _engine(model, params, num_blocks=24 if family == "paged" else 4)
    rng = np.random.RandomState(1)
    eng.telemetry.capture(True)
    eng.step()                                   # idle: nothing queued
    for rid, at in ((0, 0), (1, 0), (2, 3)):     # a scripted arrival trace
        while eng.steps < at:
            eng.step()
        eng.submit(_request(cfg, rid, rng, n=6 + 3 * rid, max_new=3))
    while eng.running or eng.sched.has_work:
        eng.step()
    eng.step()                                   # idle again
    records = eng.telemetry.capture(False)
    steps = [r for r in records if r["name"] == "serve.step"]
    assert len(steps) == eng.steps
    want = {"dispatch": 0.0, "sync": 0.0, "decode": 0.0, "retire": 0.0}
    idle = 0
    for s in steps:
        mine = {r["name"]: r for r in records
                if s["t0"] < r["t0"] and r["t1"] < s["t1"]}
        if "serve.sync" not in mine:
            idle += 1
            assert "serve.decode" not in mine
            continue
        want["dispatch"] += mine["serve.sync"]["t0"] - s["t0"]
        for k in ("sync", "decode", "retire"):
            r = mine[f"serve.{k}"]
            want[k] += r["t1"] - r["t0"]
    assert idle >= 2
    assert eng.stats["phase_time_s"] == want
    assert all(v > 0 for v in want.values())
    assert_engine_quiescent(eng)


@pytest.mark.parametrize("family", ["paged", "ssm"])
def test_translation_counts_on_the_step_spans(family, families):
    """With capture on, each step's ``serve.decode`` carries the live
    tokens its sequences attend over (their ``tokens_held`` at the
    decode, one less than after it) and, for the paged discipline, the
    tokens its table gather covers; ``serve.sync`` carries the rows and
    bytes ``Engine`` adds to ``table_rows_updated`` and
    ``table_sync_bytes``; ``serve.grow`` the blocks grown, one for each
    sequence whose next token starts a block.  The constant-state
    discipline keeps its spans, with no ``table_tokens``."""
    cfg, model, params = families[family]
    eng = _engine(model, params, num_blocks=24 if family == "paged" else 4)
    paged = family == "paged"
    bt = eng.strategy.block_tokens
    rng = np.random.RandomState(5)
    for rid, n in enumerate((7, 8, 13)):
        eng.submit(_request(cfg, rid, rng, n=n, max_new=10))
    decodes = 0
    while eng.running or eng.sched.has_work:
        n_done = len(eng.done)
        rows0, bytes0 = eng.table_rows_updated, eng.table_sync_bytes
        eng.telemetry.capture(True)
        eng.step()
        recs = {r["name"]: r for r in eng.telemetry.capture(False)}
        assert {"serve.step", "serve.sync", "serve.decode",
                "serve.retire"} <= set(recs)
        held = [r.tokens_held - 1 for r in
                list(eng.running.values()) + eng.done[n_done:]]
        dec, sync = recs["serve.decode"], recs["serve.sync"]
        assert dec["kv_tokens"] == sum(held) and dec["seqs"] == len(held)
        if paged:
            kv = eng.cache.config
            assert dec["table_tokens"] == (eng.slots * kv.max_blocks_per_seq
                                           * kv.block_tokens)
        else:
            assert "table_tokens" not in dec
        assert sync["rows"] == eng.table_rows_updated - rows0
        assert sync["bytes"] == eng.table_sync_bytes - bytes0
        want = sum(1 for h in held if h % bt == 0) if paged else 0
        assert recs["serve.grow"]["blocks"] == want
        decodes += 1
    assert decodes > 0 and eng.table_sync_bytes > 0
    assert_engine_quiescent(eng)


@pytest.mark.parametrize("family,layered", [("ssm_tiles", 1), ("ssm", 0),
                                            ("rwkv6", 0), ("mla", 0)])
def test_decode_span_says_whether_state_is_updated_in_place(
        family, layered, families):
    """``serve.decode`` carries ``layered``: 1 on every decode of a
    Mamba-2 state updated in place layer by layer, 0 on a Mamba-2 state
    too narrow for whole tiles, on RWKV6's flat state rows and on the
    paged MLA decode."""
    cfg, model, params = families[family]
    eng = _engine(model, params)
    rng = np.random.RandomState(3)
    for rid in range(3):
        eng.submit(_request(cfg, rid, rng, max_new=3))
    eng.telemetry.capture(True)
    eng.run(100)
    decodes = [r for r in eng.telemetry.capture(False)
               if r["name"] == "serve.decode"]
    assert len(eng.done) == 3 and decodes
    assert {r["layered"] for r in decodes} == {layered}
    assert_engine_quiescent(eng)


def test_admission_stamp_set_once_not_on_resume(families):
    cfg, model, params = families["paged"]
    eng = _engine(model, params, num_blocks=32)
    rng = np.random.RandomState(2)
    req = _request(cfg, 0, rng, n=8, max_new=8)
    eng.submit(req)
    for _ in range(3):
        eng.step()
    t_admit = req.t_admit
    assert req.t_submit <= t_admit <= req.t_first
    eng.preempt_latest()
    assert req.state == "preempted"
    eng.run(max_steps=100)
    assert req.state == "done" and eng.store.stats.swap_ins == 1
    assert req.t_admit == t_admit
    assert eng.telemetry.totals("serve.prefill").count == 1
    assert_engine_quiescent(eng)


def test_prefill_worker_times_through_the_prefill_span(families):
    cfg, model, params = families["paged"]
    pre = PrefillWorker(model, params, max_seq=64, num_blocks=24,
                        eos_id=-1, prefill_budget=None)
    req = _request(cfg, 5, np.random.RandomState(3), n=10)
    req.t_submit = 0.0
    tok, bundles = pre.prefill_one(req)
    assert pre.engine.telemetry.totals("serve.prefill").count == 1
    assert pre.engine.prefill_tokens == 10
    assert 0.0 < req.t_admit <= req.t_first
    assert bundles and isinstance(tok, int)
    pre.engine.release_arena()


# --------------------------------------------------------------------------
# the spans on a profiler trace
# --------------------------------------------------------------------------
def test_spans_land_on_the_host_plane(families, tmp_path):
    from jax.profiler import ProfileData
    cfg, model, params = families["ssm"]
    eng = _engine(model, params, num_blocks=4)
    rng = np.random.RandomState(4)
    eng.submit(_request(cfg, 0, rng, n=9, max_new=2))
    eng.step()                           # compile outside the trace
    eng.submit(_request(cfg, 1, rng, n=9, max_new=2))
    with jax.profiler.trace(str(tmp_path)):
        while eng.running or eng.sched.has_work:
            eng.step()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        found.setdefault(ev.name, dict(ev.stats))
    assert {"serve.step", "serve.prefill", "serve.sync", "serve.decode",
            "serve.retire"} <= set(found)
    assert found["serve.prefill"]["seqs"] == 1
    assert found["serve.prefill"]["tokens"] == 9
    assert found["serve.decode"]["W"] >= 1
    # the first request holds 10 tokens (9 and its first), the second 9
    assert found["serve.decode"]["kv_tokens"] == 19
    assert found["serve.sync"]["rows"] >= 1
    assert found["serve.sync"]["bytes"] > 0
    assert_engine_quiescent(eng)
