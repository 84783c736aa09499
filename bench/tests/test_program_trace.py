"""The program-span reduction (``bench/program_trace.py``) on synthetic
intervals and on traces, and the four readers that use the program's
spans and stamps, on a synthetic run context."""

import gzip
import shutil
import types
from pathlib import Path

import pytest

from bench import program_trace
from bench.metrics import (manager_ms_per_step, prefill_compiles,
                           prefill_idle_share, sched_wait_p95_ms)
from bench.program_trace import BETWEEN, HostSpan, build
from bench.window import Trip

FIXTURE = Path(__file__).resolve().parent / "data" / "fixture.xplane.pb.gz"
MS = 1_000_000                                   # ns


def _step(t, prefill=False, compiles=0):
    """One 100 ms step at ``t`` ms: fence 1, admit 10 (with a 7 ms prefill
    inside when asked), sync 2, decode 80, retire 1."""
    s = [HostSpan("serve.step", t * MS, (t + 100) * MS, {"batch": 2}),
         HostSpan("serve.fence", t * MS, (t + 1) * MS, {}),
         HostSpan("serve.admit", (t + 1) * MS, (t + 11) * MS, {}),
         HostSpan("serve.sync", (t + 12) * MS, (t + 14) * MS, {"W": 2}),
         HostSpan("serve.decode", (t + 14) * MS, (t + 94) * MS, {}),
         HostSpan("serve.retire", (t + 94) * MS, (t + 95) * MS, {})]
    if prefill:
        s.append(HostSpan("serve.prefill", (t + 2) * MS, (t + 9) * MS,
                          {"compiles": compiles} if compiles else {}))
    return s


def _trace():
    """Two steps in a 300 ms stretch; the device runs 5 ms of the first
    step's prefill and the decodes, and idles between steps."""
    lines = [_step(0, prefill=True, compiles=3) + _step(150)]
    device = [[(2 * MS, 4 * MS), (6 * MS, 9 * MS), (14 * MS, 94 * MS),
               (164 * MS, 244 * MS)]]
    return build((0, 300 * MS), lines, device)


def test_self_time_and_counts():
    pt = _trace()
    assert pt.count("serve.step") == 2 and pt.count("serve.prefill") == 1
    by = {(s.name, s.start): s.self_ns for s in pt.spans}
    assert by[("serve.admit", 1 * MS)] == 3 * MS      # 10 less the prefill
    assert by[("serve.admit", 151 * MS)] == 10 * MS
    assert by[("serve.step", 0)] == 100 * MS - 94 * MS
    assert pt.self_seconds(["serve.fence", "serve.sync"]) == pytest.approx(
        0.006)
    assert pt.attr_sum("serve.prefill", "compiles") == 3


def test_idle_goes_to_the_innermost_span_at_its_midpoint():
    pt = _trace()
    idle = pt.idle_s
    # gaps 0-2 (midpoint 1 ms: the admission has just started), 4-6 in
    # the prefill, 9-14 (11.5: in the step, past the admission), 94-164
    # and 244-300 between steps
    assert idle["serve.admit"] == pytest.approx(0.002)
    assert idle["serve.prefill"] == pytest.approx(0.002)
    assert idle["serve.step"] == pytest.approx(0.005)
    assert idle[BETWEEN] == pytest.approx(0.070 + 0.056)
    assert sum(idle.values()) == pytest.approx(0.300 - 0.085 - 0.080)
    assert pt.idle_share("serve.prefill") == pytest.approx(0.002 / 0.3)


def test_spans_outside_the_stretch_are_left_out():
    lines = [_step(0) + _step(150) + _step(400)]
    pt = build((100 * MS, 300 * MS), lines, [[(150 * MS, 160 * MS)]])
    assert pt.count("serve.step") == 1
    assert pt.window_s == pytest.approx(0.2)


def test_nothing_without_program_spans_or_device():
    assert build((0, MS), [], [[(0, 1)]]) is None
    assert build((0, MS), [_step(0)], []) is None


def test_a_trace_without_program_spans_reduces_to_nothing(tmp_path):
    path = tmp_path / "fixture.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    assert program_trace.reduce(str(path)) is None


def test_host_spans_read_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.serve.telemetry import Telemetry
    tel = Telemetry()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(program_trace.WINDOW_SPAN):
            with tel.span("serve.step") as sp:
                with tel.span("serve.prefill") as inner:
                    jax.jit(lambda x: x + 2)(jnp.ones(13)).block_until_ready()
                    inner.set(seqs=1)
                sp.set(batch=1)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    window, lines, _ = program_trace.read(str(path))
    assert window is not None
    spans = {s.name: s for line in lines for s in line}
    assert spans["serve.prefill"].attrs["seqs"] == 1
    assert spans["serve.prefill"].attrs["compiles"] >= 1
    assert spans["serve.step"].attrs == {"batch": 1}
    assert spans["serve.step"].start <= spans["serve.prefill"].start


# --------------------------------------------------------------------------
# readers
# --------------------------------------------------------------------------
def _ctx(trace=True, trace_host=(0.0, 0.0)):
    reqs, trips = {}, []
    for rid, (due, submit, admit) in enumerate([(1.0, 1.1, 1.3),
                                                (2.0, 2.0, 2.5),
                                                (3.0, 3.2, -1.0),
                                                (9.0, 9.0, 9.1)]):
        reqs[rid] = types.SimpleNamespace(t_submit=submit, t_admit=admit)
        trips.append(Trip(rid, due=due, submitted=submit))
    rec = types.SimpleNamespace(reqs=reqs)
    return types.SimpleNamespace(trips=trips, rec=rec, t0=0.0, t1=5.0,
                                 trace=object() if trace else None,
                                 trace_host=trace_host)


def test_sched_wait_reads_the_program_stamps():
    # due in the window: waits 0.2 and 0.5 s, and 1.8 s for the request
    # still waiting at the close (5.0); the one due at 9.0 is left out
    assert sched_wait_p95_ms.read(_ctx()) == pytest.approx(
        (0.5 + 0.9 * 1.3) * 1e3)


def test_sched_wait_leaves_out_waits_over_the_profilers_edges():
    # the stretch opens inside the first wait and closes inside the
    # third: only the 0.5 s wait is left
    ctx = _ctx(trace_host=(1.2, 4.0))
    assert sched_wait_p95_ms.read(ctx) == pytest.approx(500.0)


def test_sched_wait_reads_nothing_without_the_stamp():
    ctx = _ctx()
    for req in ctx.rec.reqs.values():
        del req.t_admit
    assert sched_wait_p95_ms.read(ctx) is None


def test_trace_readers(monkeypatch):
    pt = _trace()
    monkeypatch.setattr(program_trace, "for_ctx", lambda ctx: pt)
    ctx = _ctx()
    assert prefill_compiles.read(ctx) == 3.0     # 3 over one prefill
    # fence 1 + admit (3, then 10) + sync 2, twice, over two steps
    assert manager_ms_per_step.read(ctx) == pytest.approx(
        (1 + 3 + 2 + 1 + 10 + 2) / 2)
    assert prefill_idle_share.read(ctx) == pytest.approx(100 * 0.002 / 0.3)


def test_prefill_compiles_is_per_prefill(monkeypatch):
    lines = [_step(0, prefill=True, compiles=1) + _step(150, prefill=True)]
    pt = build((0, 300 * MS), lines, [[(14 * MS, 94 * MS)]])
    monkeypatch.setattr(program_trace, "for_ctx", lambda ctx: pt)
    assert prefill_compiles.read(_ctx()) == 0.5
    pt = build((0, 300 * MS), [_step(0)], [[(14 * MS, 94 * MS)]])
    assert prefill_compiles.read(_ctx()) is None  # no prefill to read


def test_trace_readers_read_nothing_without_spans(monkeypatch):
    monkeypatch.setattr(program_trace, "for_ctx", lambda ctx: None)
    for reader in (prefill_compiles, manager_ms_per_step,
                   prefill_idle_share):
        assert reader.read(_ctx()) is None


def test_an_untraced_run_has_no_program_trace():
    assert program_trace.for_ctx(_ctx(trace=False)) is None
