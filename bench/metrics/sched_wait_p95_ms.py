"""Scheduler: 95th percentile of submit to admission, on the program's
own stamps (``Request.t_submit``, and ``Request.t_admit``, the start of
the first prefill that took the request), over requests due in the
window; a request not admitted by the close counts until the close, as
in ``ttft_p95_ms``.  A wait that spans the opening or the closing of the
traced stretch (``ctx.trace_host``) is left out: the profiler's start
and stop stall the loop there, which is not the scheduler's doing.
Nothing from a program without the admission stamp."""

from bench.window import due_in, pct


def read(ctx):
    edges = [t for t in ctx.trace_host if t > 0]
    waits = []
    for trip in due_in(ctx.trips, ctx.t0, ctx.t1):
        req = ctx.rec.reqs.get(trip.rid)
        t_admit = getattr(req, "t_admit", None)
        if t_admit is None:
            return None
        end = t_admit if 0 <= t_admit <= ctx.t1 else ctx.t1
        if any(req.t_submit <= t <= end for t in edges):
            continue
        waits.append(end - req.t_submit)
    v = pct(waits, 95)
    return None if v is None else v * 1e3
