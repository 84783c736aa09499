"""Device: share of the traced stretch in which the device was idle
while the host was inside a ``serve.prefill`` span (each idle interval
goes to the innermost program span covering its midpoint)."""

from bench import program_trace


def read(ctx):
    pt = program_trace.for_ctx(ctx)
    if pt is None:
        return None
    return 100.0 * pt.idle_share("serve.prefill")
