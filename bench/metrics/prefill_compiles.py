"""Model step: backend compiles (or persistent-cache loads) the program
charged to its ``serve.prefill`` spans in the traced stretch, per
``serve.prefill`` (the compile counts on those spans in the trace): 1
when every prefill call compiles, 0 when none does, whatever the number
of prefills the stretch holds."""

from bench import program_trace


def read(ctx):
    pt = program_trace.for_ctx(ctx)
    n = 0 if pt is None else pt.count("serve.prefill")
    if n == 0:
        return None
    return pt.attr_sum("serve.prefill", "compiles") / n
