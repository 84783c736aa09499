"""Address space and transfer plane: host time per step of the step
loop's spans for the fence, compaction, admission less its prefill,
growth, transfer dispatch and the table or row sync (their self time in
the traced stretch), per ``serve.step`` in the stretch."""

from bench import program_trace


def read(ctx):
    pt = program_trace.for_ctx(ctx)
    steps = 0 if pt is None else pt.count("serve.step")
    if steps == 0:
        return None
    return pt.self_seconds(program_trace.MANAGER_SPANS) / steps * 1e3
