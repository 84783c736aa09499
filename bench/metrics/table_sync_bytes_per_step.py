"""Address space and transfer plane: bytes the table sync uploaded (the
``bytes`` of each ``serve.sync`` span in the traced stretch) per
``serve.step`` in the stretch.  Nothing from a program whose sync spans
carry no ``bytes``."""

from bench import program_trace


def read(ctx):
    pt = program_trace.for_ctx(ctx)
    if pt is None:
        return None
    steps = pt.count("serve.step")
    if steps == 0 or not any("bytes" in s.attrs for s in pt.spans
                             if s.name == "serve.sync"):
        return None
    return pt.attr_sum("serve.sync", "bytes") / steps
