"""Address space and transfer plane: tokens the decode gathers through
the block tables (``table_tokens``) per live token it attends over
(``kv_tokens``), summed over the ``serve.decode`` spans of the traced
stretch.  Nothing from a program whose decode spans carry neither."""

from bench import program_trace


def read(ctx):
    pt = program_trace.for_ctx(ctx)
    if pt is None:
        return None
    live = pt.attr_sum("serve.decode", "kv_tokens")
    table = pt.attr_sum("serve.decode", "table_tokens")
    if live <= 0 or table <= 0:
        return None
    return table / live
