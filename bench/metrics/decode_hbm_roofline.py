"""Model step: bytes the served decode steps must move (``decode_bytes``
of the cell's reference: every weight once, the live cached latent read,
the new latent written), from the ``kv_tokens`` and ``seqs`` of each
``serve.decode`` span in the traced stretch, over those spans' seconds,
as a share of peak HBM bandwidth.  Nothing from a program whose decode
spans carry no ``kv_tokens``."""

from bench import program_trace


def read(ctx):
    pt = program_trace.for_ctx(ctx)
    if pt is None:
        return None
    spans = [s for s in pt.spans
             if s.name == "serve.decode" and "kv_tokens" in s.attrs]
    seconds = sum(s.end - s.start for s in spans) * 1e-9
    if seconds <= 0:
        return None
    moved = sum(ctx.arch.decode_bytes(ctx.cell.cfg, s.attrs["kv_tokens"],
                                      s.attrs["seqs"]) for s in spans)
    return 100.0 * moved / seconds / ctx.peaks["hbm_bytes_per_s"]
