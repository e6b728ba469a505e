"""Latent attention: share of the roofline reached by the operations
under the program's ``mla`` scope in the sealed decode runs of the traced
window.  Each run's work is the absorbed-form MLA count of its step's
live positions (the architecture's ``Shapes.mla_decode``); the share is
the sum over runs of max(FLOPs / peak FLOP/s, bytes / peak bytes/s) over
their summed scoped device time, in percent (``bench.work``).  Nothing
where the architecture has no such count or no operation carries the
scope."""

from bench import scopes, work


def read(ctx):
    count = getattr(ctx.shapes, "mla_decode", None)
    timed = scopes.decode_time(ctx, "mla") if count else None
    if not timed:
        return None
    need = sum(work.roofline_seconds(*count(rec.positions), ctx.peaks) for rec, _ in timed)
    spent = sum(ns for _, ns in timed) / 1e9
    return 100.0 * need / spent if spent else None
