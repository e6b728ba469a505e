"""Routed experts: share of the roofline reached by the operations under
the program's ``moe`` scope in the sealed decode runs of the traced
window.  Each run's work comes from the routing counters the engine
recorded in its step (``moe.tokens_here``: assignments to the experts held
here; ``moe.experts_hit``: held experts with a token; both summed over
the MoE layers) through the architecture's ``Shapes.moe_decode``; the
share is the sum over runs of max(FLOPs / peak FLOP/s, bytes / peak
bytes/s) over their summed scoped device time, in percent.  Nothing where
the architecture has no such count, no operation carries the scope or no
step has the counters."""

from bench import scopes, work

COUNTERS = ("moe.tokens_here", "moe.experts_hit")


def read(ctx):
    count = getattr(ctx.shapes, "moe_decode", None)
    timed = scopes.decode_time(ctx, "moe") if count else None
    if not timed:
        return None
    need = spent = 0.0
    for rec, ns in timed:
        c = scopes.step_counters(ctx, rec, COUNTERS)
        if c is None:
            continue
        need += work.roofline_seconds(*count(c[0], c[1], len(rec.positions)), ctx.peaks)
        spent += ns / 1e9
    return 100.0 * need / spent if spent else None
