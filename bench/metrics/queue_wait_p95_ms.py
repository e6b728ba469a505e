"""Engine admission: 95th percentile, over the requests due in the window,
of the time from when a request was due to the start of its ``prefill``
span (the program's obs tracer, matched by request id), in ms."""

import numpy as np


def read(ctx):
    starts = {e.rid: e.ts for e in ctx.spans if e.name == "prefill" and e.ph == "X"}
    waits = [(starts[s.req.rid] - s.due) * 1e3 for s in ctx.sent
             if ctx.window.w0 <= s.due < ctx.window.w1 and s.req.rid in starts]
    return float(np.percentile(waits, 95)) if waits else None
