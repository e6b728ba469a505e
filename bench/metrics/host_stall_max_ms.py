"""Host process: the longest ``host.stall`` span (the obs tracer's stall
watch: a heartbeat that woke 50 ms or more late) that starts in the
window, over the whole window, in ms; 0.0 where the watch ran (its
once-a-second ``host.lag_ms`` samples lie in the window) and saw none."""


def read(ctx):
    inside = [e for e in ctx.spans if ctx.window.w0 <= e.ts <= ctx.window.w1]
    if not any(e.ph == "C" and e.name == "host.lag_ms" for e in inside):
        return None
    return max((e.dur * 1e3 for e in inside if e.ph == "X" and e.name == "host.stall"),
               default=0.0)
