"""The whole request on the host's clock: 95th percentile, over the
requests due in the window, of the time from when a request was due to its
first token on the host, in ms; as the end-to-end ``ttft_p95_ms``, for a
cell where a host stall moves that tail too far to bound it."""

from bench import run, serve


def read(ctx):
    ttft = run.ttft_ms(ctx.sent, ctx.window, ctx.window.w1 + serve.LATE_S)
    return run.percentile(ttft, 95) if ttft else None
