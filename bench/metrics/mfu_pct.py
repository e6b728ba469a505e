"""Whole step: model FLOPs of every prefill and decode token of the engine
steps in the window (true prompt lengths, live positions; ``bench.work``)
over the bf16 peak times the summed wall time of the dispatcher's
``step:<lane>`` spans in the window, in percent."""


def read(ctx):
    flops = 0
    for recs in ctx.records.values():
        for r in recs:
            if ctx.window.w0 <= r.t0 and r.t1 <= ctx.window.w1:
                flops += ctx.shapes.decode(r.positions)[0]
                flops += sum(ctx.shapes.prefill(p)[0] for p in r.prefills)
    step_s = sum(e.dur for e in ctx.spans
                 if e.ph == "X" and e.name.startswith("step:")
                 and ctx.window.w0 <= e.ts and e.ts + e.dur <= ctx.window.w1)
    return 100.0 * flops / (ctx.peaks.bf16_flops * step_s) if step_s else None
