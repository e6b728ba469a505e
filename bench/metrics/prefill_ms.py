"""Engine prefill: mean device time per run of the sealed prefill program
(``prefill_body``) in the traced window, in ms."""


def read(ctx):
    runs = ctx.runs("prefill")
    return sum(r.dur for r in runs) / len(runs) / 1e6 if runs else None
