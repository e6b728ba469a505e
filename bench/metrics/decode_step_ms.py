"""Model step: mean device time per run of the sealed decode program
(``decode_body`` -> ``transformer.decode_step``) in the traced window, in ms."""


def read(ctx):
    runs = ctx.runs("decode")
    return sum(r.dur for r in runs) / len(runs) / 1e6 if runs else None
