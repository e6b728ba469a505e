"""Kernels: share of the roofline reached by the sealed decode program.

For every decode run in the traced window, the work it had to do comes
from the benchmark's record of the engine step the run belongs to (the
``bench.step`` annotation that holds it): the cached positions of its
live slots.  The share is the sum over runs of
max(FLOPs / peak FLOP/s, bytes / peak bytes/s) over their summed device
time, in percent (``bench.work``)."""

from bench import work


def read(ctx):
    need = spent = 0.0
    for kind, dev_run, rec in ctx.sealed_runs():
        if kind != "decode":
            continue
        flops, nbytes = ctx.shapes.decode(rec.positions)
        need += work.roofline_seconds(flops, nbytes, ctx.peaks)
        spent += dev_run.dur / 1e9
    return 100.0 * need / spent if spent else None
