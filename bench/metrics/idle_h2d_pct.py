"""Engine step (host): share of the traced window in which the device was
idle while the engine's ``engine.h2d`` span was open for it (the host
putting a step's inputs on the device; a span counts against its lane's
chip, ``bench.spans``), averaged over the chips, in percent."""

from bench import spans


def read(ctx):
    return spans.idle_share_under(ctx, ("engine.h2d",))
