"""Engine step (host): share of the traced window in which the device was
idle while the engine's ``engine.h2d`` span was open (the host putting a
step's inputs on the device), averaged over the chips, in percent."""

from bench import spans


def read(ctx):
    return spans.idle_share_under(ctx.trace, ("engine.h2d",))
