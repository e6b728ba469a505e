"""Engine step (host): share of the traced window in which the device was
idle while the engine's ``engine.readback`` span was open for it (the
host blocked reading a step's tokens back; a span counts against its
lane's chip, ``bench.spans``), averaged over the chips, in percent."""

from bench import spans


def read(ctx):
    return spans.idle_share_under(ctx, ("engine.readback",))
