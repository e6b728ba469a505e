"""Dispatch: 95th percentile of the quantum grant latency the pool arbiter
recorded (``DispatchMetrics.grant_latency``) during the window, in ms."""

import numpy as np


def read(ctx):
    if not ctx.window.grants:
        return None
    return float(np.percentile(ctx.window.grants, 95)) * 1e3
