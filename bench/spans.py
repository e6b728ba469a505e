"""Device-idle time under the program's own spans.

The program's ``repro.obs`` scoped spans (``SpanTracer.span``) are also
profiler ``TraceMe`` events, so they lie on the host threads of the
device trace, on the same clock as the device's work.  From them: how much
of each chip's idle time in the traced window fell while a span of a given
name was open for that chip's lane.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Optional

from bench import trace as trace_mod


def held_intervals(ctx, names) -> dict:
    """The intervals (ns) of host events named in ``names``, by the device
    they are charged to: that of the lane whose ``bench.step`` holds the
    event on its thread (``ctx.lane_device``), else ``None`` (every
    device)."""
    names, out = set(names), defaultdict(list)
    for t in ctx.trace.threads.values():
        steps = [e for e in t.events if e.name == trace_mod.STEP]
        starts = [e.start for e in steps]
        for e in t.events:
            if e.name not in names:
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            lane = str(steps[i].args.get("lane", "")) if i >= 0 and e.start < steps[i].end else None
            out[ctx.lane_device.get(lane)].append((e.start, e.end))
    return out


def overlap_ns(a: list, b: list) -> float:
    """Total overlap of two sorted, merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_under(ctx, names) -> Optional[float]:
    """Percent of the window in which a device was idle while a span named
    in ``names`` was open for it (see :func:`held_intervals`), averaged
    over the devices; None where the window holds no such span (a program
    without them).  With one device every span counts against it."""
    tr, held = ctx.trace, held_intervals(ctx, names)
    covers = {d.index: trace_mod.merge(held[None] + held[d.index], tr.window)
              for d in tr.devices}
    if not any(covers.values()):
        return None
    ns = sum(overlap_ns(trace_mod.gaps(d.busy, tr.window), covers[d.index])
             for d in tr.devices)
    return 100.0 * ns / len(tr.devices) / tr.window_ns
