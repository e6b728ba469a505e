"""Device-idle time under the program's own spans.

The program's ``repro.obs`` scoped spans (``SpanTracer.span``) are also
profiler ``TraceMe`` events, so they lie on the host threads of the
device trace, on the same clock as the device's work.  From them: how much
of the device's idle time in the traced window fell while a span of a
given name was open on any host thread.
"""

from __future__ import annotations

from typing import Optional

from bench import trace as trace_mod


def open_intervals(tr, names) -> list:
    """The merged intervals (ns) in which a host event named in ``names``
    is open on any thread, clipped to the window."""
    names = set(names)
    return trace_mod.merge(((e.start, e.end) for t in tr.threads.values()
                            for e in t.events if e.name in names), tr.window)


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


def idle_share_under(tr, names) -> Optional[float]:
    """Percent of the window in which the device was idle while a span
    named in ``names`` was open, averaged over the devices; None where the
    trace holds no such span (a program without them)."""
    cover = open_intervals(tr, names)
    if not cover:
        return None
    ns = sum(overlap_ns(trace_mod.gaps(d.busy, tr.window), cover) for d in tr.devices)
    return 100.0 * ns / len(tr.devices) / tr.window_ns
