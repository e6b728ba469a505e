"""Device time, in each sealed decode run, of the operations that carry a
framework scope (the program's ``jax.named_scope``), read from the traced
run's profiler trace (:mod:`bench.xspace`), and the program's counters of
the engine step that holds each run."""

from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Optional

from bench import trace as trace_mod
from bench import xspace


def xplane_path(ctx) -> Optional[Path]:
    """The traced run's ``.xplane.pb``: ``ctx.xplane`` where set, else the
    newest under ``bench.run``'s trace directory for the cell."""
    path = getattr(ctx, "xplane", None)
    if path is None and ctx.cell is not None:
        from bench import run

        found = sorted((run.OUT_DIR / "trace" / ctx.cell.name).glob("**/*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        path = found[-1] if found else None
    return Path(path) if path is not None else None


def _ops(ctx) -> dict:
    """``{device: (starts, [(start, end, text)])}`` sorted by start, cached."""
    if getattr(ctx, "_scoped_ops", None) is None:
        path = xplane_path(ctx)
        ops = xspace.device_ops(path.read_bytes()) if path and path.is_file() else {}
        ctx._scoped_ops = {}
        for dev, evs in ops.items():
            evs.sort()
            ctx._scoped_ops[dev] = ([e[0] for e in evs], evs)
    return ctx._scoped_ops


def decode_time(ctx, scope: str) -> Optional[list]:
    """``[(StepRecord, ns)]``: for each sealed decode run in the traced
    window, the union of the device intervals of its operations under
    ``scope``; None where no operation carries the scope."""
    pattern = re.compile(rf"(^|/){re.escape(scope)}(/|$)", re.M)
    ops, out, seen = _ops(ctx), [], False
    for kind, run, rec in ctx.sealed_runs():
        if kind != "decode":
            continue
        starts, evs = ops.get(ctx.lane_device.get(getattr(rec, "lane", None)), ([], []))
        lo, hi = bisect.bisect_left(starts, run.start), bisect.bisect_right(starts, run.end)
        inside = [(s, e) for s, e, text in evs[lo:hi]
                  if e <= run.end + 1 and pattern.search(text)]
        seen = seen or bool(inside)
        ns = sum(e - s for s, e in trace_mod.merge(inside, (run.start, run.end + 1)))
        out.append((rec, ns))
    return out if seen else None


def step_counters(ctx, rec, names) -> Optional[tuple]:
    """The values of the program's counters ``names`` recorded during the
    engine step ``rec`` (its host interval), in that order; None if one is
    missing."""
    got = {}
    for e in ctx.spans:
        if getattr(e, "ph", None) == "C" and e.name in names and rec.t0 <= e.ts <= rec.t1:
            got[e.name] = (e.args or {}).get("value")
    if any(got.get(n) is None for n in names):
        return None
    return tuple(got[n] for n in names)
