"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The device planes (``/device:TPU:<n>``) carry one line of executable runs
(``XLA Modules``: one event per run of a compiled program, named after its
jitted function) and one of operations (``XLA Ops``).  Host planes carry
the benchmark's own ``jax.profiler.TraceAnnotation`` spans: ``bench.window``
bounds the traced window, ``bench.step`` wraps each engine step (with its
lane and step index as arguments) and ``bench.submit`` each submission.

From them: the device busy intervals (the union of operation intervals,
clipped to the window), device time per named executable, the operations
that took most time, and the idle time labelled by what the host was
doing.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import warnings
from collections import defaultdict
from typing import Optional

WINDOW = "bench.window"
STEP = "bench.step"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_NAME = re.compile(r"^(?:jit_)?([A-Za-z0-9_.]+?)(?:\(\d+\))?$")
SHORT_GAP_NS = 10_000    # idle gaps shorter than this lie between operations
SHORT_GAP = "between operations (gaps under 10 us)"


@dataclasses.dataclass(frozen=True)
class Event:
    """One trace event: name, start and end in ns, and its arguments."""

    name: str
    start: float
    end: float
    args: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    """What one device did: executable runs, operations (labelled by the
    run that holds them), and the merged busy intervals in the window."""

    index: int
    modules: list            # [Event] executable runs
    ops: list                # [(run Event or None, op name, start, end)]
    busy: list               # [(start, end)] merged, clipped to the window

    @property
    def busy_ns(self) -> float:
        return sum(e - s for s, e in self.busy)


class _Thread:
    """One host thread's events, sorted by start."""

    def __init__(self, events: list) -> None:
        self.events = sorted(events, key=lambda e: e.start)
        self.starts = [e.start for e in self.events]

    def innermost(self, lo: float, t: float) -> Optional[Event]:
        """The shortest event starting in ``[lo, t]`` that is open at ``t``."""
        i0, i1 = bisect.bisect_left(self.starts, lo), bisect.bisect_right(self.starts, t)
        open_ = [e for e in self.events[i0:i1] if e.end > t and not e.name.startswith("bench.")]
        return min(open_, key=lambda e: e.dur) if open_ else None


@dataclasses.dataclass
class Trace:
    """A reduced trace: the window, the devices, the host annotations."""

    window: tuple            # (start_ns, end_ns)
    devices: list            # [Device], by index
    host: list               # [Event] benchmark annotations, by start
    threads: dict            # thread name -> _Thread

    def __post_init__(self) -> None:
        self._ann = [(e, th) for th, t in self.threads.items() for e in t.events
                     if e.name.startswith("bench.") and e.name != WINDOW]
        self._ann.sort(key=lambda a: a[0].start)
        self._ann_starts = [a[0].start for a in self._ann]
        self._ann_max = max((a[0].dur for a in self._ann), default=0.0)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def top_ops(self, n: int = 10, roles: Optional[dict] = None) -> list:
        """The ``n`` operations with the most device time in the window,
        summed over devices: ``[[executable/op, seconds], ...]``.  An
        executable is named by ``roles`` (module name -> role, e.g. the
        sealed decode program) where given, else by its module name."""
        roles = roles or {}
        agg: dict = defaultdict(float)
        w0, w1 = self.window
        for d in self.devices:
            for run, op, s, e in d.ops:
                mod = "?" if run is None else roles.get(run.name, module_name(run.name))
                agg[f"{mod}/{op}"] += max(0.0, min(e, w1) - max(s, w0)) / 1e9
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def runs_in(self, device: int, start: float, end: float) -> list:
        """Executable runs on ``device`` whose middle lies in ``[start,
        end]``, by start.  The device's clock is aligned to the host's only
        so closely, so a run's end can lie just past the host span that
        waited for it."""
        d = next(d for d in self.devices if d.index == device)
        return [m for m in d.modules if start <= (m.start + m.end) / 2 <= end]

    def idle_gaps(self, n: int = 10) -> list:
        """Idle device time in the window summed by what the host was doing
        at each gap's middle, the ``n`` largest: ``[[label, seconds], ...]``."""
        agg: dict = defaultdict(float)
        for d in self.devices:
            for s, e in gaps(d.busy, self.window):
                label = SHORT_GAP if e - s < SHORT_GAP_NS else self.host_label((s + e) / 2)
                agg[label] += (e - s) / 1e9
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def host_label(self, t: float) -> str:
        """What the host was doing at ``t``: the benchmark annotation open
        at ``t`` (an engine step before a submission), then the innermost
        other host event on that thread inside it."""
        i = bisect.bisect_right(self._ann_starts, t)
        open_ = []
        while i > 0 and self._ann_starts[i - 1] >= t - self._ann_max:
            i -= 1
            ev, th = self._ann[i]
            if ev.end > t:
                open_.append((ev.name != STEP, -ev.start, ev, th))
        if not open_:
            return "outside bench annotations"
        _, _, ann, th = min(open_, key=lambda a: a[:2])
        inner = self.threads[th].innermost(ann.start, t)
        return ann.name if inner is None else f"{ann.name} > {inner.name}"


def module_name(name: str) -> str:
    """``jit_decode_body(12)`` -> ``decode_body``."""
    m = _MODULE_NAME.match(name)
    return m.group(1) if m else name


def merge(intervals, window: tuple) -> list:
    """The union of ``intervals`` ([(start, end)]) clipped to ``window``."""
    out: list = []
    for s, e in sorted(intervals):
        s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def gaps(busy: list, window: tuple) -> list:
    """The idle intervals of ``window`` between the merged ``busy`` ones."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def _events(line, with_args) -> list:
    out = []
    with warnings.catch_warnings():
        # the profiler's stats iterator type has no __module__ (Python 3.12)
        warnings.simplefilter("ignore", DeprecationWarning)
        for ev in line.events:
            name = ev.name
            start = float(ev.start_ns)
            args = dict(ev.stats) if with_args(name) else {}
            out.append(Event(name, start, start + float(ev.duration_ns), args))
    return out


def op_name(name: str) -> str:
    """``%copy.112 = bf16[...] copy(...)`` -> ``copy.112``: the XLA Ops line
    names an operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def _label_ops(mods: list, ops: list) -> list:
    """``(run, op, start, end)`` for each op, ``run`` the executable run
    holding it (None outside any)."""
    mods = sorted(mods, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        run = mods[i] if i >= 0 and op.end <= mods[i].end + 1 else None
        out.append((run, op_name(op.name), op.start, op.end))
    return out


def reduce(path: str) -> Trace:
    """Read ``path`` (an ``.xplane.pb``) and reduce it."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    devices, host = [], defaultdict(list)
    never = lambda name: False
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = _events(line, never)
                elif line.name == "XLA Ops":
                    ops = _events(line, never)
            devices.append(Device(int(m.group(1)), mods, _label_ops(mods, ops), []))
        elif plane.name.startswith("/host:") and plane.name != "/host:metadata":
            # several threads may share a line name ("python"): key by position
            for i, line in enumerate(plane.lines):
                host[f"{plane.name}/{i}/{line.name}"] = _events(
                    line, lambda name: name.startswith("bench."))
    windows = [ev for evs in host.values() for ev in evs if ev.name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    window = (windows[0].start, windows[0].end)
    for d in devices:
        d.busy = merge(((s, e) for _, _, s, e in d.ops), window)
    devices.sort(key=lambda d: d.index)
    threads = {th: _Thread([e for e in evs if e.end > window[0] and e.start < window[1]])
               for th, evs in host.items()}
    anns = sorted((e for t in threads.values() for e in t.events if e.name.startswith("bench.")),
                  key=lambda e: e.start)
    return Trace(window, devices, anns, threads)
