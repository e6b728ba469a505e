"""Host-stall watch: a heartbeat thread that notices when the process stops.

A serving process can stand still for hundreds of milliseconds at a time:
one thread holding the GIL, the whole process descheduled or throttled by
its cgroup, or paging.  Nothing that runs *inside* the standstill can
record it, so :class:`StallWatch` sleeps in short periods and measures how
late each wake comes.  A wake at least ``threshold_s`` late records one
``host.stall`` complete span (cat ``host``) on the owning tracer, covering
the sleep the standstill fell in, with what the process did meanwhile,
from readings taken before and after it:

* ``late_ms`` — how much later than asked the wake came;
* ``cpu_ms`` — process CPU time (user + system, ``getrusage``);
* ``minflt`` / ``majflt`` — minor and major page faults;
* ``nvcsw`` / ``nivcsw`` — voluntary and involuntary context switches;
* ``top_threads`` — up to three ``[name, tid, cpu_ms]`` for the threads
  that used the most CPU (``/proc/self/task/<tid>/stat``, sampled at most
  every :attr:`StallWatch.SAMPLE_S` outside a stall), a Python thread
  named as ``threading`` names it;
* ``throttled_ms`` — time the cgroup's CPU quota held the process back
  (``cpu.stat``), where that file is readable.

The three causes read differently: a GIL holder shows as one thread with
about the stall's length of CPU; a process that did not run shows almost
no CPU, with throttling or involuntary switches; paging shows major
faults.  Once a second the watch also records a ``host.lag_ms`` counter
sample, the largest lateness of that second, so a reader can tell "the
watch ran and saw no stall" from "no watch".

The tracer owns its watch: :meth:`SpanTracer.enable` starts one and
:meth:`SpanTracer.disable` stops it, so nothing runs while tracing is
off.  The watch holds its tracer weakly, so a dropped enabled tracer does
not keep a thread alive.
"""

from __future__ import annotations

import os
import resource
import threading
import weakref
from typing import Any, Callable, Optional

_TASKS = "/proc/self/task"


def _cgroup_cpu_stat() -> Optional[str]:
    """Path of this process's cgroup ``cpu.stat`` (v2, else v1), or None."""
    try:
        with open("/proc/self/cgroup") as f:
            lines = f.read().splitlines()
    except OSError:
        return None
    for line in lines:
        parts = line.split(":", 2)
        if len(parts) != 3:
            continue
        hier, ctrls, path = parts
        if hier == "0" and not ctrls:
            cand = f"/sys/fs/cgroup{path.rstrip('/')}/cpu.stat"
        elif "cpu" in ctrls.split(","):
            cand = f"/sys/fs/cgroup/{ctrls}{path.rstrip('/')}/cpu.stat"
        else:
            continue
        if os.path.exists(cand):
            return cand
    return None


def _throttled_us(path: Optional[str]) -> Optional[float]:
    """Microseconds throttled so far (v2 ``throttled_usec``, v1
    ``throttled_time`` in ns), or None where unreadable."""
    if path is None:
        return None
    try:
        with open(path) as f:
            for line in f:
                key, _, val = line.partition(" ")
                if key == "throttled_usec":
                    return float(val)
                if key == "throttled_time":
                    return float(val) / 1e3
    except (OSError, ValueError):
        return None
    return None


def _thread_cpu() -> dict:
    """``{tid: (name, cpu seconds)}`` for every thread of this process."""
    tick = os.sysconf("SC_CLK_TCK")
    out = {}
    try:
        tids = os.listdir(_TASKS)
    except OSError:
        return out
    for tid in tids:
        try:
            with open(f"{_TASKS}/{tid}/stat", "rb") as f:
                raw = f.read().decode(errors="replace")
        except OSError:
            continue                     # the thread ended meanwhile
        lp, rp = raw.find("("), raw.rfind(")")
        fields = raw[rp + 2:].split()
        try:
            # utime, stime: fields 14 and 15 of stat, 12 and 13 after comm
            cpu = (int(fields[11]) + int(fields[12])) / tick
        except (IndexError, ValueError):
            continue
        out[int(tid)] = (raw[lp + 1:rp], cpu)
    return out


class _Reading:
    """One look at the process: rusage, per-thread CPU, cgroup throttling."""

    __slots__ = ("ru", "threads", "throttled_us")

    def __init__(self, ru, threads: dict, throttled_us: Optional[float]) -> None:
        self.ru = ru
        self.threads = threads
        self.throttled_us = throttled_us


class StallWatch:
    """Heartbeat thread that records a ``host.stall`` span on ``tracer``
    for every wake that comes ``threshold_s`` or more late.

    ``period_s`` is the sleep between wakes, ``clock`` the clock the
    lateness and the spans are read on (the tracer's own by default).
    :meth:`check` is one wake; :meth:`start` runs it every ``period_s`` on
    a daemon thread until :meth:`stop`.  Tests drive :meth:`check`
    directly with an injected clock."""

    # per-thread CPU is sampled at most this often: one sample reads a
    # /proc file per thread, each read a GIL hand-off, and a TPU process
    # has some 185 threads (a 36 ms sample on a v5e host)
    SAMPLE_S = 1.0
    LAG_EVERY_S = 1.0            # one host.lag_ms counter sample per second

    def __init__(
        self,
        tracer: Any,
        *,
        period_s: float = 0.010,
        threshold_s: float = 0.050,
        clock: Optional[Callable[[], float]] = None,
    ) -> None:
        if period_s <= 0 or threshold_s <= 0:
            raise ValueError("period_s and threshold_s must be > 0")
        self._tracer = weakref.ref(tracer)
        self.period_s = period_s
        self.threshold_s = threshold_s
        self.clock = clock if clock is not None else tracer.clock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._pid = 0
        self._cpu_stat = _cgroup_cpu_stat()
        self._slept_at = self.clock()
        self._lag_from = self._slept_at
        self._lag_max = 0.0
        self._sampled_at = self._slept_at
        self._before = self._read()

    def _read(self) -> _Reading:
        return _Reading(
            resource.getrusage(resource.RUSAGE_SELF),
            _thread_cpu(),
            _throttled_us(self._cpu_stat),
        )

    @property
    def running(self) -> bool:
        """True while this process's heartbeat thread is alive (a forked
        child sees its parent's watch as not running)."""
        t = self._thread
        return t is not None and self._pid == os.getpid() and t.is_alive()

    def start(self) -> "StallWatch":
        """Start the daemon heartbeat thread (idempotent)."""
        if not self.running:
            self._stop.clear()
            self._pid = os.getpid()
            self._slept_at = self._lag_from = self.clock()
            self._thread = threading.Thread(
                target=self._run, name="repro-obs-stall-watch", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        """Stop the heartbeat thread and wait for it (idempotent)."""
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread() and self.running:
            t.join(timeout=1.0)          # one wake, at most a /proc sample
        self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            if self._tracer() is None:
                return                   # the tracer was dropped
            self.check()

    def check(self) -> Optional[float]:
        """One wake: how late it came (seconds; None below the threshold),
        recording a ``host.stall`` span when at least ``threshold_s``."""
        now = self.clock()
        slept_at = self._slept_at
        late = now - slept_at - self.period_s
        tracer = self._tracer()
        if tracer is None:
            return None
        self._lag_max = max(self._lag_max, late)
        if now - self._lag_from >= self.LAG_EVERY_S:
            tracer.counter("host.lag_ms", max(0.0, self._lag_max) * 1e3,
                           cat="host", series="ms")
            self._lag_from, self._lag_max = now, 0.0
        stalled = late >= self.threshold_s
        if stalled:
            after = self._read()
            tracer.complete(
                "host.stall", slept_at, now - slept_at, cat="host",
                args=self._what_ran(self._before, after, late),
            )
            self._before, self._sampled_at = after, now
        elif now - self._sampled_at >= self.SAMPLE_S:
            self._before, self._sampled_at = self._read(), now
        else:
            self._before.ru = resource.getrusage(resource.RUSAGE_SELF)
        self._slept_at = self.clock()
        return late if stalled else None

    @staticmethod
    def _what_ran(before: _Reading, after: _Reading, late: float) -> dict:
        b, a = before.ru, after.ru
        args = {
            "late_ms": late * 1e3,
            "cpu_ms": (a.ru_utime + a.ru_stime - b.ru_utime - b.ru_stime) * 1e3,
            "minflt": a.ru_minflt - b.ru_minflt,
            "majflt": a.ru_majflt - b.ru_majflt,
            "nvcsw": a.ru_nvcsw - b.ru_nvcsw,
            "nivcsw": a.ru_nivcsw - b.ru_nivcsw,
        }
        # a Python thread by its threading name (the OS name is the
        # process's for all of them); a native thread by its OS name
        py_names = {t.native_id: t.name for t in threading.enumerate()}
        used = []
        for tid, (name, cpu) in after.threads.items():
            prev = before.threads.get(tid)
            delta = cpu - (prev[1] if prev is not None else 0.0)
            if delta > 0:
                used.append([py_names.get(tid, name), tid, delta * 1e3])
        used.sort(key=lambda u: -u[2])
        args["top_threads"] = used[:3]
        if before.throttled_us is not None and after.throttled_us is not None:
            args["throttled_ms"] = (after.throttled_us - before.throttled_us) / 1e3
        return args
