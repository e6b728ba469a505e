"""The readers of the program's own spans: device-idle time under the
engine's ``engine.readback`` / ``engine.h2d`` spans (from the profiler's
host threads) and the longest ``host.stall`` (from the obs ring), on a
synthetic trace and ring where every value is known by construction."""

from __future__ import annotations

import pytest
from jax.profiler import ProfileData
from test_bench_trace import _xspace

from bench import run, spec, trace
from repro.obs.tracer import TraceEvent

# Window 0..1000 us.  TPU:0 is busy 100..300 and 500..700, so idle 0..100,
# 300..500 and 700..1000.  Under engine.h2d (50..120) lie 50 us of idle;
# under engine.readback (250..450 and 690..760 on one thread, 440..460 on
# another) lie 150 + 60 + 10 = 220 us.  TPU:1 is busy all through.
OPS = lambda spans: {"XLA Modules": [("jit_decode_body(1)", s, d, {}) for s, d in spans],
                     "XLA Ops": [("%fusion.1 = f32[2] fusion(%p)", s, d, {}) for s, d in spans]}
HOST = {
    "python": [("bench.window", 0, 1000, {})],
    "python ": [("step:replica0", 40, 760, {}),
                ("engine.h2d", 50, 70, {}),
                ("engine.readback", 250, 200, {}),
                ("engine.readback", 690, 70, {})],
    "python  ": [("engine.readback", 440, 20, {})],
}


def _trace(devices: dict, host: dict = HOST) -> trace.Trace:
    text = _xspace({**devices, "/host:CPU": host})
    return trace.reduce_profile(ProfileData.from_text_proto(
        text.replace('"python  "', '"python"').replace('"python "', '"python"')))


def _ctx(tr=None, spans=(), w0=10.0, w1=61.0):
    win = run.Window(w0, w1, 0, 0, [], 0)
    return run.Context(None, None, None, win, [], {}, {}, list(spans), tr)


def _read(name, ctx):
    return spec.reader(name)(ctx)


def test_idle_under_readback_and_h2d_on_one_device():
    ctx = _ctx(_trace({"/device:TPU:0": OPS([(100, 200), (500, 200)])}))
    assert _read("idle_readback_pct", ctx) == pytest.approx(22.0)
    assert _read("idle_h2d_pct", ctx) == pytest.approx(5.0)


def test_idle_shares_average_over_devices():
    ctx = _ctx(_trace({"/device:TPU:0": OPS([(100, 200), (500, 200)]),
                       "/device:TPU:1": OPS([(0, 1000)])}))
    assert _read("idle_readback_pct", ctx) == pytest.approx(11.0)
    assert _read("idle_h2d_pct", ctx) == pytest.approx(2.5)


def test_a_program_without_the_spans_reads_nothing():
    host = {"python": HOST["python"], "python ": [("step:replica0", 40, 760, {})]}
    ctx = _ctx(_trace({"/device:TPU:0": OPS([(100, 200)])}, host))
    assert _read("idle_readback_pct", ctx) is None
    assert _read("idle_h2d_pct", ctx) is None


def _ev(ts, ph, name, dur=0.0, args=None):
    return TraceEvent(ts, ph, "host", name, dur, None, "", args, 7, "repro-obs-stall-watch")


LAG = [_ev(11.0 + i, "C", "host.lag_ms", args={"ms": 1.0}) for i in range(50)]


def test_longest_stall_that_starts_in_the_window():
    spans = LAG + [_ev(5.0, "X", "host.stall", 2.5),          # before the window
                   _ev(20.0, "X", "host.stall", 0.060),
                   _ev(40.0, "X", "host.stall", 0.412),
                   _ev(60.9, "X", "host.stall", 0.300),       # starts inside
                   _ev(62.0, "X", "host.stall", 3.0)]         # after it
    assert _read("host_stall_max_ms", _ctx(spans=spans)) == pytest.approx(412.0)


def test_no_stall_reads_zero_and_no_watch_reads_nothing():
    assert _read("host_stall_max_ms", _ctx(spans=LAG)) == 0.0
    assert _read("host_stall_max_ms", _ctx(spans=[])) is None
    # a watch that ran only before the window says nothing of it
    assert _read("host_stall_max_ms", _ctx(spans=[_ev(1.0, "C", "host.lag_ms",
                                                      args={"ms": 0.0})])) is None
