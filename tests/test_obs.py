"""Observability plane tests (ISSUE 6): tracer, export, registry.

Covers the tentpole and satellite 3:

* disabled tracer records zero events and its hot-path guard is cheap
  (the steps/s delta itself is measured in ``dispatch_bench``'s
  ``tracer_overhead`` row, where a stable workload exists);
* pool-mode soak over real threads asserting per-request span-ordering
  invariants (queued ≤ grant ≤ step-start ≤ step-end ≤ complete) and that
  the exported JSON validates against the trace-event schema;
* ring-buffer bounds and honest ``dropped`` accounting, per thread;
* ``LatencySeries`` windowed ``dropped`` exposure (satellite 1);
* ticker-driven pool-occupancy sampling during idle (satellite 2);
* the metrics registry: typed instruments, one-snapshot collection of
  dispatcher + fairness + arbiter + cache groups, JSON and Prometheus
  text exposition.
"""

import json
import sys
import threading
import time

import pytest

from repro.dispatch import Dispatcher, ScheduleCache
from repro.dispatch.async_dispatcher import AsyncDispatcher, _QuantumArbiter
from repro.dispatch.metrics import DispatchMetrics, LatencySeries
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Sample,
    SpanTracer,
    StallWatch,
    register_cache,
    register_dispatch,
    register_tracer,
    to_chrome_trace,
    validate_trace,
    worker_overlap,
    write_chrome_trace,
)

from repro.obs import tracer as tracer_mod

from _fakes import SeqEngine


# -- tracer core ------------------------------------------------------------


class TestTracerCore:
    def test_disabled_records_nothing(self):
        tr = SpanTracer()
        tr.instant("a")
        tr.complete("b", 0.0, 1.0)
        tr.async_begin("r", 1)
        tr.async_end("r", 1)
        tr.counter("c", 2.0)
        assert tr.drain() == []
        st = tr.stats()
        assert st["emitted"] == 0 and st["dropped"] == 0
        assert not st["enabled"]

    def test_disabled_guard_is_cheap(self):
        # the real overhead bound (≤5% steps/s) is measured in
        # dispatch_bench's tracer_overhead row; here we only pin that the
        # disabled path is a branch, not work: 200k no-op emits must be
        # near-instant even on a loaded CI box
        tr = SpanTracer()
        t0 = time.perf_counter()
        for _ in range(200_000):
            tr.instant("x", args={"n": 1})
        assert time.perf_counter() - t0 < 2.0
        assert tr.stats()["emitted"] == 0

    def test_enable_disable_clear_roundtrip(self):
        tr = SpanTracer()
        assert tr.enable() is tr and tr.enabled
        tr.instant("a")
        assert tr.disable() is tr and not tr.enabled
        tr.instant("b")                       # ignored: disabled
        events = tr.drain()
        assert [e.name for e in events] == ["a"]
        assert events[0].ph == "i"
        tr.clear()
        assert tr.drain() == [] and tr.stats()["emitted"] == 0

    def test_ring_bounds_and_dropped(self):
        tr = SpanTracer(buffer_size=16).enable()
        for i in range(100):
            tr.instant(f"e{i}")
        assert len(tr.drain()) == 16
        st = tr.stats()
        assert st["emitted"] == 100 and st["dropped"] == 84
        # oldest dropped, newest retained
        assert [e.name for e in tr.drain()] == [f"e{i}" for i in range(84, 100)]

    @pytest.mark.timeout(30)
    def test_per_thread_rings(self):
        tr = SpanTracer().enable()
        tr.instant("main")

        def emitter():
            for i in range(5):
                tr.instant(f"worker-{i}")

        t = threading.Thread(target=emitter, name="obs-test-worker")
        t.start()
        t.join(timeout=10)
        st = tr.stats()
        assert st["threads"] == 2 and st["buffered"] == 6
        events = tr.drain()
        tids = {e.tid for e in events}
        assert len(tids) == 2
        by_thread = {e.thread for e in events if e.name.startswith("worker")}
        assert by_thread == {"obs-test-worker"}

    def test_complete_span_clamps_negative_dur(self):
        tr = SpanTracer().enable()
        tr.complete("s", 1.0, -0.5)
        (ev,) = tr.drain()
        assert ev.ph == "X" and ev.dur == 0.0

    def test_buffer_size_validation(self):
        with pytest.raises(ValueError):
            SpanTracer(buffer_size=0)


# -- scoped spans and the host-stall watch ----------------------------------


def _watch_threads() -> list:
    return [t for t in threading.enumerate() if t.name == "repro-obs-stall-watch"]


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts what is built."""

    built: list = []

    def __init__(self, name, **meta):
        _CountingAnnotation.built.append((name, meta))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class TestScopedSpans:
    def test_disabled_span_is_the_shared_noop(self, monkeypatch):
        monkeypatch.setattr(tracer_mod, "_ANNOTATION", _CountingAnnotation)
        _CountingAnnotation.built = []
        tr = SpanTracer()
        before = len(_watch_threads())
        sp = tr.span("decode", cat="engine", rid=3)
        assert sp is tracer_mod.NULL_SPAN
        assert tr.span("prefill", cat="engine") is sp
        with sp as inner:
            assert not inner                   # falsy: `if span:` guards args
        assert _CountingAnnotation.built == []
        assert tr.watch is None and len(_watch_threads()) == before
        assert tr.drain() == [] and tr.stats()["emitted"] == 0

    def test_enabled_span_annotates_and_records(self, monkeypatch):
        monkeypatch.setattr(tracer_mod, "_ANNOTATION", _CountingAnnotation)
        _CountingAnnotation.built = []
        tr = SpanTracer().enable()
        try:
            assert tr.watch is not None and tr.watch.running
            with tr.span("step:m0", cat="step", lane="m0",
                         args={"bucket": 64, "key": (1, 2)}) as sp:
                assert sp
                sp.args = {"tokens": 5}
        finally:
            tr.disable()
        assert tr.watch is None
        assert _CountingAnnotation.built == [("step:m0", {"lane": "m0", "bucket": 64})]
        (ev,) = [e for e in tr.drain() if e.ph == "X"]
        assert (ev.name, ev.cat, ev.lane, ev.args) == ("step:m0", "step", "m0", {"tokens": 5})
        assert ev.dur >= 0.0

    def test_disable_stops_and_joins_the_watch(self):
        tr = SpanTracer().enable()
        watch = tr.watch
        assert watch.running
        tr.enable()                            # idempotent: the same watch
        assert tr.watch is watch
        tr.disable()
        assert not watch.running
        assert all(t is not watch._thread for t in _watch_threads())

    @pytest.mark.timeout(120)
    def test_span_lands_in_a_cpu_profile(self, tmp_path):
        jax = pytest.importorskip("jax")
        from jax.profiler import ProfileData

        tr = SpanTracer().enable()
        try:
            jax.profiler.start_trace(str(tmp_path))
            try:
                with tr.span("obs.test.span", cat="test", rid=11):
                    time.sleep(0.02)
            finally:
                jax.profiler.stop_trace()
        finally:
            tr.disable()
        (ring,) = [e for e in tr.drain() if e.name == "obs.test.span"]
        (path,) = list(tmp_path.glob("**/*.xplane.pb"))
        profile = ProfileData.from_file(str(path))
        host = [ev for plane in profile.planes if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name == "obs.test.span"]
        assert len(host) == 1
        assert host[0].duration_ns / 1e9 == pytest.approx(ring.dur, abs=1e-3)


class _FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


class TestStallWatch:
    def _traced(self):
        tr = SpanTracer()
        tr.enabled = True                      # recording, without a live watch
        return tr

    def test_one_stall_per_late_wake_none_on_time(self):
        tr, clock = self._traced(), _FakeClock()
        watch = StallWatch(tr, period_s=0.01, threshold_s=0.05, clock=clock)
        for t in (0.010, 0.020, 0.030):        # on time
            clock.now = t
            assert watch.check() is None
        clock.now = 0.030 + 0.010 + 0.080      # 80 ms late
        assert watch.check() == pytest.approx(0.080)
        clock.now += 0.010 + 0.049             # 49 ms late: below the threshold
        assert watch.check() is None
        clock.now += 0.010 + 0.300             # 300 ms late
        assert watch.check() == pytest.approx(0.300)
        stalls = [e for e in tr.drain() if e.name == "host.stall"]
        assert [(e.ph, e.cat) for e in stalls] == [("X", "host")] * 2
        assert stalls[0].ts == pytest.approx(0.030)
        assert stalls[0].dur == pytest.approx(0.090)
        assert stalls[1].dur == pytest.approx(0.310)
        assert [e.args["late_ms"] for e in stalls] == [pytest.approx(80.0),
                                                      pytest.approx(300.0)]
        for key in ("cpu_ms", "minflt", "majflt", "nvcsw", "nivcsw", "top_threads"):
            assert key in stalls[0].args

    def test_lag_counter_once_a_second(self):
        tr, clock = self._traced(), _FakeClock()
        watch = StallWatch(tr, period_s=0.01, threshold_s=0.05, clock=clock)
        for i in range(100):                   # a second of wakes, on time
            clock.now += 0.010 + (0.020 if i == 40 else 0.0)   # but one
            assert watch.check() is None
        (lag,) = [e for e in tr.drain() if e.name == "host.lag_ms"]
        assert lag.ph == "C" and lag.args == {"ms": pytest.approx(20.0)}

    def test_bad_period_or_threshold(self):
        with pytest.raises(ValueError):
            StallWatch(SpanTracer(), period_s=0.0)
        with pytest.raises(ValueError):
            StallWatch(SpanTracer(), threshold_s=-1.0)

    @pytest.mark.timeout(60)
    def test_a_gil_hold_is_a_stall_charged_to_its_holder(self):
        tr = SpanTracer().enable()
        old = sys.getswitchinterval()
        try:
            time.sleep(0.15)                   # the watch is asleep, sampled
            sys.setswitchinterval(1.0)
            end = time.perf_counter() + 0.4
            while time.perf_counter() < end:   # holds the GIL throughout
                pass
        finally:
            sys.setswitchinterval(old)
        time.sleep(0.1)                        # the watch wakes and records
        tr.disable()
        stalls = [e for e in tr.drain() if e.name == "host.stall"]
        assert stalls
        worst = max(stalls, key=lambda e: e.dur)
        assert worst.dur >= 0.3 and worst.args["late_ms"] >= 250
        name, tid, cpu_ms = worst.args["top_threads"][0]
        me = threading.current_thread()
        assert (name, tid) == (me.name, me.native_id)
        # the holder ran through the stall (less on an oversubscribed host)
        assert cpu_ms >= 0.25 * worst.args["late_ms"]


# -- export -----------------------------------------------------------------


class TestExport:
    def _traced(self):
        tr = SpanTracer(clock=time.perf_counter).enable()
        t0 = tr.clock()
        tr.async_begin("request", 7, lane="m0")
        tr.instant("queued", cat="request", lane="m0", rid=7)
        tr.complete("step:m0", t0, 0.001, cat="step", lane="m0",
                    args={"tokens": 3})
        tr.counter("pool_busy", 2, cat="pool", series="busy")
        tr.async_end("request", 7, lane="m0")
        return tr

    def test_chrome_trace_schema(self):
        trace = to_chrome_trace(self._traced())
        assert validate_trace(trace) == []
        evs = trace["traceEvents"]
        # one thread_name metadata record for the recording thread
        metas = [e for e in evs if e["ph"] == "M"]
        assert len(metas) == 1 and metas[0]["name"] == "thread_name"
        xs = [e for e in evs if e["ph"] == "X"]
        assert xs and xs[0]["dur"] == pytest.approx(1000.0, rel=0.01)
        assert xs[0]["args"]["lane"] == "m0"
        bs = [e for e in evs if e["ph"] == "b"]
        es = [e for e in evs if e["ph"] == "e"]
        assert len(bs) == 1 and len(es) == 1 and bs[0]["id"] == es[0]["id"]
        # timestamps rebased to the earliest event
        assert min(e["ts"] for e in evs if "ts" in e) == pytest.approx(0.0)
        json.dumps(trace)                     # JSON-serializable end to end

    def test_write_chrome_trace(self, tmp_path):
        path = tmp_path / "trace.json"
        trace = write_chrome_trace(str(path), self._traced())
        assert json.loads(path.read_text()) == json.loads(json.dumps(trace))

    def test_validate_catches_structural_breakage(self):
        assert validate_trace([]) != []
        assert validate_trace({"traceEvents": 3}) != []
        bad = {"traceEvents": [
            {"ph": "Z", "name": "x", "pid": 1, "tid": 1, "ts": 0},
            {"ph": "X", "name": "y", "pid": 1, "tid": 1, "ts": 0, "dur": -1},
            {"ph": "b", "name": "r", "pid": 1, "tid": 1, "ts": 0, "id": "1",
             "cat": "request"},
        ]}
        errors = validate_trace(bad)
        assert any("unknown phase" in e for e in errors)
        assert any("bad dur" in e for e in errors)
        assert any("unbalanced" in e for e in errors)

    def test_worker_overlap_detection(self):
        def span(tid, ts, dur):
            return {"ph": "X", "cat": "step", "name": "s", "pid": 1,
                    "tid": tid, "ts": ts, "dur": dur}

        disjoint = {"traceEvents": [span(1, 0, 10), span(2, 20, 10)]}
        assert worker_overlap(disjoint) == (2, False)
        overlapping = {"traceEvents": [span(1, 0, 10), span(2, 5, 10)]}
        assert worker_overlap(overlapping) == (2, True)
        same_thread = {"traceEvents": [span(1, 0, 10), span(1, 10, 10)]}
        assert worker_overlap(same_thread) == (1, False)


# -- lifecycle spans under real threads (pool-mode soak) --------------------


N_TENANTS = 8
POOL = 4


class TestPoolSoakSpans:
    @pytest.mark.timeout(120)
    def test_span_ordering_invariants(self):
        tr = SpanTracer().enable()
        log: list = []
        disp = AsyncDispatcher(
            max_pending=10_000, stepping="pool", pool_size=POOL, tracer=tr
        )
        for i in range(N_TENANTS):
            disp.register_model(f"m{i}", SeqEngine(f"m{i}", log, slots=2))
        futures = []
        with disp:
            for i in range(48):
                futures.append(disp.submit(
                    f"m{i % N_TENANTS}", [1, 2, 3], max_new_tokens=6
                ))
            done = [f.result(timeout=60) for f in futures]
        tr.disable()
        assert len(done) == 48
        events = tr.drain()
        trace = to_chrome_trace(events)
        assert validate_trace(trace) == []

        # per-request lifecycle: queued(b) ≤ ... ≤ complete(e), matched ids
        begins = {e.rid: e.ts for e in events if e.ph == "b"}
        ends = {e.rid: e.ts for e in events if e.ph == "e"}
        completes = {
            e.rid: e.ts for e in events
            if e.ph == "i" and e.name == "complete"
        }
        assert set(begins) == set(ends) == set(completes)
        assert len(begins) == 48
        for rid, t_begin in begins.items():
            assert t_begin <= completes[rid] <= ends[rid]

        # per-lane quantum ordering: a lane is never granted to two
        # workers at once, so its k-th grant precedes (or starts) its
        # k-th step span, and step spans never overlap within a lane
        grants: dict = {}
        for e in events:
            if e.ph == "i" and e.name == "grant":
                grants.setdefault(e.lane, []).append(e.ts)
        steps: dict = {}
        for e in events:
            if e.ph == "X" and e.cat == "step":
                assert e.dur >= 0.0
                steps.setdefault(e.lane, []).append((e.ts, e.ts + e.dur))
        assert set(steps) <= set(grants)
        for lane, spans in steps.items():
            spans.sort()
            g = sorted(grants[lane])
            assert len(g) >= len(spans)
            for k, (start, end) in enumerate(spans):
                assert g[k] <= start + 1e-9
                assert start <= end
                if k:
                    prev_end = spans[k - 1][1]
                    assert prev_end <= start + 1e-9

        # every request's complete instant sits inside SOME step span
        # ordering-wise: completes happen on the stepping thread after the
        # step span is recorded, so complete_ts >= that span's start
        first_step = {
            lane: min(s[0] for s in spans) for lane, spans in steps.items()
        }
        for e in events:
            if e.ph == "i" and e.name == "complete":
                assert e.ts >= first_step[e.lane]

    @pytest.mark.timeout(120)
    def test_disabled_tracer_zero_events_under_load(self):
        tr = SpanTracer()                     # never enabled
        log: list = []
        disp = AsyncDispatcher(
            max_pending=10_000, stepping="pool", pool_size=2, tracer=tr
        )
        for i in range(3):
            disp.register_model(f"m{i}", SeqEngine(f"m{i}", log, slots=2))
        with disp:
            futs = [
                disp.submit(f"m{i % 3}", [1, 2], max_new_tokens=4)
                for i in range(12)
            ]
            for f in futs:
                f.result(timeout=60)
        assert tr.drain() == []
        assert tr.stats()["emitted"] == 0


# -- satellite 1: windowed-series dropped accounting ------------------------


class TestSeriesDropped:
    def test_latency_series_dropped(self):
        s = LatencySeries("t", window=4)
        for i in range(10):
            s.record(i * 0.001)
        assert s.count == 4 and s.dropped == 6
        summary = s.summary_ms()
        assert summary["count"] == 4 and summary["dropped"] == 6

    def test_empty_series_reports_dropped(self):
        assert LatencySeries("t").summary_ms()["dropped"] == 0

    def test_metrics_snapshot_exposes_dropped(self):
        m = DispatchMetrics()
        for i in range(3):
            m.on_ready_size(i)
            m.on_pool_occupancy(i, 4)
        snap = m.snapshot()
        assert snap["ready_size"]["dropped"] == 0
        assert snap["pool"]["dropped"] == 0
        assert snap["grant_ms"]["dropped"] == 0
        # overflow the bounded rings and the count must be honest
        m._ready_sizes = type(m._ready_sizes)(maxlen=2)
        m._pool_busy = type(m._pool_busy)(maxlen=2)
        for i in range(5):
            m.on_ready_size(i)
            m.on_pool_occupancy(i, 4)
        snap = m.snapshot()
        assert snap["ready_size"]["dropped"] == 3
        assert snap["pool"]["dropped"] == 3


# -- satellite 2: ticker-driven occupancy sampling --------------------------


class TestTickerOccupancy:
    @pytest.mark.timeout(60)
    def test_idle_pool_occupancy_sampled_by_ticker(self):
        # a parked pool with zero grants must still accumulate occupancy
        # samples (zeros) from the designated ticker's fallback expiries
        disp = Dispatcher(max_pending=16)
        m = disp.metrics
        arb = _QuantumArbiter(
            disp, None, metrics=m, pool_size=2, tick=0.002
        )
        worker = threading.Thread(target=arb.acquire_any, daemon=True)
        worker.start()
        time.sleep(0.1)
        arb.close()
        worker.join(timeout=10)
        snap = m.snapshot()
        assert arb.grants == 0
        assert snap["pool"]["samples"] >= 5        # ~50 ticks in 0.1s
        assert snap["pool"]["busy_peak"] == 0
        assert snap["pool"]["busy_mean"] == 0.0


# -- registry ---------------------------------------------------------------


class TestInstruments:
    def test_counter(self):
        c = Counter("reqs")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)
        (s,) = c.samples()
        assert s.kind == "counter" and s.value == 5

    def test_gauge_set_and_callback(self):
        g = Gauge("depth")
        g.set(3)
        assert g.samples()[0].value == 3.0
        backed = Gauge("live", fn=lambda: 7)
        assert backed.samples()[0].value == 7.0

    def test_histogram_buckets(self):
        h = Histogram("lat", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        (s,) = h.samples()
        assert s.kind == "histogram"
        assert s.value["count"] == 4
        assert s.value["sum"] == pytest.approx(5.555)
        assert s.value["buckets"] == {
            "0.01": 1, "0.1": 2, "1.0": 3, "+Inf": 4,
        }

    def test_sample_as_dict(self):
        s = Sample("x", "gauge", 1.0, (("lane", "m0"),))
        assert s.as_dict() == {
            "name": "x", "kind": "gauge", "value": 1.0,
            "labels": {"lane": "m0"},
        }


class TestRegistry:
    @pytest.mark.timeout(120)
    def test_collect_unifies_all_groups(self):
        tr = SpanTracer().enable()
        log: list = []
        cache = ScheduleCache(capacity=8)
        cache.get_or_build("k", lambda: object())
        cache.get("k")
        disp = AsyncDispatcher(
            max_pending=10_000, stepping="pool", pool_size=2, tracer=tr
        )
        for i in range(3):
            disp.register_model(f"m{i}", SeqEngine(f"m{i}", log, slots=2))
        registry = MetricsRegistry()
        register_dispatch(registry, disp)
        register_cache(registry, cache)
        register_tracer(registry, tr)
        with disp:
            futs = [
                disp.submit(f"m{i % 3}", [1, 2], max_new_tokens=4)
                for i in range(9)
            ]
            for f in futs:
                f.result(timeout=60)
            # collect while live: the arbiter series exists only while
            # steppers run
            snap = registry.collect()
            prom = registry.to_prometheus()
            as_json = registry.to_json(indent=2)
        tr.disable()

        assert set(snap) == {
            "dispatcher", "fairness", "arbiter", "pool",
            "schedule_cache", "tracer",
        }
        names = {s["name"] for s in snap["dispatcher"]}
        assert {"requests_done", "tokens_out", "ttft_ms", "pending"} <= names
        done = next(
            s for s in snap["dispatcher"] if s["name"] == "requests_done"
        )
        assert done["kind"] == "counter" and done["value"] == 9
        lanes = {
            s["labels"]["lane"] for s in snap["dispatcher"]
            if s.get("labels", {}).get("lane")
        }
        assert lanes == {"m0", "m1", "m2"}
        arb_names = {s["name"] for s in snap["arbiter"]}
        assert {"grants", "timed_wakeups", "notify_wakeups"} <= arb_names
        cache_names = {s["name"] for s in snap["schedule_cache"]}
        assert {"hits", "misses", "arena_bytes_total"} <= cache_names
        tracer_names = {s["name"] for s in snap["tracer"]}
        assert {"emitted", "dropped", "buffered"} <= tracer_names

        # both expositions are well-formed
        assert json.loads(as_json).keys() == snap.keys()
        assert "# TYPE repro_dispatcher_requests_done counter" in prom
        assert "# TYPE repro_dispatcher_ttft_ms summary" in prom
        assert 'quantile="0.95"' in prom
        assert "repro_schedule_cache_hits" in prom
        assert prom.endswith("\n")

    def test_collector_error_isolated(self):
        registry = MetricsRegistry()

        def broken():
            raise RuntimeError("scrape me not")

        registry.register("bad", broken)
        registry.register("good", Counter("ok"))
        snap = registry.collect()
        assert snap["good"][0]["name"] == "ok"
        (up,) = snap["bad"]
        assert up["name"] == "up" and up["value"] == 0.0

    def test_register_unregister(self):
        registry = MetricsRegistry()
        registry.register("g", Counter("a"))
        registry.register("g", Counter("b"))
        assert [s["name"] for s in registry.collect()["g"]] == ["a", "b"]
        registry.unregister("g")
        assert registry.collect() == {}

    def test_prometheus_histogram_exposition(self):
        registry = MetricsRegistry()
        h = Histogram("step", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        registry.register("bench", h)
        prom = registry.to_prometheus()
        assert "# TYPE repro_bench_step histogram" in prom
        assert 'repro_bench_step_bucket{le="0.1"} 1' in prom
        assert 'repro_bench_step_bucket{le="+Inf"} 2' in prom
        assert "repro_bench_step_count 2" in prom
