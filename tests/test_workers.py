"""Worker-plane failure matrix + lifecycle (ISSUE 9).

Every row of the matrix the workers module documents, asserted over BOTH
start methods (spawn re-imports, fork inherits — they fail differently,
so both must be covered):

* **setup failure** — the injected worker is condemned
  ``WorkerSetupError`` and never respawned; the rest of the fleet comes
  up and serves token-identically.
* **mid-step crash** — in-flight work fails ``WorkerCrashed`` (typed, on
  the victim's lanes only); queued work replays to completion on the
  respawned worker; bystander lanes never see an error.
* **heartbeat timeout** — a wedged (alive-but-silent) worker is
  condemned ``WorkerTimeout`` long before the step-RPC deadline; with
  respawn disabled its lanes fail typed while survivors keep serving.
* **parent-initiated shutdown** — final stats/trace collected over the
  ``bye`` handshake, shutdown idempotent, and **no orphaned processes**
  (asserted via ``multiprocessing.active_children()`` after every test —
  worker processes are children of this very process, so a leak is
  directly visible; ``make test-workers`` re-checks the same invariant
  after the whole suite).

Determinism: engines are ``WorkerTickEngine`` (request ``rid`` emits
``rid * 1000 + i``, the scenario-harness contract), and faults are
injected by request id, never by timer.  The end-to-end and trace-merge
tests drive the same plane through ``AsyncDispatcher(stepping="workers")``
— futures, typed failures, and the multi-process Perfetto merge.
"""

from __future__ import annotations

import multiprocessing as mp
import time

import numpy as np
import pytest

from _scenarios import SetupFailWorker, WorkerTickSpec
from repro import obs
from repro.dispatch import (
    AsyncDispatcher,
    ChipHeldError,
    WorkerCrashed,
    WorkerError,
    WorkerPlane,
    WorkerSetupError,
    WorkerTimeout,
)
from repro.serving import Request, ServingEngineSpec

START_METHODS = ("fork", "spawn")

# fast-failure constants: spawn children come up in ~1s, so timeouts are
# generous relative to startup but small relative to the test timeout
HB = dict(hb_interval=0.05, hb_timeout=1.0)


def _req(rid: int, max_new: int = 4) -> Request:
    return Request(
        rid=rid, prompt=np.array([1, 2, 3], np.int32),
        max_new_tokens=max_new,
    )


def _expected(rid: int, n: int) -> list:
    return [rid * 1000 + i for i in range(n)]


def _drive(proxy, deadline_s: float = 30.0) -> list:
    """Step a lane proxy until it drains; returns finished requests."""
    done: list = []
    deadline = time.monotonic() + deadline_s
    while not proxy.idle:
        done.extend(proxy.step())
        if time.monotonic() > deadline:
            raise AssertionError("lane did not drain in time")
    return done


def _assert_no_orphans() -> None:
    # worker processes are direct children of the test process; anything
    # still alive after shutdown is a leak (join reaps zombies first)
    deadline = time.monotonic() + 5.0
    while mp.active_children() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert mp.active_children() == []


@pytest.mark.timeout(120)
@pytest.mark.parametrize("start_method", START_METHODS)
def test_workers_end_to_end_token_identity(start_method):
    """4 lanes over 2 workers through the async front door: every future
    resolves with the deterministic tokens, the snapshot shows the fleet,
    and stop() leaks nothing."""
    plane = WorkerPlane(2, start_method=start_method, **HB)
    disp = AsyncDispatcher(
        max_pending=1000, stepping="workers", worker_plane=plane
    )
    names = [f"m{i}" for i in range(4)]
    for name in names:
        disp.register_model(name, WorkerTickSpec(slots=2))
    with disp:
        futures = {
            (name, rid): disp.submit_request(name, _req(rid))
            for i, name in enumerate(names)
            for rid in (2 * i, 2 * i + 1)
        }
        for (name, rid), fut in futures.items():
            r = fut.result(timeout=60)
            assert list(r.generated) == _expected(rid, 4), (name, rid)
        snap = disp.snapshot()["async"]["workers"]
        assert snap["n_workers"] == 2
        assert snap["serving"] == 2
        assert sorted(
            lane for w in snap["workers"] for lane in w["lanes"]
        ) == sorted(names)
    assert plane.leaked() == []
    _assert_no_orphans()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("start_method", START_METHODS)
def test_setup_failure_condemns_only_injected_worker(start_method):
    """Worker 0's setup raises: its lanes fail ``WorkerSetupError`` at
    assignment, it is never respawned, and worker 1 serves normally."""
    plane = WorkerPlane(
        2, start_method=start_method, worker_cls=SetupFailWorker,
        setup_kwargs={"fail_index": 0}, max_restarts=3, **HB,
    )
    try:
        plane.start()
        snap = plane.snapshot()
        assert snap["workers"][0]["status"] == "abandoned"
        assert snap["workers"][1]["status"] == "serving"
        # round-robin: first assignment lands on the condemned worker
        with pytest.raises(WorkerSetupError):
            plane.assign("doomed", WorkerTickSpec())
        survivor = plane.assign("ok", WorkerTickSpec())
        survivor.submit(_req(1))
        done = _drive(survivor)
        assert [list(r.generated) for r in done] == [_expected(1, 4)]
        # setup failures are deterministic: the monitor must never burn
        # restarts respawning it
        time.sleep(plane.hb_interval * 6)
        snap = plane.snapshot()
        assert snap["workers"][0]["status"] == "abandoned"
        assert snap["workers"][0]["restarts"] == 0
    finally:
        plane.shutdown()
    assert plane.leaked() == []
    _assert_no_orphans()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("start_method", START_METHODS)
def test_midstep_crash_fails_inflight_typed_and_replays_queued(start_method):
    """Poison rid 7 kills worker 0 mid-step: rid 7 fails ``WorkerCrashed``
    (typed, carrying the worker index), the lane's queued rid 8 replays
    to completion on the respawned worker, and worker 1's lane never sees
    any of it."""
    plane = WorkerPlane(2, start_method=start_method, max_restarts=3, **HB)
    try:
        plane.start()
        victim = plane.assign("victim", WorkerTickSpec(crash_rids=(7,)))
        bystander = plane.assign("bystander", WorkerTickSpec())
        assert victim.worker_index() != bystander.worker_index()

        victim.submit(_req(7))
        failed = victim.step()
        assert [r.rid for r in failed] == [7]
        exc = failed[0]._failure_exc
        assert isinstance(exc, WorkerCrashed)
        assert exc.worker == victim.worker_index()

        # queued work survives the crash: parked while dead, re-shipped
        # once the monitor respawns and re-registers the lane
        victim.submit(_req(8))
        done = _drive(victim, deadline_s=60.0)
        assert [list(r.generated) for r in done] == [_expected(8, 4)]
        assert all(
            getattr(r, "_failure_exc", None) is None for r in done
        )
        assert plane.snapshot()["workers"][victim.worker_index()]["restarts"] >= 1

        bystander.submit(_req(9))
        done = _drive(bystander)
        assert [list(r.generated) for r in done] == [_expected(9, 4)]
    finally:
        plane.shutdown()
    assert plane.leaked() == []
    _assert_no_orphans()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("start_method", START_METHODS)
def test_heartbeat_timeout_condemns_wedged_worker(start_method):
    """Poison rid 5 wedges worker 0 (alive but silent): the monitor's
    heartbeat sweep condemns it ``WorkerTimeout`` well before the 60s
    step-RPC deadline; with respawn disabled its lanes fail typed while
    worker 1 keeps serving."""
    plane = WorkerPlane(
        2, start_method=start_method, max_restarts=0, step_timeout=60.0,
        **HB,
    )
    try:
        plane.start()
        victim = plane.assign(
            "victim", WorkerTickSpec(hang_rids=(5,), hang_s=120.0)
        )
        survivor = plane.assign("survivor", WorkerTickSpec())

        victim.submit(_req(5))
        t0 = time.monotonic()
        failed = victim.step()
        elapsed = time.monotonic() - t0
        assert [r.rid for r in failed] == [5]
        assert isinstance(failed[0]._failure_exc, WorkerTimeout)
        # condemned by the heartbeat sweep (~hb_timeout), not the step
        # deadline — proves liveness detection works for silent wedges
        assert elapsed < 30.0

        # no respawn is coming: once the monitor marks the worker
        # abandoned (next sweep), queued work fails typed too
        victim.submit(_req(6))
        failed = []
        deadline = time.monotonic() + 10.0
        while not failed and time.monotonic() < deadline:
            failed = victim.step()
        assert [r.rid for r in failed] == [6]
        assert isinstance(failed[0]._failure_exc, WorkerError)

        survivor.submit(_req(9))
        done = _drive(survivor)
        assert [list(r.generated) for r in done] == [_expected(9, 4)]
    finally:
        plane.shutdown()
    assert plane.leaked() == []
    _assert_no_orphans()


@pytest.mark.timeout(120)
@pytest.mark.parametrize("start_method", START_METHODS)
def test_parent_shutdown_collects_and_leaves_no_orphans(start_method):
    """Clean shutdown: final worker stats collected over the ``bye``
    handshake, shutdown is idempotent, post-shutdown use raises, and no
    child outlives the plane."""
    plane = WorkerPlane(2, start_method=start_method, **HB)
    try:
        plane.start()
        lane = plane.assign("m", WorkerTickSpec())
        lane.submit(_req(3))
        _drive(lane)
    finally:
        plane.shutdown()
    snap = plane.snapshot()
    assert all(w["status"] != "serving" for w in snap["workers"])
    served = [w for w in snap["workers"] if w["stats"].get("steps")]
    assert served and served[0]["stats"]["steps"] >= 4
    plane.shutdown()                      # idempotent
    with pytest.raises(RuntimeError):
        plane.start()
    with pytest.raises(RuntimeError):
        plane.assign("late", WorkerTickSpec())
    assert plane.leaked() == []
    _assert_no_orphans()


@pytest.mark.timeout(120)
def test_async_worker_crash_fails_only_victim_lane_futures():
    """The async front door under a crash with respawn disabled: the
    victim lane's future carries the typed error, every other lane's
    future resolves token-identically — one device's death never poisons
    the fleet."""
    plane = WorkerPlane(2, start_method="fork", max_restarts=0, **HB)
    disp = AsyncDispatcher(
        max_pending=1000, stepping="workers", worker_plane=plane
    )
    # round-robin: lanes a, c on worker 0; b, d on worker 1
    disp.register_model("a", WorkerTickSpec(crash_rids=(7,)))
    disp.register_model("b", WorkerTickSpec())
    disp.register_model("c", WorkerTickSpec())
    disp.register_model("d", WorkerTickSpec())
    with disp:
        poison = disp.submit_request("a", _req(7))
        with pytest.raises(WorkerCrashed):
            poison.result(timeout=60)
        for name, rid in (("b", 1), ("d", 2)):
            r = disp.submit_request(name, _req(rid)).result(timeout=60)
            assert list(r.generated) == _expected(rid, 4)
        # worker 0 is gone for good (max_restarts=0): lane c fails typed
        with pytest.raises(WorkerError):
            disp.submit_request("c", _req(8)).result(timeout=60)
    assert plane.leaked() == []
    _assert_no_orphans()


@pytest.mark.timeout(120)
def test_trace_merge_has_per_process_tracks():
    """Workers record spans onto their own rings; after a traced run the
    merged Chrome trace validates and carries one process track per pid
    (parent + each worker)."""
    tracer = obs.get_tracer()
    tracer.clear()
    tracer.enable()
    plane = WorkerPlane(2, start_method="fork", trace=True, **HB)
    disp = AsyncDispatcher(
        max_pending=1000, stepping="workers", worker_plane=plane
    )
    disp.register_model("m0", WorkerTickSpec())
    disp.register_model("m1", WorkerTickSpec())
    try:
        with disp:
            for rid, name in ((0, "m0"), (1, "m1")):
                r = disp.submit_request(name, _req(rid)).result(timeout=60)
                assert list(r.generated) == _expected(rid, 4)
    finally:
        tracer.disable()
    worker_events = plane.trace_events()
    assert worker_events, "workers recorded no spans"
    worker_pids = {ev.pid for ev in worker_events}
    assert 1 not in worker_pids          # stamped with worker OS pids
    trace = obs.to_chrome_trace(tracer.drain(), extra_events=worker_events)
    tracer.clear()
    assert obs.validate_trace(trace) == []
    tracks = {
        ev["pid"]: ev["args"]["name"]
        for ev in trace["traceEvents"]
        if ev.get("ph") == "M" and ev.get("name") == "process_name"
    }
    assert tracks.get(1) == "dispatcher (parent)"
    assert len(tracks) >= 2
    for pid in worker_pids:
        assert tracks[pid] == f"worker pid={pid}"
    _assert_no_orphans()


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make JAX report a TPU backend (the process keeps its CPU devices)."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_worker_plane_refuses_tpu_before_spawning(tpu_backend):
    """On a TPU the parent holds the chip once it touches JAX: the plane,
    the workers front door, and the CPU parallel-stepping bench refuse
    typed instead of spawning children that would hang in setup."""
    with pytest.raises(ChipHeldError, match="holds the chip"):
        WorkerPlane(2, start_method="spawn")
    with pytest.raises(ChipHeldError):
        AsyncDispatcher(stepping="workers", devices=1)
    assert mp.active_children() == []

    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.dispatch_bench import parallel_stepping

    with pytest.raises(ChipHeldError):
        parallel_stepping()
    assert mp.active_children() == []


def test_engine_spec_refuses_an_invisible_device():
    """A device index beyond the visible devices raises; it never wraps
    onto device 0."""
    import jax

    n = len(jax.devices())
    for bad in (n, -1):
        with pytest.raises(ValueError, match="visible device"):
            ServingEngineSpec().build(bad)
