"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights made on the device from the seed, engines sealed for the
cell's buckets, one warm-up request per bucket, load started ``lead_s``
before the window) is timed from process start to the window's start as
``setup_s``.  The window then runs for ``--seconds``; with ``--trace 1``
the profiler records its last ``TRACE_S`` seconds and the per-layer
metrics are reported instead of the end-to-end ones.  After the window the engines are freed and the
served tokens of a sample of finished requests are compared with the
float32 reference (:mod:`bench.check`).

Needs a TPU with the cell's chips: on any other backend it exits non-zero
and prints no result.  The last line of standard output is the result
JSON; the last lines of standard error are the compared numbers and their
limits.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
OUT_DIR = ROOT / ".bench_out"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_S = 10.0           # the traced run's profiler covers at most this much


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``; 0 elsewhere)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.perf_counter() - process_age_s()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class GcPauses:
    """Records each garbage collection's generation, start and length
    (perf_counter seconds) while installed, to tell a collector pause from
    other host stalls in a run's standard error."""

    def __init__(self) -> None:
        self.pauses: list = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"], self._t0, time.perf_counter() - self._t0))

    def summary(self, w0: float, w1: float) -> str:
        inside = [p for p in self.pauses if w0 <= p[1] <= w1]
        if not inside:
            return "gc: no collections in the window"
        gen, t, secs = max(inside, key=lambda p: p[2])
        by_gen = [sum(1 for p in inside if p[0] == g) for g in (0, 1, 2)]
        return (f"gc: {len(inside)} collections in the window (by generation {by_gen}), "
                f"{sum(p[2] for p in inside) * 1e3:.1f} ms in all; longest "
                f"{secs * 1e3:.1f} ms, generation {gen}, at {t - w0:.2f} s")


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


@dataclasses.dataclass
class Window:
    """The measured window in perf_counter seconds, and what it saw."""

    w0: float
    w1: float
    tokens0: int
    tokens1: int
    grants: list
    compiles: int


def ttft_ms(sent: list, win: Window, late_deadline: float) -> list:
    """First-token latency in ms, from when it was due, of every request
    due in the window and neither refused nor failed; one still without a
    first token counts to ``late_deadline``."""
    return [((s.t_first or late_deadline) - s.due) * 1e3 for s in sent
            if win.w0 <= s.due < win.w1 and s.refused is None and s.req.error is None]


def end_to_end(cell, sent: list, win: Window, setup_s: float, late_deadline: float) -> dict:
    """The cell's end-to-end metrics from the request records."""
    done = [s for s in sent if s.req.done and s.req.error is None and s.refused is None
            and win.w0 <= s.req.t_done <= win.w1 and len(s.req.generated) >= 2]
    values = {"setup_s": setup_s}
    ttft = ttft_ms(sent, win, late_deadline)
    if ttft:
        values["ttft_p95_ms"] = percentile(ttft, 95)
    if done:
        span = sum(s.req.t_done - s.t_first for s in done)
        steps = sum(len(s.req.generated) - 1 for s in done)
        values["tpot_ms"] = span / steps * 1e3
        values["tpot_p95_ms"] = percentile(
            [(s.req.t_done - s.t_first) / (len(s.req.generated) - 1) * 1e3
             for s in done], 95)
    values["tokens_per_s"] = (win.tokens1 - win.tokens0) / (win.w1 - win.w0)
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


@dataclasses.dataclass
class Context:
    """What a per-layer metric reads (see ``bench/metrics/``)."""

    cell: object
    shapes: object           # the architecture module's Shapes
    peaks: object            # bench.peaks.Peaks
    window: Window
    sent: list               # [bench.serve.Sent]
    records: dict            # lane -> [bench.serve.StepRecord]
    lane_device: dict        # lane -> device id
    spans: list              # repro.obs TraceEvents from the window's start on
    trace: object            # bench.trace.Trace of the traced window

    def sealed_runs(self) -> list:
        """``(kind, device run, StepRecord)``, kind ``"prefill"`` or
        ``"decode"``, for every run of the engine's sealed programs inside a
        ``bench.step`` annotation that lies in the traced window.  An engine
        step runs one sealed prefill per admitted request, then one decode
        when a slot is live (its record says how many of each); these are
        the step's longest runs, the rest being small eager operations."""
        if getattr(self, "_sealed", None) is None:
            w0, w1 = self.trace.window
            self._sealed = []
            for ann in self.trace.host:
                lane = str(ann.args.get("lane", ""))
                if ann.name != "bench.step" or lane not in self.lane_device:
                    continue
                if ann.start < w0 or ann.end > w1:
                    continue
                rec = self.records[lane][int(ann.args["step"])]
                k = len(rec.prefills) + (1 if rec.positions else 0)
                runs = self.trace.runs_in(self.lane_device[lane], ann.start, ann.end)
                if k == 0 or len(runs) < k:
                    continue
                longest = sorted(sorted(runs, key=lambda r: r.dur)[-k:], key=lambda r: r.start)
                for i, run in enumerate(longest):
                    kind = "prefill" if i < len(rec.prefills) else "decode"
                    self._sealed.append((kind, run, rec))
        return self._sealed

    def runs(self, kind: str) -> list:
        """The device runs of the sealed ``kind`` program."""
        return [run for k, run, _ in self.sealed_runs() if k == kind]

    def roles(self) -> dict:
        """Module name -> ``decode_body`` / ``prefill_body``."""
        return {run.name: f"{k}_body" for k, run, _ in self.sealed_runs()}


def per_layer(ctx: Context, root: Path) -> dict:
    from bench import spec

    out = {}
    for m in ctx.cell.per_layer:
        value = spec.reader(m["name"], root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices: list,
             root: Path = ROOT, t_start: float = T_START, trace_dir: Path = None,
             peaks=None, control: bool = False) -> dict:
    """Set up ``cell`` on ``devices``, run its window, check its tokens;
    returns the result object (without ``device``).  ``peaks`` default to
    the table's entry for the devices' kind (needed only with ``trace``).
    With ``control`` the float8 control's tokens take the served tokens'
    place in the comparison (:mod:`bench.control`; a benchmark run never
    sets it), so ``correct`` should read false."""
    import jax

    from bench import check, serve
    from bench import trace as trace_mod
    from bench.peaks import peaks_for
    from repro.obs.tracer import get_tracer

    compile_times: list = []

    def on_event(name, secs, **_):
        if "backend_compile" in name:
            compile_times.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    if peaks is None and trace:
        peaks = peaks_for(devices[0].device_kind)
    tracer = get_tracer()
    if trace:
        tracer.enable()
    gc_pauses = GcPauses()
    gc.callbacks.append(gc_pauses)
    served = serve.build(cell, seed, devices, annotate=trace)
    log(f"set up in {time.perf_counter() - t_start:.1f}s since process start")
    load = serve.Load(served, cell.traffic, seed)
    metrics = served.dispatcher.metrics
    t_load = load.start()
    w0 = t_load + cell.traffic["lead_s"]
    time.sleep(max(0.0, w0 - time.perf_counter()))
    setup_s = time.perf_counter() - t_start
    tokens0, grants0 = served.tokens(), metrics.grant_latency.count
    if trace:
        # the profiler records the window's last TRACE_S seconds, so that
        # writing the trace out comes after the window
        tracer.clear()
        time.sleep(max(0.0, w0 + seconds - TRACE_S - time.perf_counter()))
        jax.profiler.start_trace(str(trace_dir), profiler_options=_profile_options())
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(max(0.0, w0 + seconds - time.perf_counter()))
    else:
        time.sleep(max(0.0, w0 + seconds - time.perf_counter()))
    w1 = time.perf_counter()
    tokens1 = served.tokens()
    grants = list(metrics.grant_latency.values)[grants0:metrics.grant_latency.count]
    if trace:
        jax.profiler.stop_trace()
    compiles = sum(w0 <= t <= w1 for t in compile_times)
    # every request due in the window gets its first token (open loop: the
    # load goes on meanwhile), up to LATE_S past the close
    late_deadline = w1 + serve.LATE_S
    if cell.traffic["loop"] == "open":
        while time.perf_counter() < late_deadline and load.error is None:
            if all(s.t_first or s.req.done or s.refused
                   for s in load.sent if w0 <= s.due < w1):
                break
            time.sleep(0.05)
    load.stop()
    gc.callbacks.remove(gc_pauses)
    if load.error is not None:
        raise load.error
    # after the late wait, so requests due late in the window have theirs
    spans = []
    if trace:
        spans = tracer.drain()
        tracer.disable()
    win = Window(w0, w1, tokens0, tokens1, grants, compiles)
    sent = list(load.sent)
    peaks_by_device = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    memory_peak = max(peaks_by_device)
    log(f"memory peak per device: {peaks_by_device} B")
    records = {r._lane: list(r.records) for r in served.recorders}
    lane_device = {lane: d.id for lane, d in zip(served.lanes, devices)}
    params = served.params[0]
    served.free()
    gc.collect()

    attempted = [s for s in sent if w0 <= s.due < w1]
    # refused, failed by the engine, or (open loop) no first token LATE_S
    # past the close; a closed loop's backlog still queued is not failed
    open_loop = cell.traffic["loop"] == "open"
    failed = sum(1 for s in attempted if s.refused or s.req.error or
                 (open_loop and not s.t_first and not s.req.done))
    lateness = [max(0.0, s.sent - s.due) * 1e3 for s in sent] or [0.0]
    late = {"p50_ms": percentile(lateness, 50), "p99_ms": percentile(lateness, 99),
            "max_ms": max(lateness)}
    log(f"generator lateness: p50 {late['p50_ms']:.3f} ms, p99 {late['p99_ms']:.3f} ms, "
        f"max {late['max_ms']:.3f} ms; {len(attempted)} attempted, {failed} failed, "
        f"{compiles} compiles in the window")

    _log_load(sent, w0, w1)
    log(gc_pauses.summary(w0, w1))
    shapes = cell.arch.Shapes.from_model(cell.model)
    if trace:
        tr = trace_mod.reduce(_xplane(trace_dir))
        ctx = Context(cell, shapes, peaks, win, sent, records, lane_device, spans, tr)
        metrics_out = per_layer(ctx, root)
    else:
        tr = None
        metrics_out = end_to_end(cell, sent, win, setup_s, late_deadline)

    t_check = time.perf_counter()
    done = [s for s in sent if s.req.done and s.req.error is None and s.refused is None
            and w0 <= s.req.t_done <= w1]
    chk = cell.config["check"]
    picks = check.sample(done, seed, chk["sample_tokens"], chk["sample_requests"])
    faults = check.complete(picks, cell.model["vocab"])
    gap = (check.widest_gap(cell.arch, params, cell.model, picks, cell.engine["max_len"],
                            control)
           if picks else {"gap_max": float("inf"), "tokens": 0, "requests": 0})
    correct = bool(picks) and not faults and gap["gap_max"] <= chk["max_logit_gap"]
    lanes = len({s.lane for s in picks})
    log(f"reference over {gap['requests']} requests of {lanes} lanes, "
        f"{gap['tokens']} served tokens, "
        f"in {time.perf_counter() - t_check:.1f}s")
    for f in faults[:10]:
        log(f"fault: {f}")
    compared = {
        "logit_gap_max": {"value": gap["gap_max"], "limit": chk["max_logit_gap"]},
        "incomplete_requests": {"value": len(faults), "limit": 0},
    }
    result = {
        "correct": correct,
        "attempted": len(attempted),
        "failed": failed,
        "metrics": metrics_out,
        "memory_peak_bytes": memory_peak,
        "lateness": late,
        "compiles_in_window": compiles,
        "checked": {"requests": gap["requests"], "tokens": gap["tokens"],
                    "lanes": lanes},
        "compared": compared,
    }
    if tr is not None:
        result["busy_s"] = tr.busy_s()
        result["window_s"] = tr.window_ns / 1e9
        result["breakdown"] = {"device_ops": tr.top_ops(10, ctx.roles()),
                               "idle_gaps": tr.idle_gaps(10)}
    return result


def _log_load(sent: list, w0: float, w1: float) -> None:
    """Whether the load was sustained: first-token latency in each half of
    the window (a growing queue shows as a later half far slower) and the
    rate of requests completed against the rate offered."""
    mid = (w0 + w1) / 2
    halves = []
    for lo, hi in ((w0, mid), (mid, w1)):
        ttft = [(s.t_first - s.due) * 1e3 for s in sent
                if lo <= s.due < hi and s.t_first]
        halves.append(percentile(ttft, 50) if ttft else float("nan"))
    offered = sum(w0 <= s.due < w1 for s in sent) / (w1 - w0)
    completed = sum(bool(s.req.done) and w0 <= s.req.t_done <= w1 for s in sent) / (w1 - w0)
    log(f"load: ttft p50 {halves[0]:.1f} ms in the first half, {halves[1]:.1f} ms in "
        f"the second; {offered:.2f} req/s offered, {completed:.2f} req/s completed")


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _xplane(trace_dir: Path) -> str:
    paths = sorted(Path(trace_dir).glob("**/*.xplane.pb"), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return str(paths[-1])


def prepare(workload: str):
    """``(cell, devices)`` with the program importable and the compile
    cache in the checkout; ``(None, None)``, said on standard error, where
    the program is missing or JAX finds no TPU or too few chips."""
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench: the program (src/repro) is not in {ROOT}")
        return None, None
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from bench import spec

    cell = spec.load_cell(workload, ROOT)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
        return None, None
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found {len(devices)}")
        return None, None
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell, devices[: cell.chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the mix's arrival rate (for finding the knee "
                         "only; the cell's rate is the one in its traffic file)")
    args = ap.parse_args(argv)

    cell, devices = prepare(args.workload)
    if cell is None:
        return 3
    if args.rate is not None:
        cell.traffic["rate_per_s"] = args.rate
    OUT_DIR.mkdir(exist_ok=True)
    trace_dir = OUT_DIR / "trace" / args.workload
    if args.trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                      trace_dir=trace_dir)
    if args.trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    import jax

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": result.pop("memory_peak_bytes")}
    if args.trace:
        device["busy_s"] = result.pop("busy_s")
        device["window_s"] = result.pop("window_s")
    compared = result.pop("compared")
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": result["metrics"], "device": device}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["compared"] = compared
    run_file = OUT_DIR / f"{args.workload}.{args.seed}.{args.trace}.json"
    with open(run_file, "w") as f:
        json.dump({**line, "lateness": result["lateness"], "checked": result["checked"],
                   "compiles_in_window": result["compiles_in_window"]}, f, indent=1)
    print(json.dumps(line), flush=True)
    for name, c in compared.items():
        log(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
