"""``bench/trace.py`` and the device-trace readers on a small trace written
out as an XSpace text proto: what every number should be is known."""

from __future__ import annotations

import re
from types import SimpleNamespace

import _bench_smoke as S
import pytest
from jax.profiler import ProfileData

from bench import run, spec, trace, work
from bench.peaks import PEAKS

US = 1_000_000          # picoseconds per microsecond


def _xspace(planes: dict) -> str:
    """``{plane: {line: [(name, start_us, dur_us, {stat: value})]}}`` as an
    XSpace text proto (every line's timestamp at 0)."""
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names, stats, body = {}, {}, []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = []
            for name, start, dur, st in events:
                mid = names.setdefault(name, len(names) + 1)
                st_txt = ""
                for k, v in st.items():
                    sid = stats.setdefault(k, len(stats) + 1)
                    val = f'str_value: "{v}"' if isinstance(v, str) else f"int64_value: {v}"
                    st_txt += f" stats {{ metadata_id: {sid} {val} }}"
                evs.append(f"events {{ metadata_id: {mid} offset_ps: {int(start * US)} "
                           f"duration_ps: {int(dur * US)}{st_txt} }}")
            body.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 {" ".join(evs)} }}')
        body += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                 for n, i in names.items()]
        body += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                 for n, i in stats.items()]
        out.append(f'planes {{ id: {pid} name: "{plane}" {" ".join(body)} }}')
    return "\n".join(out)


# Window 0..1000 us.  Step 0 (0..420): one prefill 10..110, then a decode
# 200..400 and a tiny eager op 401..402.  Step 1 (500..900): decode 600..800.
# The sealed programs carry no name of their own (``jit__unknown(<id>)``):
# the step records tell them apart.  Each idle gap goes to what the host
# was doing at its middle: 0..10 and 402..600 to a bench.step, 110..200 to
# the "PjitFunction(decode_body)" inside step 0, 800..1000 to nothing
# annotated; 350..354 and 400..401 between operations are short gaps.  The host's
# threads share a line name, as the profiler's Python threads do.
DECODE, PREFILL = "jit__unknown(8072118143256181884)", "jit__unknown(5185379177254712685)"
DEVICE = {
    "XLA Modules": [(PREFILL, 10, 100, {}), (DECODE, 200, 200, {}),
                    ("jit_convert_element_type(153)", 401, 1, {}), (DECODE, 600, 200, {})],
    "XLA Ops": [("%fusion.1 = bf16[1,64,128]{2,1,0} fusion(%p)", 10, 100, {}),
                ("%fusion.7 = bf16[4,1,128]{2,1,0} fusion(%p)", 200, 150, {}),
                ("%copy.2 = bf16[2,4,128]{2,1,0} copy(%c)", 354, 46, {}),
                ("%convert.1 = s32[] convert(%x)", 401, 1, {}),
                ("%fusion.7 = bf16[4,1,128]{2,1,0} fusion(%p)", 600, 150, {}),
                ("%copy.2 = bf16[2,4,128]{2,1,0} copy(%c)", 750, 50, {})],
}
HOST = {
    "python": [("bench.window", 0, 1000, {})],
    "python ": [("bench.step", 0, 420, {"lane": "replica0", "step": 0}),
                ("PjitFunction(decode_body)", 120, 70, {}),
                ("bench.step", 500, 400, {"lane": "replica0", "step": 1})],
}


@pytest.fixture(scope="module")
def tr():
    host = {k.strip(): v for k, v in HOST.items()}      # one line name twice
    text = _xspace({"/device:TPU:0": DEVICE, "/host:CPU": HOST})
    assert set(host) == {"python"}
    return trace.reduce_profile(ProfileData.from_text_proto(text.replace('"python "', '"python"')))


def test_busy_window_and_ops(tr):
    assert tr.window == (0.0, 1_000_000.0)
    assert tr.busy_s() == pytest.approx(497e-6)        # 100 + 200 + 1 + 200 - 4
    assert len(tr.runs_in(0, 0, 420_000)) == 3
    assert tr.top_ops(2) == [["_unknown/fusion.7", pytest.approx(300e-6)],
                             ["_unknown/fusion.1", pytest.approx(100e-6)]]
    roles = {DECODE: "decode_body", PREFILL: "prefill_body"}
    assert tr.top_ops(3, roles)[2] == ["decode_body/copy.2", pytest.approx(96e-6)]


def test_idle_gaps_are_labelled_by_the_host(tr):
    gaps = dict(tr.idle_gaps())
    assert gaps["bench.step > PjitFunction(decode_body)"] == pytest.approx(90e-6)
    assert gaps["bench.step"] == pytest.approx(10e-6 + 198e-6)
    assert gaps["outside bench annotations"] == pytest.approx(200e-6)
    assert gaps[trace.SHORT_GAP] == pytest.approx(4e-6 + 1e-6)
    assert sum(gaps.values()) == pytest.approx(1e-3 - tr.busy_s())


def test_device_readers_on_the_trace(tr):
    cell = spec.load_cell("stablelm-chat")
    shapes = cell.arch.Shapes.from_model(cell.model)
    peaks = PEAKS["TPU v5 lite"]
    rec = lambda i, pre, pos: SimpleNamespace(index=i, prefills=pre, positions=pos)
    records = {"replica0": [rec(0, [60], [100, 59]), rec(1, [], [101, 60])]}
    ctx = run.Context(cell, shapes, peaks, None, [], records, {"replica0": 0}, [], tr)
    assert [(k, r.start, rc.index) for k, r, rc in ctx.sealed_runs()] == [
        ("prefill", 10_000.0, 0), ("decode", 200_000.0, 0), ("decode", 600_000.0, 1)]
    assert ctx.roles() == {DECODE: "decode_body", PREFILL: "prefill_body"}
    read = lambda name: spec.reader(name)(ctx)
    assert read("decode_step_ms") == pytest.approx(0.2)
    assert read("prefill_ms") == pytest.approx(0.1)
    assert read("device_idle_pct") == pytest.approx(50.3)
    need = sum(work.roofline_seconds(*shapes.decode(p), peaks) for p in ([100, 59], [101, 60]))
    assert read("decode_roofline") == pytest.approx(100 * need / 400e-6)


def test_a_run_ending_just_past_its_step_still_belongs_to_it():
    # step 1's annotation closes 2 us before its decode run ends on the
    # device clock, and a tiny eager run lies inside it: the decode run is
    # still the step's, and the tiny one is not taken for it
    device = {**DEVICE, "XLA Modules": DEVICE["XLA Modules"] + [("jit_scatter(9)", 560, 1, {})]}
    host = {**HOST, "python ": HOST["python "][:2] + [
        ("bench.step", 500, 298, {"lane": "replica0", "step": 1})]}
    text = _xspace({"/device:TPU:0": device, "/host:CPU": host})
    tr = trace.reduce_profile(ProfileData.from_text_proto(text.replace('"python "', '"python"')))
    rec = lambda i, pre, pos: SimpleNamespace(index=i, prefills=pre, positions=pos)
    records = {"replica0": [rec(0, [60], [100, 59]), rec(1, [], [101, 60])]}
    ctx = run.Context(None, None, None, None, [], records, {"replica0": 0}, [], tr)
    assert [(k, r.start) for k, r, _ in ctx.sealed_runs()] == [
        ("prefill", 10_000.0), ("decode", 200_000.0), ("decode", 600_000.0)]
    assert spec.reader("decode_step_ms")(ctx) == pytest.approx(0.2)


def test_four_replicas_read_per_lane_and_per_chip():
    # four lanes, each stepping on its own chip at the same times; chip d's
    # decode runs last 200 + 20 d us, so a lane read on another lane's
    # chip would move the mean.  Busy and idle are averaged over the chips.
    planes, host = {}, {"python": [("bench.window", 0, 1000, {})]}
    for d in range(4):
        dec = 200 + 20 * d
        runs = [(PREFILL, 10, 100), (DECODE, 200, dec), (DECODE, 600, dec)]
        planes[f"/device:TPU:{d}"] = {
            "XLA Modules": [(n, t, u, {}) for n, t, u in runs],
            "XLA Ops": [("%fusion.1 = bf16[4,1,128]{2,1,0} fusion(%p)", t, u, {})
                        for _, t, u in runs]}
        # lane d reads back over 110 + 20 d .. 130 + 20 d, idle on its own
        # chip (between its prefill and decode); lane 0 puts inputs over
        # 150..190, and a put outside any step (960..990) counts on every chip
        host["python" + " " * (d + 1)] = [
            ("bench.step", 0, 480, {"lane": f"replica{d}", "step": 0}),
            ("engine.readback", 110 + 20 * d, 20, {}),
            ("bench.step", 500, 450, {"lane": f"replica{d}", "step": 1})] + (
            [("engine.h2d", 150, 40, {})] if d == 0 else [])
    host["python"].append(("engine.h2d", 960, 30, {}))
    text = re.sub(r'name: "python +"', 'name: "python"', _xspace({**planes, "/host:CPU": host}))
    tr4 = trace.reduce_profile(ProfileData.from_text_proto(text))
    cell = spec.load_cell("stablelm-chat")
    shapes, peaks = cell.arch.Shapes.from_model(cell.model), PEAKS["TPU v5 lite"]
    rec = lambda i, pre, pos: SimpleNamespace(index=i, prefills=pre, positions=pos)
    lanes = {f"replica{d}": d for d in range(4)}
    records = {lane: [rec(0, [60], [100, 59]), rec(1, [], [101, 60])] for lane in lanes}
    ctx = run.Context(cell, shapes, peaks, None, [], records, lanes, [], tr4)
    assert len(ctx.runs("decode")) == 8 and len(ctx.runs("prefill")) == 4
    read = lambda name: spec.reader(name)(ctx)
    assert read("decode_step_ms") == pytest.approx(0.23)          # mean of 200..260 us
    assert read("prefill_ms") == pytest.approx(0.1)
    assert tr4.busy_s() == pytest.approx(560e-6)                  # 100 + 2 (200 + 20 d), mean
    assert read("device_idle_pct") == pytest.approx(44.0)
    need = 4 * sum(work.roofline_seconds(*shapes.decode(p), peaks) for p in ([100, 59], [101, 60]))
    assert read("decode_roofline") == pytest.approx(100 * need / 1840e-6)
    # each span counts against its own lane's chip: 20 us on each (2%), not
    # the 80 us that all four lanes' spans cover together
    assert read("idle_readback_pct") == pytest.approx(2.0)
    assert read("idle_h2d_pct") == pytest.approx((40 / 4 + 30) / 10)


def test_a_trace_without_the_window_or_a_device_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_profile(ProfileData.from_text_proto(_xspace({"/device:TPU:0": DEVICE})))
    with pytest.raises(ValueError):
        trace.reduce_profile(ProfileData.from_text_proto(_xspace({"/host:CPU": HOST})))
