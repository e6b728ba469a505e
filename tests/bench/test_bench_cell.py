"""A cell's inner pipeline end to end on the CPU at smoke size: set-up,
load through ``AsyncDispatcher``, window, metrics, the reference check;
and the harness finding a new cell's files by name alone."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import _bench_smoke as S
import jax
import pytest

from bench import run, serve, spec, work
from bench.peaks import PEAKS

pytestmark = pytest.mark.timeout(300)


def _run(root: Path, name: str, seconds: float = 1.5, seed: int = 2**33 + 5, **kw) -> dict:
    cell = spec.load_cell(name, root)
    return run.run_cell(cell, seed, seconds, False, jax.devices()[:1], root=root,
                        t_start=time.perf_counter(), **kw)


def test_open_loop_cell_runs_end_to_end(tmp_path):
    root = S.write_root(tmp_path, {"chat": S.open_mix()})
    res = _run(root, "chat")
    assert res["correct"], res["compared"]
    assert res["attempted"] > 20 and res["failed"] == 0
    m = res["metrics"]
    for name in ("setup_s", "ttft_p95_ms", "tpot_ms", "tpot_p95_ms", "tokens_per_s"):
        assert m[name]["value"] > 0, name
    assert res["compiles_in_window"] == 0
    assert res["compared"]["logit_gap_max"]["limit"] == S.SMOKE_LIMIT
    assert list(res)[-1] == "compared"


def test_closed_loop_cell_keeps_every_slot_busy(tmp_path):
    root = S.write_root(tmp_path, {"batch": S.closed_mix()})
    res = _run(root, "batch")
    assert res["correct"], res["compared"]
    assert res["failed"] == 0
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_new_config_mix_cell_and_metric_need_no_harness_edit(tmp_path):
    root = S.write_root(tmp_path, {"chat": S.open_mix()})
    # a second configuration, a second mix, a cell pairing them and a new
    # per-layer metric: files and entries only
    cfg = json.loads((root / "bench/configs/smoke.json").read_text())
    cfg["name"] = "smoke-gqa"
    cfg["model"].update(n_heads=8, n_kv_heads=2, norm="rmsnorm", tie_embeddings=True)
    (root / "bench/configs/smoke-gqa.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/slow.json").write_text(json.dumps(S.open_mix(rate=15.0)))
    (root / "bench/metrics/finished_share.py").write_text(
        "def read(ctx):\n"
        "    n = [s for s in ctx.sent if ctx.window.w0 <= s.due < ctx.window.w1]\n"
        "    return 100.0 * sum(bool(s.req.done) for s in n) / len(n) if n else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "smoke-gqa", "source": "test",
                             "file": "bench/configs/smoke-gqa.json", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "gqa-slow", "config": "smoke-gqa", "traffic": "slow",
                               "chips": 1, "why": "t"})
    bench["per_layer"] = [{"name": "finished_share", "unit": "%", "better": "higher",
                           "source": "host_clock", "layer": "test", "moves": "tpot_ms",
                           "workloads": ["gqa-slow"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("gqa-slow", root)
    assert cell.model["n_kv_heads"] == 2 and cell.traffic["rate_per_s"] == 15.0
    assert [m["name"] for m in cell.per_layer] == ["finished_share"]
    assert spec.load_cell("chat", root).per_layer == []
    res = _run(root, "gqa-slow")
    assert res["correct"], res["compared"]
    # the new reader is found by its name and reads the context a traced
    # run hands every per-layer reader; with nothing to read it is left out
    win = run.Window(0.0, 10.0, 0, 0, [], 0)
    sent = [SimpleNamespace(due=1.0, req=SimpleNamespace(done=d)) for d in (True, False)]
    ctx = run.Context(cell, None, PEAKS["TPU v5 lite"], win, sent, {}, {}, [], None)
    assert run.per_layer(ctx, root) == {"finished_share": {"value": 50.0, "unit": "%"}}
    ctx.sent = []
    assert run.per_layer(ctx, root) == {}


# An architecture module written beside a configuration, in the cell's
# root only: the dense one underneath, with every call recorded, the
# counts doubled (so readings show whose counts they are) and the
# reference's logits breakable.
RECORDED_ARCH = '''
from bench.arch import dense

CALLS = []
BREAK = False


def layout(model):
    CALLS.append("layout")
    return dense.layout(model)


def logits_at(params, model, tokens, rows, cols, mode="float32", block=4):
    CALLS.append("logits_at:" + mode)
    out = dense.logits_at(params, model, tokens, rows, cols, mode=mode, block=block)
    return out.at[:, 0].add(1e3) if BREAK else out


class Shapes(dense.Shapes):
    def decode(self, positions):
        CALLS.append("decode")
        return tuple(2 * x for x in super().decode(positions))

    def prefill(self, prompt_len):
        CALLS.append("prefill")
        return tuple(2 * x for x in super().prefill(prompt_len))
'''


def _add_config(root: Path, name: str, cell: str, **changes) -> None:
    cfg = json.loads((root / "bench/configs/smoke.json").read_text())
    cfg.update(name=name, **changes)
    (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": cell, "config": name, "traffic": "chat",
                               "chips": 1, "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_architecture_joins_as_a_file_with_no_harness_edit(tmp_path):
    from test_bench_trace import DEVICE, HOST, _xspace

    from bench import trace
    from bench.arch import dense
    from jax.profiler import ProfileData

    root = S.write_root(tmp_path, {"chat": S.open_mix()})
    (root / "bench/arch/recorded.py").write_text(RECORDED_ARCH)
    _add_config(root, "smoke-rec", "rec-chat", arch="recorded")
    cell = spec.load_cell("rec-chat", root)
    mod = spec.arch(cell, root)
    assert cell.arch is mod and mod.__file__ == str((root / "bench/arch/recorded.py").resolve())

    # its layout made the weights and its reference decided ``correct``
    res = _run(root, "rec-chat")
    assert res["correct"], res["compared"]
    assert mod.CALLS.count("layout") == 1 and "logits_at:float32" in mod.CALLS
    mod.BREAK = True
    try:
        broken = _run(root, "rec-chat")
    finally:
        mod.BREAK = False
    assert broken["correct"] is False
    assert broken["compared"]["logit_gap_max"]["value"] > 100

    # its Shapes feed the readers, as run_cell hands them over
    shapes = cell.arch.Shapes.from_model(cell.model)
    plain = dense.Shapes.from_model(cell.model)
    text = _xspace({"/device:TPU:0": DEVICE, "/host:CPU": HOST})
    tr = trace.reduce_profile(ProfileData.from_text_proto(text.replace('"python "', '"python"')))
    rec = lambda i, pre, pos, t0: SimpleNamespace(index=i, prefills=pre, positions=pos,
                                                  t0=t0, t1=t0 + 0.5)
    records = {"replica0": [rec(0, [60], [100, 59], 1.0), rec(1, [], [101, 60], 2.0)]}
    steps = [SimpleNamespace(ph="X", name="step:replica0", ts=t, dur=0.5) for t in (1.0, 2.0)]
    peaks = PEAKS["TPU v5 lite"]
    ctx = run.Context(cell, shapes, peaks, run.Window(0.0, 10.0, 0, 0, [], 0), [],
                      records, {"replica0": 0}, steps, tr)
    del mod.CALLS[:]
    got = run.per_layer(ctx, root)
    assert {"decode", "prefill"} <= set(mod.CALLS)
    need = sum(work.roofline_seconds(*(2 * x for x in plain.decode(p)), peaks)
               for p in ([100, 59], [101, 60]))
    assert got["decode_roofline"]["value"] == pytest.approx(100 * need / 400e-6)
    flops = 2 * (plain.decode([100, 59])[0] + plain.prefill(60)[0] + plain.decode([101, 60])[0])
    assert got["mfu_pct"]["value"] == pytest.approx(100 * flops / (peaks.bf16_flops * 1.0))


@pytest.mark.parametrize("arch", [None, "no_such_arch", "../configs/smoke"])
def test_a_configuration_without_its_architecture_module_fails_in_load_cell(tmp_path, arch):
    root = S.write_root(tmp_path, {"chat": S.open_mix()})
    changes = {"arch": arch} if arch else {}
    _add_config(root, "smoke-bad", "bad-chat", **changes)
    if arch is None:
        cfg = json.loads((root / "bench/configs/smoke-bad.json").read_text())
        del cfg["arch"]
        (root / "bench/configs/smoke-bad.json").write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match="'smoke-bad'"):
        spec.load_cell("bad-chat", root)
    assert spec.load_cell("chat", root).arch.Shapes is not None


def test_per_layer_ttft_reads_what_the_end_to_end_metric_reads():
    cell = spec.load_cell("stablelm-rag")
    win = run.Window(0.0, 10.0, 0, 0, [], 0)
    req = lambda err=None: SimpleNamespace(error=err, done=False)
    sent = [SimpleNamespace(due=1.0 + 0.1 * i, t_first=1.3 + 0.1 * i + 0.01 * i,
                            refused=None, req=req()) for i in range(40)]
    sent += [SimpleNamespace(due=5.0, t_first=None, refused=None, req=req()),
             SimpleNamespace(due=6.0, t_first=None, refused="full", req=req()),
             SimpleNamespace(due=7.0, t_first=None, refused=None, req=req("boom")),
             SimpleNamespace(due=10.5, t_first=10.6, refused=None, req=req())]
    e2e = run.end_to_end(dataclasses.replace(cell, end_to_end=[
        {"name": "ttft_p95_ms", "unit": "ms"}]), sent, win, 1.0, 10.0 + serve.LATE_S)
    ctx = run.Context(cell, None, None, win, sent, {}, {}, [], None)
    got = spec.reader("ttft_p95_ms.rag")(ctx)
    assert got == e2e["ttft_p95_ms"]["value"]
    # refused, failed and not-yet-due requests are left out; one with no
    # first token counts to the deadline
    ttft = run.ttft_ms(sent, win, 70.0)
    assert len(ttft) == 41 and max(ttft) == pytest.approx(65_000.0)
    ctx.sent = sent[-1:]
    assert spec.reader("ttft_p95_ms.rag")(ctx) is None


def _bench_cmd(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "stablelm-chat", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_off_the_chip_it_exits_nonzero_and_prints_no_result():
    out = _bench_cmd(S.REPO, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout and out.stdout.strip() == ""


def test_without_the_program_it_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(S.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in json.loads((S.REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(S.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench_cmd(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
