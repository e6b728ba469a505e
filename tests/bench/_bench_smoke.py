"""A smoke-size cell laid out as the benchmark lays out its files, in a
directory of its own, so tests drive the harness as a run does."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# The widest gap of served tokens under the float32 reference at this size,
# on the CPU: sound runs read 0 to 0.024 over 12 seeds, the float8 control
# 0.24 to 0.57 on the same requests.  The limit lies between, about three
# times from each.
SMOKE_LIMIT = 0.08


def smoke_model(tie: bool = False) -> dict:
    model = json.loads((REPO / "bench/configs/stablelm-1.6b.json").read_text())["model"]
    model.update(name="smoke", n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                 d_ff=256, vocab=512, tie_embeddings=tie)
    return model


def open_mix(rate: float = 40.0) -> dict:
    return {"loop": "open", "rate_per_s": rate, "lead_s": 0.3, "block": 16,
            "prompt": {"median": 24, "sigma": 0.5, "min": 4, "max": 60},
            "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 40}}


def closed_mix() -> dict:
    return {**open_mix(), "loop": "closed", "backlog": 4}


def write_root(root: Path, mixes: dict, model: dict = None) -> Path:
    """BENCHMARK.json with one smoke configuration and one cell per mix
    (``{cell name: mix}``), plus the committed metric readers and
    architecture modules."""
    root = Path(root)
    (root / "bench/configs").mkdir(parents=True, exist_ok=True)
    (root / "bench/traffic").mkdir(parents=True, exist_ok=True)
    for d in ("bench/metrics", "bench/arch"):
        if not (root / d).exists():
            shutil.copytree(REPO / d, root / d, ignore=shutil.ignore_patterns("__pycache__"))
    config = {"name": "smoke", "arch": "dense", "model": model or smoke_model(),
              "engine": {"max_slots": 4, "max_len": 128, "buckets": [16, 32, 64]},
              "check": {"max_logit_gap": SMOKE_LIMIT, "sample_tokens": 96,
                        "sample_requests": 12}}
    (root / "bench/configs/smoke.json").write_text(json.dumps(config))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "smoke", "source": "test", "file": "bench/configs/smoke.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = []
    for name, mix in mixes.items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
        bench["workloads"].append({"name": name, "config": "smoke", "traffic": name,
                                   "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
