"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

Nothing here knows a particular configuration, architecture, mix or
metric: a later change adds a file under ``configs/``, ``arch/``,
``traffic/`` or ``metrics/`` and an entry in ``BENCHMARK.json``, and this
code picks it up.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    """One workload entry with its configuration, mix and metrics."""

    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file
    arch: ModuleType      # the architecture module it names (see arch())
    traffic_name: str
    traffic: dict         # the traffic file
    end_to_end: list      # metric entries of BENCHMARK.json reported here
    per_layer: list

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def engine(self) -> dict:
        return self.config["engine"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``."""
    root = Path(root)
    bench = load_benchmark(root)
    try:
        wl = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}") from None
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{wl['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=wl["chips"], config_name=wl["config"], config=config,
        arch=arch(config, root),
        traffic_name=wl["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


_MODULES: dict = {}


def _load(path: Path, prefix: str) -> ModuleType:
    """The module in the file ``path``, loaded once per file under a name
    made from ``prefix`` and registered in ``sys.modules`` (dataclasses
    look their module up there)."""
    path = path.resolve()
    if path not in _MODULES:
        name = f"{prefix}{path.stem.replace('.', '_')}_{len(_MODULES)}"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reader(metric: str, root: Path = ROOT) -> Callable:
    """The ``read(ctx)`` function of per-layer metric ``metric``: from
    ``bench/metrics/<metric>.py``, or, for a suffixed name such as
    ``decode_roofline.batch``, from the file of its base name."""
    d = Path(root) / "bench" / "metrics"
    path = d / f"{metric}.py"
    if not path.exists():
        path = d / f"{metric.split('.')[0]}.py"
    return _load(path, "bench_metric_").read


def arch(cell_or_config, root: Path = ROOT) -> ModuleType:
    """The architecture module that a configuration (or a cell's) names by
    its top-level ``"arch"`` key: ``<root>/bench/arch/<arch>.py``.  It
    provides ``layout``, ``logits_at`` and ``Shapes`` (see
    ``bench/arch/dense.py``).  There is no default: a missing key or a
    name with no file raises ``KeyError`` naming the configuration."""
    config = getattr(cell_or_config, "config", cell_or_config)
    name = config.get("arch")
    path = Path(root) / "bench" / "arch" / f"{name}.py"
    if not (isinstance(name, str) and re.fullmatch(r"[A-Za-z0-9_]+", name)
            and path.is_file()):
        raise KeyError(f"configuration {config.get('name')!r} names no architecture "
                       f"module: its 'arch' is {name!r}, and {path} is not a file")
    return _load(path, "bench_arch_")
