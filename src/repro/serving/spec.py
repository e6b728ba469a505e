"""Engine pickling/rehydration contract for the multi-process plane.

A live :class:`~repro.serving.engine.ServingEngine` cannot cross a
process boundary — its parameters, KV arenas, and sealed executables are
device state.  What *can* cross is the recipe: an :class:`EngineSpec` is
the small picklable object the parent ships to a worker process, which
calls :meth:`EngineSpec.build` **in the worker** so the AoT seal, the
weights, and the schedule cache all live (and die) with that device's
process.  The parent keeps only the spec and the scheduling-relevant
scalar it needs for admission control: ``max_slots``.

Contract:

* the spec (and everything it holds) must pickle — ship configs, seeds,
  and sizes, never arrays or engines;
* ``build(device_index, schedule_cache=None)`` runs in the worker
  process exactly once per registration; ``schedule_cache`` is the
  worker's shared per-device cache (pass it through so co-located lanes
  coalesce builds), and specs that ignore it may drop the keyword;
* ``max_slots`` must equal the built engine's slot capacity — the
  parent's lane proxy uses it for ``free_slots`` bookkeeping.
"""

from __future__ import annotations

import dataclasses
import pickle
from typing import Any, Optional, Union


def pickle_spec(spec: "EngineSpec") -> bytes:
    """Serialize an engine recipe for a process boundary or the request
    journal, verifying the round trip.

    Returns the pickle bytes after confirming they load back — a spec
    that captures an unpicklable closure or a live engine must fail
    loudly HERE, on the registering thread, not later inside a worker
    spawn or a journal recovery where the stack no longer points at the
    culprit.  Raises :class:`TypeError` with the offending spec named."""
    try:
        blob = pickle.dumps(spec)
        pickle.loads(blob)
    except Exception as exc:
        raise TypeError(
            f"engine spec {spec!r} is not picklable (specs must hold "
            f"configs/seeds/sizes, never arrays, engines, or closures): {exc}"
        ) from exc
    return blob


class EngineSpec:
    """Base rehydration recipe: subclass and implement :meth:`build`.

    ``max_slots`` (class or instance attribute) is read by the parent
    for slot accounting; everything else is yours."""

    max_slots: int = 4

    def build(self, device_index: int, schedule_cache: Any = None) -> Any:
        """Construct the engine in the worker process on ``device_index``."""
        raise NotImplementedError


@dataclasses.dataclass
class ServingEngineSpec(EngineSpec):
    """The real-model recipe: architecture name + sizes + init seed.

    ``build`` resolves the config (``smoke=True`` keeps worker start-up
    CI-sized), initializes parameters from ``seed``, places the engine on
    the worker's device (a ``device_index`` the process cannot see
    raises ``ValueError``), and seals schedules through the worker's shared
    cache — so registration cost is paid in the worker, and parent
    steppers still never compile."""

    arch: str = "stablelm-1.6b"
    max_slots: int = 4
    max_len: int = 128
    bucketing: Union[str, tuple] = "pow2:8:32"
    seed: int = 0
    smoke: bool = True
    dtype: Optional[str] = "float32"

    def build(self, device_index: int, schedule_cache: Any = None) -> Any:
        """Rehydrate a :class:`~repro.serving.engine.ServingEngine`."""
        import jax

        import repro.configs as C
        from repro.models import init_model

        from .engine import ServingEngine

        devices = jax.devices()
        if not 0 <= device_index < len(devices):
            raise ValueError(
                f"device_index {device_index} is not one of the "
                f"{len(devices)} visible device(s)"
            )
        device = devices[device_index]
        cfg = C.get(self.arch, smoke=self.smoke)
        if self.dtype is not None:
            cfg = dataclasses.replace(cfg, dtype=self.dtype)
        params, _ = init_model(jax.random.key(self.seed), cfg)
        return ServingEngine(
            cfg, params, max_slots=self.max_slots, max_len=self.max_len,
            bucketing=self.bucketing, schedule_cache=schedule_cache,
            device=device,
        )
