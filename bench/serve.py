"""Set up one cell on its devices and drive its traffic through the
program's normal serving path: ``AsyncDispatcher(stepping="pool")`` ->
``Dispatcher`` -> ``ServingEngine.step`` -> the sealed prefill and decode
programs from ``ScheduleCache``.

The benchmark's only code between the dispatcher and the engine is
:class:`StepRecorder`, a thin wrapper that records, for each engine step,
the prompt lengths it prefilled and the cached positions of the slots it
decoded (read from the requests, not from the program), and, in a traced
run, wraps the step in a ``jax.profiler.TraceAnnotation``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
import typing
from typing import Optional

import jax
import numpy as np

from bench import generator, weights
from repro.configs.base import ModelConfig
from repro.dispatch import AsyncDispatcher, ScheduleCache
from repro.dispatch.errors import QueueFullError
from repro.dispatch.slo import AdmissionRejected
from repro.serving import Request, ServingEngine

LATE_S = 60.0            # how long past the window a request may take


@dataclasses.dataclass
class StepRecord:
    """One engine step: lane, host start/end (perf_counter), the true
    lengths of the prompts it prefilled, and the positions each decoded
    slot held before the step."""

    lane: str
    index: int
    t0: float
    t1: float
    prefills: list
    positions: list


class StepRecorder:
    """Delegates to a ``ServingEngine``; records each ``step``."""

    def __init__(self, engine: ServingEngine, lane: str, annotate: bool) -> None:
        self._engine = engine
        self._lane = lane
        self._annotate = annotate
        self.records: list[StepRecord] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step(self):
        eng = self._engine
        before = {id(r): len(r.generated) for r in eng.slots if r is not None}
        index = len(self.records)
        ann = (jax.profiler.TraceAnnotation("bench.step", lane=self._lane, step=index)
               if self._annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            finished = eng.step()
        t1 = time.perf_counter()
        prefills, positions = [], []
        for req in [r for r in eng.slots if r is not None] + list(finished):
            n = len(req.generated)
            if id(req) in before:
                decoded = n > before[id(req)]
            else:
                if n == 0:
                    continue                      # failed at admission
                prefills.append(len(req.prompt))
                decoded = n >= 2
            if decoded:
                positions.append(len(req.prompt) + n - 2)
        self.records.append(StepRecord(self._lane, index, t0, t1, prefills, positions))
        return finished


class Tokens(list):
    """A request's token list that notes when its first token reached the
    host.  The engine stamps ``Request.t_first`` before it reads the
    prefill's token back from the device, so that stamp leaves out the
    prefill's device time; ``append`` runs after the read."""

    t_first = 0.0

    def append(self, token) -> None:
        if not self:
            self.t_first = time.perf_counter()
        super().append(token)


@dataclasses.dataclass
class Sent:
    """One request the load generator sent: when it was due and sent
    (perf_counter), the request, and why it was refused if it was."""

    item: generator.Item
    lane: str
    due: float
    sent: float
    req: Request
    refused: Optional[str] = None

    @property
    def t_first(self) -> float:
        """When the first token was on the host (0.0: not yet)."""
        return self.req.generated.t_first


@dataclasses.dataclass
class Served:
    """A cell set up on its devices: engines, recorders, dispatcher."""

    cfg: ModelConfig
    params: list
    engines: list
    recorders: list
    lanes: list
    dispatcher: AsyncDispatcher

    def tokens(self) -> int:
        """Tokens produced so far (prefill first tokens and decode tokens)."""
        return sum(e.stats.tokens_out + e.stats.prefill_tokens for e in self.engines)

    def free(self) -> None:
        """Stop the dispatcher and drop the engines and their KV caches;
        the weights stay for the reference."""
        self.dispatcher.stop(drain=False, timeout=30.0)
        for e in self.engines:
            # the engine and its dispatcher refer to each other: drop the
            # device buffers now rather than when a cycle collection runs
            e.kv_cache = None
            e.params = None
            e.set_submit_hook(None)
        self.engines.clear()
        self.recorders.clear()
        self.dispatcher = None


def used_buckets(cell) -> tuple:
    """The configuration's buckets that the mix's prompts land in."""
    buckets = sorted(cell.engine["buckets"])
    lo, hi = cell.traffic["prompt"]["min"], cell.traffic["prompt"]["max"]
    first = next(b for b in buckets if b >= lo)
    last = next(b for b in buckets if b >= hi)
    return tuple(b for b in buckets if first <= b <= last)


def _typed(hint, value):
    """``value`` from a JSON file as the type ``hint`` names: a dict as the
    dataclass it names (``Optional[...]`` unwrapped), a list as a tuple."""
    if isinstance(value, dict):
        for cls in (hint, *typing.get_args(hint)):
            if dataclasses.is_dataclass(cls):
                return _dataclass(cls, value)
    if isinstance(value, list) and typing.get_origin(hint) is tuple:
        return tuple(value)
    return value


def _dataclass(cls, fields: dict):
    hints = typing.get_type_hints(cls)
    return cls(**{k: _typed(hints[k], v) for k, v in fields.items()})


def model_config(model: dict) -> ModelConfig:
    """The program's configuration object, every field pinned by the file;
    a nested section (``moe``, ``mla``, ...) becomes the dataclass that
    ``ModelConfig``'s field names, ``None`` stays ``None``."""
    return _dataclass(ModelConfig, model)


def build(cell, seed: int, devices: list, annotate: bool) -> Served:
    """Weights per device from ``seed`` in the layout of the configuration's
    architecture module, one sealed engine per device (only the buckets
    this cell's prompts use), one pool dispatcher over them, and one
    warm-up request per bucket per engine."""
    eng_cfg = cell.engine
    buckets = used_buckets(cell)
    generator.check_fits(cell.traffic, eng_cfg["max_len"], buckets)
    cfg = model_config(cell.model)
    cache = ScheduleCache(capacity=64)
    params, engines, recorders, lanes = [], [], [], []
    for i, dev in enumerate(devices):
        p = weights.make(cell.arch.layout(cell.model), seed, dev)
        eng = ServingEngine(
            cfg, p, max_slots=eng_cfg["max_slots"], max_len=eng_cfg["max_len"],
            bucketing=buckets, schedule_cache=cache, device=dev,
        )
        params.append(p)
        engines.append(eng)
        lanes.append(f"replica{i}")
        recorders.append(StepRecorder(eng, lanes[-1], annotate))
    disp = AsyncDispatcher(stepping="pool", pool_size=len(engines), max_pending=1 << 16)
    for lane, rec in zip(lanes, recorders):
        disp.register_model(lane, rec)
    disp.start()
    served = Served(cfg, params, engines, recorders, lanes, disp)
    warm = np.random.default_rng(seed % 2**64)
    futs = [
        disp.submit_request(lane, Request(
            rid=-1 - k, prompt=warm.integers(0, cfg.vocab, b).astype(np.int32),
            max_new_tokens=3))
        for k, (lane, b) in enumerate((lane, b) for lane in lanes for b in buckets)
    ]
    for f in futs:
        req = f.result(timeout=600)
        if req.error is not None:
            raise RuntimeError(f"warm-up request failed: {req.error}")
    for rec in recorders:
        rec.records.clear()
    return served


def _submit(served: Served, item: generator.Item, lane: str, due: float) -> Sent:
    req = Request(rid=item.index, prompt=item.prompt, max_new_tokens=item.max_new,
                  generated=Tokens())
    ann = jax.profiler.TraceAnnotation("bench.submit", rid=item.index)
    refused = None
    with ann:
        try:
            fut = served.dispatcher.submit_request(lane, req)
        except QueueFullError as exc:
            refused = f"queue full: {exc}"
            fut = None
    if fut is not None and fut.done() and fut.exception() is not None:
        exc = fut.exception()
        refused = f"{type(exc).__name__}: {exc}"
    return Sent(item, lane, due, time.perf_counter(), req, refused)


class Load:
    """Drives a mix's requests into the dispatcher on a thread of its own:
    an open loop sends each request when it is due (round-robin over the
    lanes); a closed loop keeps ``slots + backlog`` requests outstanding."""

    def __init__(self, served: Served, traffic: dict, seed: int) -> None:
        self.served = served
        self.traffic = traffic
        self.items = generator.requests(traffic, seed, served.cfg.vocab)
        self.sent: list[Sent] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.start_t = 0.0
        self.error: Optional[BaseException] = None

    def start(self) -> float:
        self.start_t = time.perf_counter()
        self._thread = threading.Thread(target=self._run, name="bench-load", daemon=True)
        self._thread.start()
        return self.start_t

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)

    def _run(self) -> None:
        try:
            if self.traffic["loop"] == "open":
                self._open()
            else:
                self._closed()
        except BaseException as exc:  # noqa: BLE001 - reported by the run
            self.error = exc

    def _open(self) -> None:
        lanes = self.served.lanes
        for item in self.items:
            due = self.start_t + item.due_s
            while True:
                wait = due - time.perf_counter()
                if self._stop.is_set():
                    return
                if wait <= 0:
                    break
                self._stop.wait(min(wait, 0.05))
            self.sent.append(_submit(self.served, item, lanes[item.index % len(lanes)], due))

    def _closed(self) -> None:
        lanes = self.served.lanes
        slots = sum(e.max_slots for e in self.served.engines)
        floor = slots + self.traffic["backlog"]
        outstanding = []
        while not self._stop.is_set():
            outstanding = [s for s in outstanding if not s.req.done and s.refused is None]
            for _ in range(generator.closed_refill(len(outstanding), floor)):
                item = next(self.items)
                now = time.perf_counter()
                s = _submit(self.served, item, lanes[item.index % len(lanes)], now)
                self.sent.append(s)
                outstanding.append(s)
            self._stop.wait(0.002)
