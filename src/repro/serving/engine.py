"""Serving engine: continuous batching over AoT-sealed prefill/decode steps.

The Nimble story applied to inference serving: both step functions are
scheduled **once** ahead of time (traced, compiled, memory reserved — the
task schedule), and the request loop only *submits* them.  Per-request state
lives in batch slots of a shared KV cache; each slot decodes at its own
offset (``kv_cache["pos"]`` is per-slot), so finished requests are replaced
without disturbing neighbours — iteration-level continuous batching.

Sealed executables are obtained through a ``repro.dispatch.ScheduleCache``
rather than compiled inline: prefill runs per request into its slot, padded
to a bucket length chosen by a ``repro.dispatch.bucketing`` policy, and each
(bucket, config) executable is built at most once — shared across engines
that use the same cache, and evicted LRU under shape churn.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aot import ScheduleKey
from repro.dispatch.bucketing import BucketingPolicy, make_policy
from repro.dispatch.cache import ScheduleCache
from repro.models import decode_step, forward, init_cache, init_model
from repro.models.transformer import encode_memory
from repro.obs.tracer import get_tracer


@dataclasses.dataclass
class Request:
    """One generation request: prompt in, tokens out, engine-stamped
    timestamps (``t_submit``/``t_first``/``t_done``) for latency metrics.
    The unit of traffic for both the engine and the dispatch layer.
    ``t_first`` is stamped once the first token has been read back from
    the device to the host, so it includes the request's prefill.

    ``truncated`` is set when the engine stopped the request early because
    its context window filled (``prompt + generated`` reached ``max_len``)
    — the caller got fewer than ``max_new_tokens`` tokens and this flag is
    the signal saying why.  ``error`` is set (with ``done``) when the
    request was failed rather than served — an unservable prompt reaching
    admission, or a retire racing a direct submit — so no request ever
    silently vanishes.  ``deadline`` (0.0 — none) is stamped by the
    dispatcher's SLO plane at admission when the lane carries a latency
    target: submit time plus target, on the SLO policy's clock — the
    value overload shedding compares against.  ``state`` is the explicit
    lifecycle state (:class:`repro.dispatch.lifecycle.RequestState`)
    stamped by the dispatcher's lifecycle tracker; requests submitted
    straight to an engine keep the empty string and are exempt from
    lifecycle enforcement (and from journaling)."""

    rid: int
    prompt: np.ndarray                 # (P,) int32
    max_new_tokens: int = 16
    tenant: str = ""                   # set by the dispatcher (multi-tenant)
    model: str = ""
    deadline: float = 0.0              # SLO deadline (0.0: best-effort)
    on_complete: Optional[Callable] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # filled by the engine:
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False            # finished early: context window full
    error: Optional[str] = None        # failed (not served): why
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    state: str = ""                    # dispatcher lifecycle state ("" = untracked)


@dataclasses.dataclass
class EngineStats:
    """Per-engine counters: compiles, steps, token and wall-time totals
    (prefill vs decode split)."""

    prefill_compiles: int = 0
    decode_compiles: int = 0
    steps: int = 0
    tokens_out: int = 0          # decode-produced tokens only
    prefill_tokens: int = 0      # first tokens, produced by prefill
    prefill_s: float = 0.0
    decode_s: float = 0.0

    @property
    def decode_tok_per_s(self) -> float:
        """Decode-only token throughput (tokens out / decode seconds)."""
        return self.tokens_out / self.decode_s if self.decode_s else 0.0


_PREFILL_READBACK = {"kind": "prefill"}
_DECODE_READBACK = {"kind": "decode"}


def _bind_cfg(body: Callable, cfg) -> Callable:
    """``body`` with ``cfg`` bound, keeping ``body``'s name: ``jax.jit``
    names the sealed program after it, so the device trace shows
    ``jit_decode_body`` rather than ``jit__unknown``."""
    bound = functools.partial(body, cfg=cfg)
    bound.__name__ = bound.__qualname__ = body.__name__
    return bound


def decode_body(params, cache, tokens, *, cfg):
    """Sealed decode step: one greedy token for every slot; a MoE model
    also returns the step's routing counts over the live slots, int32[2]:
    assignments to the experts held here and held experts hit, summed over
    its MoE layers.  A slot the engine freed sits at offset 0
    (``_finish``), and for a MoE model the step leaves it there, so the
    live slots are those at a non-zero offset.

    Module-level (``cfg`` bound with ``functools.partial``) so the body
    the engine seals can also be lowered from abstract shapes alone."""
    if cfg.moe is None:
        logits, cache = decode_step(params, cache, tokens, cfg)
        routing = ()
    else:
        live = cache["pos"] > 0
        logits, cache, counts = decode_step(params, cache, tokens, cfg, live=live)
        cache["pos"] = jnp.where(live, cache["pos"], 0)
        routing = (counts,)
    nxt = jnp.argmax(logits[:, :, : cfg.vocab], axis=-1).astype(jnp.int32)
    return (nxt, cache) + routing


def prefill_body(params, tokens, cache, slot, true_len, *, cfg):
    """Sealed prefill: one request (padded to a bucket) into cache slot
    ``slot``; returns its first token and the updated cache."""
    max_slots = cache["pos"].shape[0]
    # run the padded prompt through decode-style attention with cache,
    # writing K/V at offsets [0, P) of the slot.
    sub_cache = jax.tree_util.tree_map(
        lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1)
        if c.ndim >= 2 and c.shape[1] == max_slots
        else c,
        {k: v for k, v in cache.items() if k != "pos"},
    )
    sub_cache["pos"] = jnp.zeros((1,), jnp.int32)
    logits, sub_cache = decode_step(params, sub_cache, tokens, cfg)
    # next token from the true last prompt position (pre-pad)
    last = logits[0, true_len - 1, : cfg.vocab]
    nxt = jnp.argmax(last).astype(jnp.int32)
    # write slot state back
    new_cache = {}
    for k, v in cache.items():
        if k == "pos":
            new_cache[k] = v.at[slot].set(true_len)
        elif v.ndim >= 2 and v.shape[1] == max_slots:
            new_cache[k] = jax.lax.dynamic_update_slice_in_dim(
                v, sub_cache[k].astype(v.dtype), slot, axis=1
            )
        else:
            new_cache[k] = v
    return nxt, new_cache


# The step programs take the KV cache as a donated argument: each returns
# the updated cache in the buffers of the one it was given, so the engine
# must rebind ``kv_cache`` to every output and keep no other reference.
DECODE_DONATE = (1,)
PREFILL_DONATE = (2,)


def decode_program(cfg):
    """``decode_body`` for ``cfg``, jitted as the engine seals it."""
    return jax.jit(_bind_cfg(decode_body, cfg), donate_argnums=DECODE_DONATE)


def prefill_program(cfg):
    """``prefill_body`` for ``cfg``, jitted as the engine seals it."""
    return jax.jit(_bind_cfg(prefill_body, cfg), donate_argnums=PREFILL_DONATE)


class ServingEngine:
    """AoT-scheduled batched serving for any registered architecture."""

    def __init__(
        self,
        cfg,
        params,
        *,
        max_slots: int = 4,
        max_len: int = 256,
        prompt_buckets: tuple[int, ...] = (32, 128),
        bucketing: Any = None,
        schedule_cache: Optional[ScheduleCache] = None,
        warmup: bool = True,
        greedy: bool = True,
        device: Any = None,
        tracer: Any = None,
    ) -> None:
        if cfg.family in ("hybrid", "ssm"):
            raise NotImplementedError(
                "slot-replacement serving needs re-settable recurrent state; "
                "use batch decode directly for SSM/hybrid archs"
            )
        self.cfg = cfg
        # `device` pins this engine's weights, KV cache, and executables to
        # one device — the serving analogue of the paper's stream
        # assignment: per-engine steppers over engines on *different*
        # devices overlap decode with no shared execution queue.  On CPU,
        # expose extra host devices with
        # XLA_FLAGS=--xla_force_host_platform_device_count=N.
        self.device = device
        if device is not None:
            params = jax.device_put(params, device)
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        # `bucketing` (policy/spec) generalizes the old `prompt_buckets`
        # tuple, which remains as the explicit-buckets shorthand.
        self.bucketing: BucketingPolicy = make_policy(
            bucketing if bucketing is not None else prompt_buckets
        )
        # explicit None-check: an empty ScheduleCache is falsy (__len__ == 0)
        self.schedule_cache = (
            ScheduleCache(capacity=32) if schedule_cache is None else schedule_cache
        )
        self.greedy = greedy
        self.stats = EngineStats()
        self.tracer = tracer if tracer is not None else get_tracer()

        # sealed-executable identity beyond arg shapes: anything that changes
        # the traced computation without changing input shapes.  The device
        # is part of the identity: an executable compiled for device 0 must
        # not be replayed against arrays committed to device 1.
        self._key_options = (
            ("cfg", repr(cfg)),
            ("max_len", max_len),
            ("max_slots", max_slots),
            ("device", repr(device) if device is not None else ""),
        )

        # --- AoT scheduling: seal the step executables through the cache --
        self.kv_cache = init_cache(cfg, max_slots, max_len)
        if device is not None:
            self.kv_cache = jax.device_put(self.kv_cache, device)
        # per-engine memo of bucket -> ScheduleKey: key construction flattens
        # the whole params pytree, too costly per admitted request.  Only the
        # *key* is memoized — executables stay owned by the shared cache, so
        # its LRU eviction and invalidate()/clear() genuinely govern their
        # lifetime (an evicted bucket transparently rebuilds on next use).
        self._prefill_keys: "OrderedDict[int, ScheduleKey]" = OrderedDict()
        self._prefill_key_cap = 64
        self._decode = self._get_decode_exec()
        if warmup:
            for b in self._warm_buckets():
                self._get_prefill_exec(b)

        self.slots: list[Optional[Request]] = [None] * max_slots
        self.queue: list[Request] = []
        self._next_tok = np.zeros((max_slots, 1), np.int32)
        # thread-safety contract: the engine is single-stepper — exactly one
        # thread may drive step() at a time.  Under the dispatch layer that
        # thread is whoever holds this engine's lane step-lock (one
        # dedicated stepper per engine in AsyncDispatcher's per-engine
        # mode; the loop thread in single mode).  This guard turns an
        # accidental second stepper — e.g. an engine registered with two
        # dispatchers, or a caller stepping directly while dispatched —
        # into a loud error instead of corrupted KV state.
        self._step_mu = threading.Lock()
        self._retired = False
        # engine-side submit hook (installed by a dispatcher): called after
        # every direct submit() so directly-enqueued work reaches the
        # indexed ready set — without it, pool grants never see traffic
        # that bypassed the dispatcher's front door
        self._submit_hook: Optional[Callable[[], None]] = None

    def retire(self) -> None:
        """Lane-retire hook: release this engine's serving lifecycle.

        Called by ``Dispatcher.unregister_model`` after the lane drained.
        Refuses all further submissions (``validate_request`` raises) and
        drops the per-engine ``ScheduleKey`` memo so the shared schedule
        cache's LRU — not a dead tenant's memo — governs how long the
        sealed executables stay referenced.  Requests still queued (a
        direct ``submit`` racing the retire — there are none after a
        dispatcher drain) are FAILED loudly: each is completed with
        ``error`` set and its ``on_complete`` fired, never silently
        dropped.  Idempotent.
        """
        self._retired = True
        stranded, self.queue = list(self.queue), []
        self._prefill_keys.clear()
        for req in stranded:
            self._fail_request(req, "engine retired with request queued")

    def _fail_request(self, req: Request, why: str) -> None:
        """Complete ``req`` as failed: ``done`` + ``error`` set, terminal
        timestamp stamped, ``on_complete`` fired (no locks held)."""
        req.error = why
        req.done = True
        req.t_done = time.perf_counter()
        cb = req.on_complete
        if cb is not None:
            cb(req.model, req)

    def set_submit_hook(self, hook: Optional[Callable[[], None]]) -> None:
        """Install (or clear, with ``None``) the direct-submit hook.

        The hook fires after every :meth:`submit` appends to the engine
        queue.  ``Dispatcher.register_model`` points it at the lane's
        ready-index recompute, so work submitted to the engine directly —
        bypassing the dispatcher — still lands in the indexed ready set
        and pool grants (and the batch composer's refill path) can see
        it.  The hook must be fast and must not call back into the
        engine."""
        self._submit_hook = hook

    # -- sealed executables through the schedule cache ---------------------
    _EXEC_ARENA_FLOOR = 4096     # conservative floor: never report ~free

    def _exec_arena_bytes(self, *extra_shapes: tuple) -> int:
        """Reserved-memory estimate for one step executable, derived from
        its output buffer shapes: every step returns the full KV cache
        (the dominant term; the step is sealed with the cache donated, so
        that output aliases its input and this over-counts) plus the
        next-token array.  ``extra_shapes`` adds
        ``(shape, dtype)`` pairs for per-executable outputs/temps (e.g. a
        prefill's padded token buffer).  Byte-budget eviction needs a
        non-zero number here: raw executables carry no TaskSchedule stats,
        and reporting 0 would make them invisible to the budget.  The
        KV-cache term is memoized (shapes are fixed for the engine's
        lifetime): this runs on every request admission, and the estimate
        only matters on a cache miss."""
        kv = getattr(self, "_kv_arena_bytes", None)
        if kv is None:
            kv = self._kv_arena_bytes = sum(
                int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
                for leaf in jax.tree_util.tree_leaves(self.kv_cache)
            )
        total = kv
        for shape, dtype in extra_shapes:
            total += int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        return max(self._EXEC_ARENA_FLOOR, total)

    def _warm_buckets(self) -> tuple[int, ...]:
        static = self.bucketing.static_buckets()
        if static is None:
            return ()
        return tuple(b for b in static if b <= self.max_len)

    @property
    def prompt_buckets(self) -> tuple[int, ...]:
        """Bucket family currently pre-sealable (exact policies: empty)."""
        return self._warm_buckets()

    def _get_decode_exec(self):
        key = ScheduleKey.from_call(
            decode_step,
            (self.params, self.kv_cache,
             jax.ShapeDtypeStruct((self.max_slots, 1), jnp.int32)),
            self._key_options + (("donate_argnums", DECODE_DONATE),),
            fn_id=f"serving.decode/{self.cfg.name}",
        )

        def build():
            exe = decode_program(self.cfg).lower(
                self.params, self.kv_cache,
                jax.ShapeDtypeStruct((self.max_slots, 1), jnp.int32),
            ).compile()
            self.stats.decode_compiles += 1
            return exe

        # no pin: the key's fn_id is an explicit string (no id() component
        # to protect), and pinning params would keep a dropped engine's
        # whole weight pytree alive in a shared cache until eviction
        return self.schedule_cache.get_or_build(
            key, build,
            arena_bytes=self._exec_arena_bytes(
                ((self.max_slots, 1), jnp.int32)
            ),
        )

    def _prefill_key(self, bucket: int) -> ScheduleKey:
        key = self._prefill_keys.get(bucket)
        if key is not None:
            self._prefill_keys.move_to_end(bucket)
            return key
        key = ScheduleKey.from_call(
            decode_step,
            (self.params,
             jax.ShapeDtypeStruct((1, bucket), jnp.int32),
             self.kv_cache),
            self._key_options + (("donate_argnums", PREFILL_DONATE),),
            fn_id=f"serving.prefill/{self.cfg.name}",
        )
        self._prefill_keys[bucket] = key
        while len(self._prefill_keys) > self._prefill_key_cap:
            self._prefill_keys.popitem(last=False)
        return key

    def _get_prefill_exec(self, bucket: int):
        key = self._prefill_key(bucket)

        def build():
            exe = prefill_program(self.cfg).lower(
                self.params,
                jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                self.kv_cache,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
            ).compile()
            self.stats.prefill_compiles += 1
            return exe

        return self.schedule_cache.get_or_build(
            key, build,
            arena_bytes=self._exec_arena_bytes(((1, bucket), jnp.int32)),
        )

    def compose_key(self) -> tuple:
        """Batched-decode compatibility key for the batch composer.

        Two engines whose keys are equal replay the *same* sealed decode
        executable over interchangeable KV-cache slots, so their lanes'
        requests may share one batched decode step: the key is the sealed
        executable's identity beyond shapes (``_key_options``: cfg, device,
        ``max_len``, ``max_slots``), the bucketing policy (prefill shapes
        must land in the same bucket family), and the **weights' object
        identity** — same config with different parameters is a different
        computation and must never coalesce."""
        return (self._key_options, repr(self.bucketing), id(self.params))

    # -- request flow --------------------------------------------------------
    def validate_request(self, req: Request) -> None:
        """Reject requests this engine can never serve.

        Dispatchers call this at submit time so an unservable prompt raises
        on the *submitter* (synchronous backpressure semantics), not later
        on a stepping thread where it would poison every tenant's futures.
        A retired engine (see :meth:`retire`) rejects everything.
        """
        if self._retired:
            raise RuntimeError("engine is retired; it no longer serves")
        self._bucket(len(req.prompt))          # ValueError if unservable

    def submit(self, req: Request) -> None:
        """Enqueue ``req`` for admission on a later :meth:`step` (stamps
        ``t_submit`` unless the dispatcher already did), then fires the
        installed submit hook so directly-submitted work becomes visible
        to the dispatch layer's ready index."""
        if not req.t_submit:         # dispatcher may have stamped lane entry
            req.t_submit = time.perf_counter()
        self.queue.append(req)
        hook = self._submit_hook
        if hook is not None:
            hook()

    def free_slots(self) -> int:
        """Seats available right now (admission control hook), clamped at
        0 — once the queue holds more requests than free seats there is
        no capacity, not negative capacity (admission-control arithmetic
        built on this must never see a negative)."""
        return max(0, sum(1 for s in self.slots if s is None) - len(self.queue))

    @property
    def idle(self) -> bool:
        """True when no request is queued and every batch slot is free."""
        return not self.queue and all(s is None for s in self.slots)

    def _bucket(self, plen: int) -> int:
        b = self.bucketing.bucket(plen)
        if b > self.max_len:
            raise ValueError(
                f"prompt bucket {b} exceeds engine max_len {self.max_len}"
            )
        return b

    def _finish(self, req: Request, slot: int) -> None:
        with self.tracer.span("engine.finish", cat="engine", rid=req.rid):
            req.done = True
            req.t_done = time.perf_counter()
            self.slots[slot] = None
            # reset the slot's write offset for the next occupant
            self.kv_cache["pos"] = self.kv_cache["pos"].at[slot].set(0)

    def _admit(self) -> list[Request]:
        finished: list[Request] = []
        for slot in range(self.max_slots):
            if self.slots[slot] is not None or not self.queue:
                continue
            # validate BEFORE popping: an unservable directly-submitted
            # prompt (dispatcher submits are validated up front) is failed
            # and returned as finished — popping first and then raising
            # would lose the request and poison the stepping thread
            req = self.queue[0]
            plen = len(req.prompt)
            try:
                b = self._bucket(plen)
            except ValueError as exc:
                self.queue.pop(0)
                self._fail_request(req, f"unservable prompt: {exc}")
                finished.append(req)
                continue
            self.queue.pop(0)
            exe = self._get_prefill_exec(b)    # schedule-cache hit when warm
            padded = np.zeros((1, b), np.int32)
            padded[0, :plen] = req.prompt
            tr = self.tracer
            t0 = time.perf_counter()
            # the spans nest inside the dispatcher's step span (same
            # thread); prefill times the launch, the device's time is in
            # the device trace
            with tr.span("prefill", cat="engine", rid=req.rid,
                         args={"bucket": b} if tr.enabled else None):
                with tr.span("engine.h2d", cat="engine"):
                    tokens = jnp.asarray(padded)
                    at, length = jnp.int32(slot), jnp.int32(plen)
                nxt, self.kv_cache = exe(self.params, tokens, self.kv_cache, at, length)
            self.stats.prefill_s += time.perf_counter() - t0
            with tr.span("engine.readback", cat="engine", args=_PREFILL_READBACK):
                first = int(nxt)
            req.t_first = time.perf_counter()
            req.generated.append(first)
            self.stats.prefill_tokens += 1
            if len(req.generated) >= req.max_new_tokens:
                # e.g. a 1-token request: done at prefill, never seats
                self._finish(req, slot)
                finished.append(req)
                continue
            self._next_tok[slot, 0] = first
            self.slots[slot] = req
        return finished

    def step(self) -> list[Request]:
        """One engine iteration: admit + one decode step for all live slots.

        Returns every request that finished during this step — including
        those admitted and completed within it (they were invisible to the
        old snapshot-based ``run_until_drained``).
        """
        if not self._step_mu.acquire(blocking=False):
            raise RuntimeError(
                "ServingEngine.step() entered concurrently: the engine is "
                "single-stepper; drive it from one thread or lane (e.g. "
                "through a Dispatcher, which serializes per-lane stepping "
                "even with per-engine stepper threads)"
            )
        try:
            return self._step_locked()
        finally:
            self._step_mu.release()

    def _step_locked(self) -> list[Request]:
        finished = self._admit()
        live = [s for s in range(self.max_slots) if self.slots[s] is not None]
        if not live:
            return finished
        tr = self.tracer
        t0 = time.perf_counter()
        with tr.span("decode", cat="engine",
                     args={"live": len(live)} if tr.enabled else None):
            with tr.span("engine.h2d", cat="engine"):
                tokens = jnp.asarray(self._next_tok)
            nxt, self.kv_cache, *routing = self._decode(self.params, self.kv_cache, tokens)
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.steps += 1
        with tr.span("engine.readback", cat="engine", args=_DECODE_READBACK):
            nxt_np = np.asarray(nxt)
        if routing and tr.enabled:
            here, hit = np.asarray(routing[0]).tolist()
            tr.counter("moe.tokens_here", here, cat="moe")
            tr.counter("moe.experts_hit", hit, cat="moe")
        for s in live:
            req = self.slots[s]
            req.generated.append(int(nxt_np[s, 0]))
            self._next_tok[s, 0] = nxt_np[s, 0]
            self.stats.tokens_out += 1
            pos_full = len(req.prompt) + len(req.generated)
            if len(req.generated) >= req.max_new_tokens or pos_full >= self.max_len - 1:
                if len(req.generated) < req.max_new_tokens:
                    # context window full before max_new_tokens: the caller
                    # gets fewer tokens than asked — say so, loudly
                    req.truncated = True
                self._finish(req, s)
                finished.append(req)
        return finished

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        """Step until queue and slots are empty; raises
        :class:`~repro.dispatch.DrainTimeoutError` if ``max_steps`` pass
        with requests still in flight (mirrors ``Dispatcher``)."""
        from repro.dispatch.dispatcher import DrainTimeoutError

        finished: list[Request] = []
        for _ in range(max_steps):
            finished.extend(self.step())
            if self.idle:
                return finished
        if self.idle:
            return finished
        raise DrainTimeoutError(
            f"engine drain exhausted {max_steps} steps with "
            f"{len(self.queue) + sum(s is not None for s in self.slots)} "
            f"requests still in flight"
        )
