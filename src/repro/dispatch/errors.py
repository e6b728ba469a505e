"""Unified error taxonomy for the dispatch plane.

Every failure the dispatcher can surface — backpressure, admission
control, drain timeouts, worker-plane faults, lifecycle violations,
journal corruption — derives from one :class:`DispatchError` base so a
caller can write ``except DispatchError`` once instead of enumerating
the zoo.  The base extends :class:`RuntimeError` because every one of
these classes historically did; existing ``except RuntimeError`` (and
the narrower historical types, which live on as subclasses) keep
working unchanged.

The hierarchy::

    DispatchError(RuntimeError)
    ├── QueueFullError          submit-side backpressure (dispatcher.py)
    ├── DrainTimeoutError       drain exhausted its budget (dispatcher.py)
    ├── AdmissionRejected       SLO admission / shedding (slo.py)
    ├── IllegalTransition       lifecycle state-machine violation
    ├── JournalCorrupt          unreadable / torn request journal
    ├── FaultInjected           a FaultInjector fired (tests only)
    ├── ChipHeldError           worker processes refused on a TPU host
    └── WorkerError             worker-plane faults (workers.py)
        ├── WorkerSetupError
        ├── WorkerCrashed
        └── WorkerTimeout

``QueueFullError``/``DrainTimeoutError`` are still importable from
``repro.dispatch.dispatcher``, ``AdmissionRejected`` from
``repro.dispatch.slo``, and the worker family from
``repro.dispatch.workers`` — those modules re-export the classes defined
(or re-parented) here, so no call site changes.
"""

from __future__ import annotations


class DispatchError(RuntimeError):
    """Base class for every error the dispatch plane raises on purpose.

    Catch this to handle any typed dispatcher failure — backpressure,
    admission rejection, worker faults, lifecycle violations, journal
    corruption — with one handler."""


class QueueFullError(DispatchError):
    """Raised by :meth:`Dispatcher.submit` when the bounded queue is full."""


class DrainTimeoutError(DispatchError):
    """Raised when a drain exhausts its step/time budget with work pending."""


class IllegalTransition(DispatchError):
    """A request or lane was asked to make a lifecycle transition the
    state machine forbids (e.g. ``COMPLETED → QUEUED``).

    Attributes: ``entity`` (``"request"`` or ``"lane"``), ``key`` (rid or
    lane name), ``src`` and ``dst`` (the offending transition)."""

    def __init__(self, entity: str, key: object, src: str, dst: str) -> None:
        super().__init__(
            f"illegal {entity} transition {src!r} -> {dst!r} ({entity}={key!r})"
        )
        self.entity = entity
        self.key = key
        self.src = src
        self.dst = dst


class JournalCorrupt(DispatchError):
    """The request journal could not be read back consistently (torn
    write beyond WAL recovery, schema damage, or an unpicklable lane
    spec).  Carries ``path`` when known."""

    def __init__(self, msg: str, *, path: str = "") -> None:
        super().__init__(msg)
        self.path = path


class FaultInjected(DispatchError):
    """Raised by a :class:`~repro.dispatch.journal.FaultInjector` hook —
    the deterministic stand-in for a crash in recovery tests.  Never
    raised in production paths unless an injector is installed."""


class ChipHeldError(DispatchError):
    """Raised before any worker process is spawned when the backend is a
    TPU: one process holds the chip, and a parent that has touched JAX
    (which the plane's device topology does) holds it, so spawned
    workers needing it would fail or hang in setup.  Serve every TPU
    device from one process instead (engines pinned per device)."""
