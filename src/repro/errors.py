"""Structured error taxonomy: plan-time rejections (PR 7) and
serving-time failures (PR 8).

Every rejection in :func:`repro.plan` raises one of these instead of a
bare ``ValueError`` so callers (and serving front ends) can react to the
*shape* of the failure, not a message string:

* :class:`PlanError` — base class; subclasses ``ValueError`` so existing
  ``except ValueError`` call sites keep working.
* :class:`UnknownKnobError` — the value of a single knob is not in its
  vocabulary (unknown backend/schedule string, malformed ``n``/``v``).
* :class:`UnservableConfigError` — every knob is individually valid but
  the combination cannot be served (four_step depth beyond the canonical
  chain, a tile that cannot fit the VMEM budget at ``row_blk=1``, a
  Pallas backend on the wide width, the wide inverse-CRT overflow).

All three carry the offending ``knob`` name, the rejected ``value`` and
a tuple of nearest valid ``alternatives`` (may be empty when nothing is
close).

The serving layer (:mod:`repro.serve.crypto_engine`) has its own branch:
a request that cannot be served is *resolved* with one of these — its
future carries the error, it is never silently dropped:

* :class:`EngineError` — base class; subclasses ``RuntimeError`` (these
  are execution-time conditions, not configuration mistakes).
* :class:`QueueFullError` — bounded-submission-queue backpressure: the
  blocking ``submit(timeout=)`` expired while the queue stayed full.
  The only taxonomy member *raised at* the caller rather than stored on
  a future (the request was never admitted, so no future exists).
* :class:`DeadlineExceededError` — admission control shed the request:
  its deadline passed, or could not be met, before dispatch.
* :class:`BackendFailedError` — every dispatch attempt (bounded retry +
  backend degradation) failed; the last underlying exception rides in
  ``__cause__`` and the attribute fields say where it died.
* :class:`CompileFailedError` — the plan's executable did not lower or
  compile.  Not a transient fault: it is raised by
  ``PolymulEngine.warmup`` and fails a bucket's requests without retry
  or degradation.
"""
from __future__ import annotations

from typing import Any, Iterable


class PlanError(ValueError):
    """A configuration was rejected at plan time.

    Attributes
    ----------
    knob:
        Name of the offending keyword (``"backend"``, ``"schedule"``,
        ``"tiling"``, ``"row_blk"``, ``"n"``, ``"v"``, ...), or ``None``
        when the failure is not attributable to a single knob.
    value:
        The rejected value, verbatim.
    alternatives:
        Nearest valid values for that knob (possibly empty).
    """

    def __init__(
        self,
        message: str,
        *,
        knob: str | None = None,
        value: Any = None,
        alternatives: Iterable[Any] = (),
    ) -> None:
        super().__init__(message)
        self.knob = knob
        self.value = value
        self.alternatives = tuple(alternatives)


class UnknownKnobError(PlanError):
    """A single knob's value is outside its vocabulary."""


class UnservableConfigError(PlanError):
    """Individually-valid knobs combine into a config no datapath serves."""


class EngineError(RuntimeError):
    """A request failed at serving time (see module docstring).

    Attributes
    ----------
    request_seq:
        The engine-assigned submission sequence number of the affected
        request, or ``None`` when the failure is not per-request
        (``QueueFullError`` — the request was never admitted).
    """

    def __init__(self, message: str, *, request_seq: int | None = None) -> None:
        super().__init__(message)
        self.request_seq = request_seq


class QueueFullError(EngineError):
    """The bounded submission queue stayed full past the submit timeout.

    Attributes
    ----------
    queue_depth:
        Queued requests at the moment the timeout expired.
    max_pending:
        The engine's configured bound.
    """

    def __init__(
        self, message: str, *, queue_depth: int = 0, max_pending: int = 0
    ) -> None:
        super().__init__(message)
        self.queue_depth = queue_depth
        self.max_pending = max_pending


class DeadlineExceededError(EngineError):
    """Admission control shed the request: its deadline passed (or the
    estimated service time could not meet it) before dispatch.

    Attributes
    ----------
    deadline_s:
        The absolute deadline (engine clock) the request carried.
    late_s:
        How far past (or, for a cannot-be-met shed, short of) the
        deadline the shed decision fell, in seconds (>= 0).
    """

    def __init__(
        self,
        message: str,
        *,
        request_seq: int | None = None,
        deadline_s: float = 0.0,
        late_s: float = 0.0,
    ) -> None:
        super().__init__(message, request_seq=request_seq)
        self.deadline_s = deadline_s
        self.late_s = late_s


class BackendFailedError(EngineError):
    """Every dispatch attempt for the request failed — bounded retries
    (and any backend degradation the bucket's circuit breaker performed)
    included.  The final underlying exception is chained as
    ``__cause__``.

    Attributes
    ----------
    backend:
        The backend string of the last attempted dispatch.
    attempts:
        Dispatch attempts this request rode before being failed.
    """

    def __init__(
        self,
        message: str,
        *,
        request_seq: int | None = None,
        backend: str = "",
        attempts: int = 0,
    ) -> None:
        super().__init__(message, request_seq=request_seq)
        self.backend = backend
        self.attempts = attempts


class CompileFailedError(BackendFailedError):
    """The executable for a plan did not lower or compile for this
    device.  The compiler's exception rides in ``__cause__``.  Unlike
    a dispatch fault it is deterministic, so the engine neither retries
    it nor opens a breaker on it (``attempts`` is 0)."""
