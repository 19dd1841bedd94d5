"""Request-scoped tracing: spans with trace IDs, an append-only span
log, and a JSONL wire format the report CLI and CI gates consume.

A *span* is one request's lifecycle: minted at
``PolymulEngine.submit()`` (the trace ID lands on the returned future as
``fut.trace_id``), carried through queueing, EDF dispatch, retries and
breaker transitions as timestamped *events*, and closed exactly once
with a terminal status.  The span-conservation invariant — every
admitted request has exactly ONE terminal span, one of
``resolved`` / ``shed`` / ``failed`` — is what the ``obs-smoke`` CI gate
asserts over a soak run's log (:mod:`repro.launch.obs_report`).

Span state machine (DESIGN.md §12)::

    submit -> [rejected]                      # backpressure, never admitted
    submit -> admit -> (queue ...) -> dispatch -> resolved
                    \\-> shed                  # deadline passed / unmeetable
                    \\-> ... retry/breaker_open events ... -> failed

Each engine dispatch attempt is a span too: ``engine.step``, with one
child span per phase (``engine.pack``, ``engine.upload``,
``engine.launch``, ``engine.device_wait``, ``engine.readback``,
``engine.resolve``), each carrying ``parent_id`` = the step's id.
:meth:`SpanLog.traced` and :meth:`Span.child` record such spans and, over
the same ``with``, a ``jax.profiler.TraceAnnotation`` of the same name,
so a profiler trace shows the same phases on its host plane.

Engine-level happenings that are not tied to one request (circuit
breaker opening/closing, probe dispatches) are logged as *event*
records, so a log line is one of two kinds::

    {"kind": "span",  "trace_id": "...", "parent_id": null,
     "name": "request", "status": ..., "t_start": ..., "t_end": ...,
     "attrs": {...}, "events": [...]}
    {"kind": "event", "name": "breaker_open", "t": ..., "attrs": {...}}

Timestamps are ``time.perf_counter()`` seconds (monotonic, same clock
as the engine's deadlines) plus one ``t_unix`` wall anchor on each
record — derived from a single per-log wall/monotonic anchor pair, not
a syscall per span — so logs from one process are internally orderable
and placeable in wall time.  The profiler stamps its host events with
the same wall clock (``CLOCK_REALTIME``, ns since the Unix epoch): an
``.xplane.pb`` event lies at the ``Task Environment`` plane's
``profile_start_time`` plus its ``start_ns``, and
:meth:`SpanLog.to_trace_ns` maps a span's ``perf_counter`` time there,
so a span log of a whole run lays over a trace of any stretch of it.

Overhead: recording is append-to-list under one lock, no I/O; the JSONL
serialization happens only at :meth:`SpanLog.flush`.  Trace IDs come
from one ``itertools.count`` (``next()`` is atomic under the GIL — no
extra lock) behind a precomputed ``prefix-pid-`` string.  With no span
log installed the engine's tracing branches are single ``is None``
checks — the ``obs-smoke`` gate bounds the enabled cost at <= 5% of
closed-loop throughput.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, IO, Iterable

__all__ = [
    "Span",
    "SpanLog",
    "TERMINAL_STATUSES",
    "conservation",
    "read_jsonl",
]

# The one-terminal-per-admitted-request vocabulary (conservation gate).
TERMINAL_STATUSES = ("resolved", "shed", "failed")
# "rejected" spans exist too, but the request was never admitted (no
# future obligations), so conservation counts them separately.

# next() on itertools.count is atomic in CPython; no lock needed.
_trace_counter = itertools.count()


class Span:
    """One in-flight trace: a request, or a dispatch and its phases.
    Engine-internal mutation only; the record becomes immutable once
    :meth:`finish` hands it to the log."""

    __slots__ = ("trace_id", "parent_id", "name", "t_start", "attrs",
                 "events", "status", "t_end", "failed_child", "_log",
                 "_annotation", "_meta")

    def __init__(self, log: "SpanLog", name: str, trace_id: str,
                 attrs: dict[str, Any], parent_id: str | None = None) -> None:
        self._log = log
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.name = name
        self.t_start = time.perf_counter()
        self.attrs = attrs
        self.events: list[dict[str, Any]] = []
        self.status: str | None = None
        self.t_end: float | None = None
        # the child span that an exception left, if any (traced spans)
        self.failed_child: str | None = None
        self._annotation = None  # the profiler annotation (traced spans)
        self._meta: dict[str, Any] = {}  # its metadata, children inherit it

    def event(self, name: str, **attrs: Any) -> None:
        """Append a timestamped event; no-op after the span finished
        (a late event cannot reopen a terminal span)."""
        if self.status is not None:
            return
        ev = {"t": time.perf_counter(), "name": name}
        if attrs:
            ev["attrs"] = attrs
        self.events.append(ev)

    def finish(self, status: str, **attrs: Any) -> None:
        """Close the span exactly once and emit it to the log.  A second
        finish raises — the tracing twin of the future's resolve-once
        invariant."""
        if self.status is not None:
            raise RuntimeError(
                f"span {self.trace_id} finished twice "
                f"({self.status!r} then {status!r})"
            )
        self.status = status
        self.t_end = time.perf_counter()
        if attrs:
            self.attrs.update(attrs)
        self._log._emit(self.to_record())

    def discard(self) -> None:
        """Close the span without recording it (a traced stretch that
        turned out to hold nothing worth a span)."""
        self.status = "discarded"

    def annotate(self, **meta: Any) -> None:
        """Add attrs that also go on the span's profiler annotation and
        on those of the children opened after this call."""
        self.attrs.update(meta)
        self._meta.update(meta)
        if self._annotation is not None:
            self._annotation.set_metadata(**meta)

    def child(self, name: str, **attrs: Any):
        """A traced child span of this one (see :meth:`SpanLog.traced`);
        it inherits the metadata set by :meth:`annotate`."""
        return _Traced(self._log, name, self, attrs)

    def to_record(self) -> dict[str, Any]:
        return {
            "kind": "span",
            "trace_id": self.trace_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "status": self.status,
            "t_start": self.t_start,
            "t_end": self.t_end,
            # wall placement from the log's one-time anchor pair: no
            # time.time() syscall on the per-span hot path
            "t_unix": self._log.to_unix(self.t_start),
            "attrs": self.attrs,
            "events": self.events,
        }


class SpanLog:
    """Thread-safe span/event collector with an optional JSONL sink.

    ``path=None`` keeps records in memory only (tests, ad-hoc probes);
    with a path, :meth:`flush` appends every record accumulated since
    the last flush.  ``SpanLog`` is also a context manager (flushes on
    exit)."""

    def __init__(self, path: str | os.PathLike[str] | None = None,
                 *, trace_prefix: str = "req") -> None:
        self.path = os.fspath(path) if path is not None else None
        self.trace_prefix = trace_prefix
        # precomputed so minting a trace ID is one format, zero syscalls
        self._id_prefix = f"{trace_prefix}-{os.getpid():x}-"
        # one wall/monotonic anchor pair; every record's t_unix derives
        # from it instead of a per-record time.time() call.  The wall
        # reading is bracketed by two perf_counter readings and paired
        # with their midpoint, a whole number of ns (see to_trace_ns).
        t0 = time.perf_counter_ns()
        self._anchor_ns = time.time_ns()
        self._anchor_perf_ns = (t0 + time.perf_counter_ns()) // 2
        self._anchor_perf = self._anchor_perf_ns / 1e9
        self._lock = threading.Lock()
        self._records: list[dict[str, Any]] = []
        self._unflushed: list[dict[str, Any]] = []

    def to_unix(self, t_perf: float) -> float:
        """Map a ``perf_counter`` timestamp to wall time via the log's
        anchor pair."""
        return self._anchor_ns / 1e9 + (t_perf - self._anchor_perf)

    def to_trace_ns(self, t_perf: float) -> int:
        """Map a ``perf_counter`` timestamp to the profiler's host clock:
        integer ns since the Unix epoch, where an ``.xplane.pb`` event
        lies at ``profile_start_time + start_ns`` (module docstring).
        Both clocks tick in step, so the mapping is the anchor's one
        offset; PERF.md gives its residual against a chip trace.

        The offset is a whole number of ns and ``t_perf`` is rounded to
        the ns from its exact binary value, so two times a whole number
        of ns apart map exactly that far apart; with a half-ns offset or
        a float product, float noise would decide the rounding."""
        num, den = t_perf.as_integer_ratio()
        t_ns = (2 * num * 10**9 + den) // (2 * den)  # round half up
        return self._anchor_ns - self._anchor_perf_ns + t_ns

    # -- recording -----------------------------------------------------
    def start_span(self, name: str, *, parent: Span | None = None,
                   **attrs: Any) -> Span:
        tid = f"{self._id_prefix}{next(_trace_counter):08x}"
        return Span(self, name, tid, attrs,
                    None if parent is None else parent.trace_id)

    def traced(self, name: str, **attrs: Any):
        """Context manager: a span of this log named ``name`` and, over
        the same ``with``, a ``jax.profiler.TraceAnnotation`` of the same
        name (host plane of a profiler trace, when one is recording).
        Yields the :class:`Span`.  On leaving, a span still open closes
        ``ok``; an exception closes it ``failed`` with the error type and
        the ``phase`` that raised (its failed child's name, else its
        own) and marks it as its parent's ``failed_child``.  Children
        come from :meth:`Span.child`."""
        return _Traced(self, name, None, attrs)

    def event(self, name: str, **attrs: Any) -> None:
        """Record an engine-level (non-request) event."""
        t = time.perf_counter()
        self._emit({
            "kind": "event",
            "name": name,
            "t": t,
            "t_unix": self.to_unix(t),
            "attrs": attrs,
        })

    def _emit(self, record: dict[str, Any]) -> None:
        with self._lock:
            self._records.append(record)
            self._unflushed.append(record)

    # -- reading / sinking ---------------------------------------------
    @property
    def records(self) -> list[dict[str, Any]]:
        with self._lock:
            return list(self._records)

    def spans(self, status: str | None = None) -> list[dict[str, Any]]:
        return [
            r for r in self.records
            if r["kind"] == "span" and (status is None or r["status"] == status)
        ]

    def flush(self, fp: IO[str] | None = None) -> int:
        """Append unflushed records as JSONL to ``fp`` or ``self.path``;
        returns the number of records written (0 when neither sink
        exists — records stay readable in memory)."""
        if fp is None and self.path is None:
            return 0
        with self._lock:
            batch, self._unflushed = self._unflushed, []
        if not batch:
            return 0
        lines = "".join(json.dumps(r, sort_keys=True) + "\n" for r in batch)
        if fp is not None:
            fp.write(lines)
        else:
            assert self.path is not None
            with open(self.path, "a") as f:
                f.write(lines)
        return len(batch)

    def __enter__(self) -> "SpanLog":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.flush()


class _Traced:
    """The context manager behind :meth:`SpanLog.traced` and
    :meth:`Span.child` (a class, not a generator: it runs on every
    traced dispatch phase)."""

    __slots__ = ("_log", "_name", "_parent", "_attrs", "_span")

    def __init__(self, log: SpanLog, name: str, parent: Span | None,
                 attrs: dict[str, Any]) -> None:
        self._log, self._name, self._parent = log, name, parent
        self._attrs = attrs

    def __enter__(self) -> Span:
        # imported here so that repro.obs stays importable without JAX
        from jax.profiler import TraceAnnotation

        meta = {} if self._parent is None else self._parent._meta
        span = self._log.start_span(self._name, parent=self._parent, **meta,
                                    **self._attrs)
        span._meta = dict(meta)
        span._annotation = TraceAnnotation(self._name, **meta)
        span._annotation.__enter__()
        self._span = span
        return span

    def __exit__(self, exc_type, exc, tb) -> None:
        span = self._span
        if span.status is None:
            if exc_type is None:
                span.finish("ok")
            else:
                span.finish("failed", error=exc_type.__name__,
                            phase=span.failed_child or self._name)
        if exc_type is not None and self._parent is not None:
            if self._parent.failed_child is None:
                self._parent.failed_child = self._name
        span._annotation.__exit__(None, None, None)


def read_jsonl(path: str | os.PathLike[str]) -> list[dict[str, Any]]:
    """Parse a span-log JSONL file back into records (report CLI / CI
    gate input).  Raises ``ValueError`` naming the offending line on
    malformed input — a truncated log should fail loudly."""
    out: list[dict[str, Any]] = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i}: not valid JSON: {e}") from e
            if not isinstance(rec, dict) or rec.get("kind") not in (
                "span", "event"
            ):
                raise ValueError(
                    f"{path}:{i}: not a span/event record: {line[:80]}"
                )
            out.append(rec)
    return out


def conservation(records: Iterable[dict[str, Any]]) -> dict[str, Any]:
    """Span-conservation accounting over a log: every admitted request
    span must carry exactly one terminal status.  Returns the counts and
    a ``violations`` list (empty = the invariant holds) — the core of
    the ``obs-smoke`` gate (see :mod:`repro.launch.obs_report`)."""
    by_status: dict[str, int] = {}
    violations: list[str] = []
    seen_ids: set[str] = set()
    admitted = 0
    for r in records:
        if r.get("kind") != "span" or r.get("name") != "request":
            continue
        tid = r.get("trace_id", "?")
        if tid in seen_ids:
            violations.append(f"trace {tid}: more than one span record")
        seen_ids.add(tid)
        status = r.get("status")
        by_status[status] = by_status.get(status, 0) + 1
        if status == "rejected":
            continue  # never admitted: no terminal obligation
        admitted += 1
        if status not in TERMINAL_STATUSES:
            violations.append(
                f"trace {tid}: non-terminal status {status!r} "
                f"(want one of {TERMINAL_STATUSES})"
            )
    terminal = sum(by_status.get(s, 0) for s in TERMINAL_STATUSES)
    if terminal != admitted:
        violations.append(
            f"{admitted} admitted spans but {terminal} terminal statuses"
        )
    return {
        "spans": len(seen_ids),
        "admitted": admitted,
        "by_status": by_status,
        "violations": violations,
    }
