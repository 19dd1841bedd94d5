"""Batched HE-polymul serving engine: shape-bucketed continuous batching,
mesh-sharded execution, and fault-tolerant async serving over the
plan/execute API.

The paper's pitch is *low latency and high sample rate* — the
feed-forward PaReNTT datapath "can be pipelined at arbitrary levels" —
and the GPU-HE literature (Shivdikar et al., accelerating polynomial
multiplication on GPUs) locates the real throughput in batching many
residue-polynomial products into one device dispatch.  The FIFO-pipelined
and hazard-free dataflow NTT architectures (arXiv 2501.11867,
2410.04805) get their *sustained* rates from bounded in-flight occupancy
and stall-free hazard handling; this module is the software analogue of
both halves:

* **Shape buckets.**  Requests arrive with heterogeneous plans; the
  frozen, hashable :class:`repro.api.PlanConfig` (``api.plan_key``) is
  the bucket key.  Every distinct config gets exactly ONE jit trace —
  the engine's executor takes the :class:`~repro.api.Plan` pytree as an
  ordinary argument, so same-config dispatches hit one compiled entry
  (asserted by the trace-count probe in ``tests/test_serve_crypto.py``).
* **Fixed batch slots.**  Each dispatch pads its bucket's pending
  requests to ``batch_slots`` rows with zero polynomials, so the
  compiled executable sees ONE static shape per config.  Zero rows are
  dead weight, not a correctness hazard: results are sliced back per
  request.
* **Deadlines, priorities, EDF.**  ``submit(..., deadline=, priority=)``
  attaches scheduling metadata; dispatch picks the bucket whose head
  request is earliest-deadline-first (deadline-less requests order FIFO
  behind any deadline, priority breaks ties).  Admission control sheds
  requests whose deadline has passed or cannot be met — each shed
  future resolves with :class:`repro.errors.DeadlineExceededError`,
  never silently dropped.
* **Backpressure.**  ``max_pending=`` bounds the submission queue;
  ``submit(timeout=)`` blocks for space (raising
  :class:`repro.errors.QueueFullError` on expiry) and ``try_submit``
  returns ``None`` instead of waiting.
* **Failure semantics.**  A dispatch that raises fails or requeues
  exactly the popped requests: bounded per-request retries with
  exponential per-bucket backoff, then
  :class:`repro.errors.BackendFailedError` (underlying exception
  chained as ``__cause__``).  Every admitted request resolves exactly
  once — a value or a typed :class:`repro.errors.EngineError`.
* **Circuit breaker / degradation.**  ``breaker_threshold`` consecutive
  dispatch failures re-plan the bucket one step down the backend
  fallback chain (``pallas_fused_e2e -> pallas -> jnp``) via
  :func:`repro.api.plan` with the same ``n/t/v``, so degraded results
  stay bit-exact; after ``breaker_cooldown_s`` the next dispatch probes
  the original backend and restores it on success.
* **Compilation is not a dispatch.**  Each config's batch executable
  is compiled ahead of time (:meth:`PolymulEngine.warmup`, or before a
  bucket's first dispatch).  A lowering or compile error is
  deterministic, so it never enters the retry/breaker path above: it
  raises :class:`repro.errors.CompileFailedError` from ``warmup`` and
  fails the bucket's requests with it, and the bucket serves nothing
  else in its place.
* **Async front end.**  ``start()`` launches two background
  dispatcher workers driving :meth:`PolymulEngine.step`, so up to two
  dispatches are outstanding: one batch is packed, uploaded and
  launched, or read back and resolved, while the other runs on the
  device.  A second dispatch starts only on a full, healthy bucket
  (:meth:`PolymulEngine._select_locked`), so an open mix at a moderate
  rate and a degraded bucket dispatch one batch at a time, as before.
  Futures support ``result(timeout=)`` blocking waits.  The synchronous
  ``step()``/``run_until_idle()`` closed loop keeps one dispatch per
  call.
* **Mesh mode.**  With ``mesh=``, dispatches run
  :func:`polymul_sharded`: the RNS channel axis of the residue cascade
  shard_maps over ``model`` and the batch axis over ``data``, with the
  plan's table leaves placed one channel slice per ``model`` shard when
  :meth:`PolymulEngine.plan` builds the plan
  (``partition.plan_leaf_shardings``) — the leaf-threaded ops layer
  (DESIGN §7) at work.

Usage::

    eng = PolymulEngine(batch_slots=8, max_pending=64)
    with eng:                               # background dispatcher
        pl = eng.plan(n=4096, t=6, v=30)
        eng.warmup(pl)                      # compile now, not on traffic
        fut = eng.submit(pl, za, zb, deadline=0.5)   # za, zb: (n, S)
        limbs = fut.result(timeout=5.0)     # (n, L), or raises EngineError

Fault injection for soak testing wraps ``engine.executor``
(:mod:`repro.serve.faults`); the soak driver is
``launch/serve_soak.py`` (the ``serve-soak`` CI gate).  Throughput is
measured on the chip by ``bench/run.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import itertools
import math
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from repro import api
from repro.errors import (
    BackendFailedError,
    CompileFailedError,
    DeadlineExceededError,
    QueueFullError,
)
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.sharding import ctx as ctx_mod
from repro.sharding import partition

__all__ = [
    "FALLBACK_NEXT",
    "SNAPSHOT_KEYS",
    "SNAPSHOT_SCHEMA_VERSION",
    "PolymulEngine",
    "PolymulFuture",
    "negacyclic_mul_sharded",
    "polymul_sharded",
]


# --------------------------------------------------------------------------
# mesh-sharded execution (the model x data layout of DESIGN §8)
# --------------------------------------------------------------------------


def _mesh_sizes(mesh) -> tuple[int, int]:
    """(model_size, data_batch_size) of a serving mesh."""
    msize = mesh.shape["model"] if "model" in mesh.axis_names else 1
    bsize = 1
    for a in partition.batch_axes(mesh):
        bsize *= mesh.shape[a]
    return msize, bsize


def negacyclic_mul_sharded(pl: api.Plan, a, b, *, mesh):
    """``shard_map`` the residue cascade: ``a``, ``b`` are ``(t, B, n)``
    residue tensors; the RNS channel axis shards over ``model``, the
    batch axis over the data axes, and the plan's table leaves are
    sliced per shard alongside them (``partition.plan_leaf_specs``) so
    every shard's NTT runs on locally-resident tables.

    Bit-exact vs. the single-device :func:`repro.api.negacyclic_mul`:
    the per-channel cascades are independent (the RNS parallelism the
    paper's t datapaths exploit), so sharding channels is a pure
    layout decision.  Device widths only: the int64 width rebinds its
    kernel tables from the sliced leaves (``api._bound_params``), and
    the wide width rebuilds shard-local channel specs from its
    ``wide_qs``/``wide_betas`` leaves (``api._wide_exec_specs`` — the
    channel-offset view); the oracle width is host-only and cannot be
    traced, let alone sharded.
    """
    cfg = api.plan_key(pl)
    if cfg.width not in ("int64", "wide"):
        raise ValueError(
            f"negacyclic_mul_sharded serves int64/wide-width plans only "
            f"(got width={cfg.width!r}); the oracle datapath is host-only "
            f"and cannot be traced"
        )
    msize, bsize = _mesh_sizes(mesh)
    if cfg.t % msize:
        raise ValueError(
            f"t={cfg.t} RNS channels do not divide the model axis "
            f"({msize}-way): shrink the axis or pick t a multiple of it"
        )
    if a.ndim != 3 or a.shape[0] != cfg.t or a.shape[-1] != cfg.n:
        raise ValueError(
            f"negacyclic_mul_sharded: expected residues (t={cfg.t}, B, "
            f"n={cfg.n}), got shape {tuple(a.shape)}"
        )
    if a.shape != b.shape:
        raise ValueError(
            f"negacyclic_mul_sharded: operand shapes differ: "
            f"{tuple(a.shape)} vs {tuple(b.shape)}"
        )
    if a.shape[1] % bsize:
        raise ValueError(
            f"batch {a.shape[1]} does not divide the data axes "
            f"({bsize}-way); pad the batch (the engine's slot padding "
            f"guarantees this)"
        )
    return _cascade_sharded(pl, a, b, mesh=mesh)


# Jitted so an eager caller gets one compiled program: shard_map outside
# jit runs op by op, minutes per call for one small wide-width product.
@functools.partial(jax.jit, static_argnames=("mesh",))
def _cascade_sharded(pl: api.Plan, a, b, *, mesh):
    leaf_specs = partition.plan_leaf_specs(mesh, pl)
    res_spec = partition.polymul_specs(mesh, pl)["residues"]

    def _local(consts, a_s, b_s):
        # Rebuild a shard-local Plan around the sliced leaves: the ops
        # layer rebinds its kernels to these tables and re-derives the
        # local channel count from their shapes (api._bound_params).
        local = api.Plan(config=pl.config, params=pl.params, consts=consts)
        return api.negacyclic_mul(local, a_s, b_s)

    fn = shard_map(
        _local,
        mesh=mesh,
        in_specs=(leaf_specs, res_spec, res_spec),
        out_specs=res_spec,
    )
    return fn(pl.consts, a, b)


def polymul_sharded(pl: api.Plan, za, zb, *, mesh):
    """Mesh-mode end-to-end polymul: ``(B, n, S)`` segments ->
    ``(B, n, L)`` limbs.  Decompose/compose are batch-parallel edges
    (constrained to the ``polymul_specs`` layout so GSPMD cannot
    all-gather the residue tensors); the cascade between them is the
    explicit ``model`` x ``data`` ``shard_map`` of
    :func:`negacyclic_mul_sharded`.  Compose's channel reduction is the
    one cross-``model`` collective, and GSPMD inserts exactly that."""
    cfg = api.plan_key(pl)
    if cfg.width not in ("int64", "wide"):
        raise ValueError(
            f"polymul_sharded serves int64/wide-width plans only "
            f"(got width={cfg.width!r})"
        )
    return _polymul_sharded(pl, za, zb, mesh=mesh)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _polymul_sharded(pl: api.Plan, za, zb, *, mesh):
    pol = ctx_mod.make_crypto_policy(mesh, pl)
    za = pol(za, "segments")
    zb = pol(zb, "segments")
    ra = pol(api.decompose(pl, za), "residues")
    rb = pol(api.decompose(pl, zb), "residues")
    rp = negacyclic_mul_sharded(pl, ra, rb, mesh=mesh)
    return pol(api.compose(pl, rp), "limbs")


# --------------------------------------------------------------------------
# request plumbing
# --------------------------------------------------------------------------


class PolymulFuture:
    """Handle for one submitted product, with a three-state lifecycle:

    ``PENDING`` (queued or in flight) -> ``DONE`` (``result()`` returns
    the ``(n, L)`` limb array; ``latency_s`` holds submit-to-resolve
    wall time) or ``FAILED`` (``result()`` re-raises the stored
    :class:`repro.errors.EngineError`; ``exception()`` returns it).

    ``result(timeout=)``/``exception(timeout=)`` block up to ``timeout``
    seconds for resolution (raising ``TimeoutError`` on expiry).  With
    no timeout, a future submitted while the engine's background
    dispatcher is running blocks until resolved; otherwise an unserved
    future raises immediately — drive the engine (``step()`` /
    ``run_until_idle()``).  A future resolves exactly once; a second
    resolution attempt is an engine bug and raises.
    """

    PENDING = "PENDING"
    DONE = "DONE"
    FAILED = "FAILED"

    __slots__ = (
        "_value", "_exc", "_state", "_event", "_async",
        "latency_s", "dispatch_index", "trace_id",
    )

    def __init__(self):
        self._value = None
        self._exc = None
        self._state = PolymulFuture.PENDING
        self._event = threading.Event()
        self._async = False
        self.latency_s = None
        self.dispatch_index = None  # executor call index that resolved it
        self.trace_id = None  # obs span id (engines with a span_log)

    @property
    def state(self) -> str:
        return self._state

    def done(self) -> bool:
        return self._state != PolymulFuture.PENDING

    def exception(self, timeout: float | None = None):
        """The stored EngineError of a FAILED future, None when DONE."""
        self._wait(timeout)
        return self._exc

    def result(self, timeout: float | None = None):
        self._wait(timeout)
        if self._state == PolymulFuture.DONE:
            return self._value
        if self._state == PolymulFuture.FAILED:
            raise self._exc
        raise RuntimeError(
            "request not served yet — drive the engine "
            "(step() / run_until_idle()), or pass result(timeout=)"
        )

    def _wait(self, timeout: float | None) -> None:
        if self._state != PolymulFuture.PENDING:
            return
        if timeout is not None:
            if not self._event.wait(timeout):
                raise TimeoutError(
                    f"request still PENDING after {timeout}s"
                )
        elif self._async:
            self._event.wait()

    # -- engine side ---------------------------------------------------
    def _check_unresolved(self) -> None:
        if self._state != PolymulFuture.PENDING:
            raise RuntimeError(
                f"future resolved twice (state={self._state}) — "
                f"engine invariant violation"
            )

    def _resolve(self, value, latency_s: float, dispatch_index=None):
        self._check_unresolved()
        self._value = value
        self.latency_s = latency_s
        self.dispatch_index = dispatch_index
        self._state = PolymulFuture.DONE
        self._event.set()

    def _fail(self, exc: Exception, latency_s=None, dispatch_index=None):
        self._check_unresolved()
        self._exc = exc
        self.latency_s = latency_s
        self.dispatch_index = dispatch_index
        self._state = PolymulFuture.FAILED
        self._event.set()


@dataclasses.dataclass
class _Request:
    za: np.ndarray  # (n, S)
    zb: np.ndarray  # (n, S)
    future: PolymulFuture
    seq: int
    t_submit: float
    deadline: float | None = None  # absolute engine-clock deadline
    priority: int = 0  # higher dispatches sooner among equal deadlines
    attempts: int = 0  # failed dispatch attempts ridden so far
    span: obs_tracing.Span | None = None  # request trace (span_log engines)


def _order_key(req: _Request) -> tuple:
    """Heap key: earliest deadline first (deadline-less requests sort
    behind every deadline), then priority (higher first), then FIFO."""
    dl = req.deadline if req.deadline is not None else math.inf
    return (dl, -req.priority, req.seq)


@dataclasses.dataclass
class _Bucket:
    """One PlanConfig's queue + breaker state.  ``chain[0]`` is the
    original plan; ``chain[level]`` is the currently-active (possibly
    degraded) plan.  ``failures`` counts consecutive dispatch failures
    at the current level; ``not_before`` is the backoff gate."""

    key: api.PlanConfig
    chain: list  # [Plan, ...] original + lazily-built fallbacks
    heap: list = dataclasses.field(default_factory=list)
    level: int = 0
    failures: int = 0
    not_before: float = 0.0
    opened_at: float = 0.0  # when the breaker last opened / probe failed
    ewma_service_s: float = 0.0

    def push(self, req: _Request) -> None:
        heapq.heappush(self.heap, (*_order_key(req), req))

    def pop(self) -> _Request:
        return heapq.heappop(self.heap)[3]

    @property
    def plan(self) -> api.Plan:  # the original, pre-degradation plan
        return self.chain[0]

    @property
    def active_plan(self) -> api.Plan:
        return self.chain[self.level]


# Backend degradation chain (circuit breaker): each entry's fallback is
# strictly simpler/more portable; all entries are bit-exact vs each
# other (tests/test_backends.py), so degrading never changes results.
FALLBACK_NEXT = {
    "pallas_fused_e2e": "pallas",
    "pallas_fused": "pallas",
    "pallas": "jnp",
}


# --------------------------------------------------------------------------
# observability vocabulary
# --------------------------------------------------------------------------

# The engine's counters, registered as `repro_engine_<key>_total` counter
# families labeled {engine=<name>} in the process metrics registry
# (repro.obs.metrics).  `PolymulEngine.stats` is a live dict view over
# this engine's children; exporters read the same numbers.
_STAT_KEYS = (
    "submitted",  # admitted + DOA-shed requests (rejected NOT included)
    "served",  # futures resolved with a result
    "dispatches",  # successful executor calls
    "padded_slots",  # zero rows padded across successful dispatches
    "rejected",  # backpressure: never admitted (no future created)
    "shed",  # futures resolved with DeadlineExceededError
    "retried",  # request requeues after failed dispatches
    "failed",  # futures resolved with BackendFailedError
    "dispatch_failures",  # executor calls that raised
    "breaker_opened",  # bucket degradations down FALLBACK_NEXT
    "breaker_recovered",  # successful probes restoring the original
    "probes",  # original-backend probe dispatches while degraded
)

# snapshot() wire contract, pinned by tests/test_obs.py: the exact key
# set a snapshot dict carries at SNAPSHOT_SCHEMA_VERSION.  Changing the
# schema means bumping the version AND this tuple in the same commit —
# downstream dashboards key on it.
SNAPSHOT_SCHEMA_VERSION = 1
SNAPSHOT_KEYS = _STAT_KEYS + (
    "schema_version",  # int, == SNAPSHOT_SCHEMA_VERSION
    "engine",  # engine instance name (metrics label value)
    "queue_depth",  # queued, not yet dispatched
    "inflight",  # popped, dispatch outcome pending
    "latency_p50_ms",  # submit->result p50 over latency_window (or None)
    "latency_p99_ms",  # submit->result p99 over latency_window (or None)
    "degraded_buckets",  # buckets currently serving a fallback backend
    "bucket_backends",  # {bucket key str: active backend str}
)

_engine_names = itertools.count()


def _result_dtype(cfg: api.PlanConfig):
    """The dtype the batch executable's result crosses to the host in:
    int32 while a base-2^w limb fits a signed 32-bit word (every width
    today: w = 28), else int64.  An int64 array reads back from a v5e
    at ~0.2 GB/s, an int32 one 13–22x faster (PERF.md §6, Findings)."""
    return jnp.int32 if cfg.w <= 31 else jnp.int64


class _StepLocal(threading.local):
    step = None  # the traced engine.step span of this thread's dispatch
    dispatch_index = None  # the dispatch whose executor call this thread runs


def _bucket_key_str(cfg: api.PlanConfig) -> str:
    """Human-stable bucket label used in snapshot()['bucket_backends']
    and span attrs: enough of the PlanConfig to tell buckets apart."""
    return f"n{cfg.n}_t{cfg.t}_v{cfg.v}_{cfg.backend}"


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------


class PolymulEngine:
    """Shape-bucketed continuous-batching engine over the Plan API, with
    deadline/priority scheduling, bounded-queue backpressure, bounded
    retry, per-bucket circuit breaking onto fallback backends, and an
    optional two-worker background dispatcher (see module docstring).

    Parameters
    ----------
    batch_slots:
        Fixed rows per dispatch.  Every micro-batch is padded to this
        many polynomials, so each distinct ``PlanConfig`` compiles ONE
        executable.
    mesh:
        Optional ``jax.sharding.Mesh`` with ``model``/data axes; when
        set, dispatches run :func:`polymul_sharded`.  ``batch_slots``
        must divide the data axes so the padded batch always shards.
    donate:
        Donate the padded operand buffers to XLA (they are rebuilt per
        dispatch, so nothing reads them back).
    max_pending:
        Bound on queued (not yet dispatched) requests; ``None`` (the
        default) leaves the queue unbounded.  With a bound set,
        ``submit`` blocks for space and ``try_submit`` returns ``None``
        when full.
    max_retries:
        How many times one request may be re-queued after a failed
        dispatch before its future fails with ``BackendFailedError``
        (probe dispatches of the original backend don't count).
    breaker_threshold:
        Consecutive dispatch failures at the bucket's current backend
        before the circuit breaker degrades it one step down
        ``FALLBACK_NEXT``.
    breaker_cooldown_s:
        How long a degraded bucket serves its fallback before the next
        dispatch probes the original backend again.
    backoff_base_s:
        Base of the per-bucket exponential dispatch backoff
        (``base * 2^(failures-1)``, capped at 1 s).
    name:
        Metrics label for this engine instance (``engine=<name>`` on
        every ``repro_engine_*`` series); auto-minted when omitted.
    registry:
        The :class:`repro.obs.metrics.MetricsRegistry` to count into
        (default: the process-wide registry).
    span_log:
        Optional :class:`repro.obs.tracing.SpanLog`.  When set, every
        ``submit()`` mints a request span (trace id on the returned
        future as ``fut.trace_id``) and the full lifecycle — admit,
        dispatch, retry, breaker transitions, terminal resolve/shed/
        fail — lands in the log; each dispatch attempt is an
        ``engine.step`` span with one child span per phase (pack,
        upload, launch, device_wait, readback, resolve), each also a
        ``jax.profiler`` annotation.  ``None`` (default) keeps the hot
        paths tracing-free.
    """

    # Background dispatcher workers, hence dispatches outstanding at once
    # under start(): a second starts only where _select_locked allows it.
    _WORKERS = 2

    def __init__(self, *, batch_slots: int = 8, mesh=None,
                 donate: bool = False, max_pending: int | None = None,
                 max_retries: int = 3, breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 1.0,
                 backoff_base_s: float = 0.01,
                 latency_window: int = 4096,
                 name: str | None = None,
                 registry: obs_metrics.MetricsRegistry | None = None,
                 span_log: obs_tracing.SpanLog | None = None):
        if batch_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got {batch_slots}")
        if mesh is not None:
            _, bsize = _mesh_sizes(mesh)
            if batch_slots % bsize:
                raise ValueError(
                    f"batch_slots={batch_slots} must divide the mesh's "
                    f"data axes ({bsize}-way) so padded batches shard"
                )
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got {breaker_threshold}"
            )
        self.batch_slots = batch_slots
        self.mesh = mesh
        self.max_pending = max_pending
        self.max_retries = max_retries
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown_s = breaker_cooldown_s
        self.backoff_base_s = backoff_base_s
        self._plans: dict[api.PlanConfig, api.Plan] = {}
        self._buckets: dict[api.PlanConfig, _Bucket] = {}
        self._seq = itertools.count()
        self._trace_log: list[api.PlanConfig] = []
        self._cond = threading.Condition()
        self._threads: list[threading.Thread] = []  # the running workers
        self._compile_lock = threading.Lock()
        self._stop_evt = threading.Event()
        self._loop_error: BaseException | None = None
        self._inflight = 0
        self._dispatch_seq = 0  # executor call counter (success + failure)
        self._latencies: deque = deque(maxlen=latency_window)
        self.name = name if name is not None else f"engine-{next(_engine_names)}"
        self.span_log = span_log
        # the engine.step span of the dispatch this thread is running, for
        # the executor's phase spans (set only while a span_log traces)
        self._tls = _StepLocal()
        self._registry = (
            registry if registry is not None else obs_metrics.registry()
        )
        # One counter child per stat, labeled by engine instance; the
        # `stats` property is a read view over exactly these children.
        self._m = {
            k: self._registry.counter(
                f"repro_engine_{k}_total", labelnames=("engine",)
            ).labels(engine=self.name)
            for k in _STAT_KEYS
        }
        self._h_latency = self._registry.histogram(
            "repro_engine_latency_seconds",
            "submit-to-result latency of served requests",
            ("engine",),
        ).labels(engine=self.name)
        self._h_queue_wait = self._registry.histogram(
            "repro_engine_queue_wait_seconds",
            "submit-to-first-dispatch wait of dispatched requests",
            ("engine",),
        ).labels(engine=self.name)
        self._g_queue_depth = self._registry.gauge(
            "repro_engine_queue_depth", "queued, not yet dispatched",
            ("engine",),
        ).labels(engine=self.name)
        self._g_inflight = self._registry.gauge(
            "repro_engine_inflight", "popped, dispatch outcome pending",
            ("engine",),
        ).labels(engine=self.name)
        # Outside _STAT_KEYS, so snapshot()'s schema does not change.
        self._m_packed_readbacks = self._registry.counter(
            "repro_engine_packed_readbacks_total",
            "executor results read back from the device as int32",
            ("engine",),
        ).labels(engine=self.name)
        self._m_overlapped = self._registry.counter(
            "repro_engine_overlapped_dispatches_total",
            "executor calls begun while another dispatch was outstanding",
            ("engine",),
        ).labels(engine=self.name)

        def _run(pl, za, zb):
            """The batch executable: ``(B, n, S)`` segments -> ``(B, n,
            L)`` product limbs in :func:`_result_dtype`;
            ``_execute_batch`` widens them back to int64 on the host."""
            # Appended at TRACE time only: the probe that asserts one
            # compilation per distinct PlanConfig.
            self._trace_log.append(api.plan_key(pl))
            if mesh is not None:
                out = polymul_sharded(pl, za, zb, mesh=mesh)
            else:
                out = api.polymul(pl, za, zb)
            with jax.named_scope("engine.result_narrow"):
                return out.astype(_result_dtype(pl.config))

        self._exec = jax.jit(
            _run, donate_argnums=(1, 2) if donate else ()
        )
        # Successful dispatches by the plan's datapath width; also
        # outside _STAT_KEYS.  Children are made on first use.
        self._m_width_dispatches = self._registry.counter(
            "repro_engine_width_dispatches_total",
            "successful executor calls by datapath width",
            ("engine", "width"),
        )
        # PlanConfig -> its compiled batch executable, or the
        # CompileFailedError it raised (so a bucket that cannot compile
        # fails fast instead of recompiling per dispatch).
        self._compiled: dict = {}
        # PlanConfig -> the executable's shardings of (za, zb), where a
        # traced dispatch places the operands (one device, or the mesh)
        self._operand_shardings: dict = {}
        # The raw batch executor, called synchronously: one padded batch
        # in, its (batch_slots, n, L) int64 result out.  Fault injectors
        # and tests wrap THIS attribute (FaultInjector.install).  Every
        # dispatch goes through it, so a wrapper sees one call per engine
        # dispatch attempt; under start() two workers may call it at
        # once, and out of dispatch-index order, so the index of the
        # call in progress is on the calling thread's ``_tls``.
        self.executor = self._execute_batch

    # -- plan cache ----------------------------------------------------
    def plan(self, n: int = 4096, t: int = 6, v: int = 30, **kw) -> api.Plan:
        """Build-or-fetch a plan, cached by its resolved
        :func:`api.plan_key` — repeated preset lookups share one Plan
        object (and, transitively, one set of device tables).  In mesh
        mode the plan's table leaves are placed on the mesh, each RNS
        channel's tables on the ``model`` shard that serves it."""
        pl = api.plan(n=n, t=t, v=v, **kw)
        cfg = api.plan_key(pl)
        if (self.mesh is not None and cfg not in self._plans
                and cfg.width in ("int64", "wide")):
            pl = api.Plan(
                config=pl.config, params=pl.params,
                consts=jax.device_put(
                    pl.consts, partition.plan_leaf_shardings(self.mesh, pl)
                ),
            )
        return self._plans.setdefault(cfg, pl)

    def warmup(self, pl: api.Plan) -> float:
        """Compile the batch executable for ``pl``'s config before any
        traffic; returns the seconds spent (0.0 when it was already
        compiled, and for the host-only oracle width).  Raises
        :class:`repro.errors.CompileFailedError` when the plan does not
        lower or compile for this device."""
        cfg = api.plan_key(pl)
        if cfg.width == "oracle":
            return 0.0
        t0 = time.perf_counter()
        self._executable(self._plans.setdefault(cfg, pl))
        return time.perf_counter() - t0

    def _executable(self, pl: api.Plan):
        """``pl``'s compiled batch executable, compiled on first use for
        the padded ``(batch_slots, n, S)`` operand shape; its result is
        ``(batch_slots, n, L)`` in :func:`_result_dtype`."""
        cfg = api.plan_key(pl)
        got = self._compiled.get(cfg)
        if got is None:
            with self._compile_lock:  # two workers that miss compile once
                got = self._compiled.get(cfg)
                if got is None:
                    got = self._compiled[cfg] = self._compile(pl, cfg)
        if isinstance(got, CompileFailedError):
            raise got
        return got

    def _compile(self, pl: api.Plan, cfg: api.PlanConfig):
        z = jax.ShapeDtypeStruct(
            (self.batch_slots, cfg.n, cfg.seg_count), jnp.int64
        )
        try:
            got = self._exec.lower(pl, z, z).compile()
            self._operand_shardings[cfg] = got.input_shardings[0][1:]
        except Exception as e:  # noqa: BLE001 — any lowering/compile error
            got = CompileFailedError(
                f"bucket {_bucket_key_str(cfg)}: the batch executable "
                f"did not compile for {jax.devices()[0].platform}: "
                f"{type(e).__name__}: {e}",
                backend=cfg.backend,
            )
            got.__cause__ = e
        return got

    # -- request intake ------------------------------------------------
    def _validate_submit(self, pl, za, zb):
        cfg = api.plan_key(pl)
        za = np.asarray(za)
        zb = np.asarray(zb)
        want = (cfg.n, cfg.seg_count)
        for name, z in (("za", za), ("zb", zb)):
            if z.shape != want:
                raise ValueError(
                    f"submit: expected {name} segments (n={cfg.n}, "
                    f"S={cfg.seg_count}), got shape {z.shape}"
                )
        if self.mesh is not None:
            # Mirror the sharded-dispatch preconditions HERE: step()
            # pops requests before dispatching, so a config that can
            # only fail at trace time would burn retries for nothing.
            if cfg.width not in ("int64", "wide"):
                raise ValueError(
                    f"mesh mode serves int64/wide-width plans only "
                    f"(got width={cfg.width!r})"
                )
            msize, _ = _mesh_sizes(self.mesh)
            if cfg.t % msize:
                raise ValueError(
                    f"mesh mode: t={cfg.t} RNS channels do not divide "
                    f"the model axis ({msize}-way); pick t a multiple "
                    f"of it or shrink the axis"
                )
        return cfg, za, zb

    def _enqueue_locked(self, cfg, pl, za, zb, deadline, priority,
                        now: float) -> PolymulFuture:
        bucket = self._buckets.get(cfg)
        if bucket is None:
            bucket = self._buckets[cfg] = _Bucket(
                key=cfg, chain=[self._plans.setdefault(cfg, pl)]
            )
        fut = PolymulFuture()
        fut._async = bool(self._threads)
        req = _Request(
            za=za, zb=zb, future=fut, seq=next(self._seq), t_submit=now,
            deadline=(now + deadline) if deadline is not None else None,
            priority=priority,
        )
        if self.span_log is not None:
            req.span = self.span_log.start_span(
                "request", engine=self.name, seq=req.seq,
                bucket=_bucket_key_str(cfg), deadline=req.deadline,
                priority=priority,
            )
            fut.trace_id = req.span.trace_id
        self._m["submitted"].inc()
        if req.deadline is not None and req.deadline <= now:
            # dead on arrival: admission control resolves it, queue
            # untouched (typed error, never a silent drop)
            self._m["shed"].inc()
            if req.span is not None:
                req.span.finish("shed", reason="doa", latency_s=0.0)
            fut._fail(
                DeadlineExceededError(
                    f"deadline expired {now - req.deadline:.6f}s before "
                    f"admission (seq {req.seq})",
                    request_seq=req.seq, deadline_s=req.deadline,
                    late_s=now - req.deadline,
                ),
                latency_s=0.0,
            )
            return fut
        if req.span is not None:
            req.span.event("admit", queue_depth=self._pending_locked())
        bucket.push(req)
        self._cond.notify_all()
        return fut

    def submit(self, pl: api.Plan, za, zb, *, deadline: float | None = None,
               priority: int = 0,
               timeout: float | None = None) -> PolymulFuture:
        """Enqueue one product ``a * b`` under plan ``pl``.  ``za``,
        ``zb``: ``(n, S)`` base-2^v segment arrays.  ``deadline`` is
        seconds from now; a request that cannot dispatch in time is shed
        (future fails with ``DeadlineExceededError``).  ``priority``
        orders equal-deadline requests (higher first).  When the queue
        is bounded and full, blocks up to ``timeout`` seconds for space
        (forever if ``timeout`` is None — pass a timeout or use
        :meth:`try_submit` when nothing else drives the engine), then
        raises :class:`repro.errors.QueueFullError`.  Returns a
        :class:`PolymulFuture`; drive the engine (or run the background
        dispatcher) to resolve it."""
        cfg, za, zb = self._validate_submit(pl, za, zb)
        t_end = None if timeout is None else time.perf_counter() + timeout
        with self._cond:
            while (self.max_pending is not None
                   and self._pending_locked() >= self.max_pending):
                remaining = (
                    None if t_end is None else t_end - time.perf_counter()
                )
                if remaining is not None and remaining <= 0:
                    self._m["rejected"].inc()
                    if self.span_log is not None:
                        s = self.span_log.start_span(
                            "request", engine=self.name,
                            bucket=_bucket_key_str(cfg),
                        )
                        s.finish("rejected", reason="queue_full")
                    raise QueueFullError(
                        f"submission queue full "
                        f"({self._pending_locked()} >= "
                        f"max_pending={self.max_pending}) after "
                        f"{timeout}s",
                        queue_depth=self._pending_locked(),
                        max_pending=self.max_pending,
                    )
                # bounded wait so a missed notify cannot wedge a waiter
                self._cond.wait(0.05 if remaining is None
                                else min(remaining, 0.05))
            return self._enqueue_locked(
                cfg, pl, za, zb, deadline, priority, time.perf_counter()
            )

    def try_submit(self, pl: api.Plan, za, zb, *,
                   deadline: float | None = None,
                   priority: int = 0) -> PolymulFuture | None:
        """Non-blocking :meth:`submit`: returns ``None`` (and counts a
        rejection) instead of waiting when the bounded queue is full."""
        cfg, za, zb = self._validate_submit(pl, za, zb)
        with self._cond:
            if (self.max_pending is not None
                    and self._pending_locked() >= self.max_pending):
                self._m["rejected"].inc()
                if self.span_log is not None:
                    s = self.span_log.start_span(
                        "request", engine=self.name,
                        bucket=_bucket_key_str(cfg),
                    )
                    s.finish("rejected", reason="queue_full")
                return None
            return self._enqueue_locked(
                cfg, pl, za, zb, deadline, priority, time.perf_counter()
            )

    def _pending_locked(self) -> int:
        return sum(len(b.heap) for b in self._buckets.values())

    def pending(self) -> int:
        with self._cond:
            return self._pending_locked()

    def _has_work_locked(self) -> bool:
        return self._inflight > 0 or any(
            b.heap for b in self._buckets.values()
        )

    # -- execution -----------------------------------------------------
    def _execute_batch(self, pl: api.Plan, za, zb):
        """The raw dispatch executor: one padded micro-batch through the
        plan's datapath, returned as one C-contiguous ``(batch_slots, n,
        L)`` int64 array.  ``self.executor`` points here unless a fault
        injector (or test double) wrapped it.  The call is synchronous:
        it returns once the result is on the host.  The background
        dispatcher overlaps two batches by calling it from two workers,
        never by splitting it.

        The result crosses to the host as int32 (:func:`_result_dtype`)
        and is widened to int64 here.  On a v5e the device already holds
        it lane-dense, n on the 128 lanes, whatever its logical axis
        order: XLA picks that layout for the executable's result.

        Under a traced ``engine.step`` the call is split into its four
        operations, each a phase span: operands onto the device (with
        the executable's input shardings), the executable and its
        result's copy queued, the wait for the executable, the wait for
        the copy and the widening (the span's ``bytes`` are the device
        bytes read back).  Untraced it stays one call, which does
        the same in the runtime's C++ path: the split costs ~0.25 ms of
        host time a dispatch on a v5e host (PERF.md §6, Findings)."""
        cfg = api.plan_key(pl)
        if cfg.width == "oracle":
            # Host-only width: no tracing — runs the exact bigint path.
            return np.asarray(api.polymul(pl, za, zb))
        exe = self._executable(pl)
        step = self._tls.step
        if step is None:
            limbs = np.asarray(exe(pl, za, zb), np.int64)
        else:
            with step.child("engine.upload", bytes=za.nbytes + zb.nbytes):
                za, zb = jax.device_put((za, zb), self._operand_shardings[cfg])
            with step.child("engine.launch"):
                out = exe(pl, za, zb)
                out.copy_to_host_async()  # as np.asarray does, before the wait
            with step.child("engine.device_wait"):
                out.block_until_ready()
            with step.child("engine.readback", bytes=out.nbytes):
                limbs = np.asarray(out, np.int64)
        if _result_dtype(cfg) == jnp.int32:
            self._m_packed_readbacks.inc()
        return limbs

    def _fallback_plan(self, pl: api.Plan) -> api.Plan | None:
        """The next plan down the degradation chain for ``pl`` — same
        n/t/v (bit-exact results), one backend simpler — or ``None``
        when the chain is exhausted / the width has no chain."""
        cfg = api.plan_key(pl)
        if cfg.width != "int64":
            return None
        nb = FALLBACK_NEXT.get(cfg.backend)
        if nb is None:
            return None
        # Prefer carrying the frozen spec (identical tiling); if that
        # combination is unservable on the fallback backend, let plan()
        # re-resolve from the schedule kind.
        for sched in (cfg.schedule, cfg.schedule.kind):
            try:
                return api.plan(
                    n=cfg.n, t=cfg.t, v=cfg.v, backend=nb, schedule=sched,
                    row_blk=cfg.row_blk, use_sau=cfg.use_sau,
                )
            except ValueError:
                continue
        return None

    # -- scheduling ----------------------------------------------------
    def _collect_expired(self) -> list[tuple[_Request, float]]:
        """Pop every queued request whose deadline has already passed.
        The heap orders by deadline first, so expired entries are always
        at the head."""
        out = []
        now = time.perf_counter()
        with self._cond:
            for b in self._buckets.values():
                while b.heap and b.heap[0][0] < now:
                    out.append((b.pop(), now))
            if out:
                self._m["shed"].inc(len(out))
                self._cond.notify_all()  # queue space freed
        return out

    def _resolve_shed(self, items: list[tuple[_Request, float]]) -> int:
        for req, now in items:
            if req.span is not None:
                reason = (
                    "expired" if req.deadline is not None
                    and req.deadline <= now else "unmeetable"
                )
                req.span.finish(
                    "shed", reason=reason, latency_s=now - req.t_submit
                )
            req.future._fail(
                DeadlineExceededError(
                    f"deadline missed before dispatch (seq {req.seq}, "
                    f"{max(now - req.deadline, 0.0):.6f}s late)",
                    request_seq=req.seq, deadline_s=req.deadline,
                    late_s=max(now - req.deadline, 0.0),
                ),
                latency_s=now - req.t_submit,
            )
        return len(items)

    def _select_locked(self, now: float):
        """EDF bucket choice: among buckets whose backoff gate is open,
        pick the one whose head request sorts first by
        (deadline, -priority, seq).  Returns ``None`` (idle),
        ``("defer", wake_at)`` (all live buckets backing off),
        ``("hold",)`` (another dispatch is outstanding and this one may
        not start beside it) or ``("go", bucket, plan_to_use, probing)``.
        Changes nothing, so the dispatcher's idle test calls it too.

        A second dispatch starts beside an outstanding one only on a
        bucket that fills a whole batch and is healthy: not degraded,
        not failing (so not backing off) and so not probing either.  An
        open mix at a moderate rate thus keeps one partial batch at a
        time, and the breaker one probe or degraded dispatch."""
        live = [b for b in self._buckets.values() if b.heap]
        if not live:
            return None
        ready = [b for b in live if b.not_before <= now]
        bucket = min(ready, key=lambda b: b.heap[0][:3]) if ready else None
        if self._inflight and (
            bucket is None or len(bucket.heap) < self.batch_slots
            or bucket.level > 0 or bucket.failures > 0
        ):
            return ("hold",)
        if bucket is None:
            return ("defer", min(b.not_before for b in live))
        probing = (
            bucket.level > 0
            and now - bucket.opened_at >= self.breaker_cooldown_s
        )
        use_plan = bucket.chain[0] if probing else bucket.active_plan
        return ("go", bucket, use_plan, probing)

    def _count_probe_locked(self, bucket: _Bucket) -> None:
        self._m["probes"].inc()
        if self.span_log is not None:
            self.span_log.event(
                "probe", engine=self.name,
                bucket=_bucket_key_str(bucket.key),
                backend=api.plan_key(bucket.chain[0]).backend,
            )

    def _admit_locked(self, bucket: _Bucket):
        """Pop up to ``batch_slots`` requests, shedding any whose
        deadline cannot be met given the bucket's EWMA service time."""
        now = time.perf_counter()
        est = bucket.ewma_service_s
        reqs, shed = [], []
        while bucket.heap and len(reqs) < self.batch_slots:
            req = bucket.pop()
            if req.deadline is not None and now + est > req.deadline:
                shed.append((req, now))
            else:
                reqs.append(req)
        if shed:
            self._m["shed"].inc(len(shed))
        if reqs or shed:
            self._cond.notify_all()  # queue space freed
        return reqs, shed

    # -- dispatch ------------------------------------------------------
    def step(self) -> int:
        """Dispatch ONE micro-batch from the EDF-chosen bucket.  Returns
        the number of requests *resolved* during the call — served,
        shed, or failed; 0 when idle.  A dispatch that raises never
        loses requests: the popped requests are requeued (bounded
        retries, per-bucket backoff + circuit breaking) or their futures
        fail with a typed error.  Called directly, each call runs at
        most one dispatch; the background dispatcher (:meth:`start`)
        calls it from two workers, so that two may be outstanding.

        With a ``span_log``, a call that attempts a dispatch is an
        ``engine.step`` span from its start, with its phases as child
        spans (:mod:`repro.obs.tracing`); a call that dispatches nothing
        leaves no span."""
        if self.span_log is None:
            return self._step(None)
        with self.span_log.traced("engine.step") as span:
            resolved = self._step(span)
            if span.attrs.get("dispatch_index") is None:
                span.discard()  # idle, deferred, held or shed: no dispatch
            return resolved

    def _step(self, step) -> int:
        resolved = self._resolve_shed(self._collect_expired())
        while True:
            with self._cond:  # one hold: a second worker sees _inflight
                pick = self._select_locked(time.perf_counter())
                if pick is not None and pick[0] == "go":
                    _, bucket, use_plan, probing = pick
                    if probing:
                        self._count_probe_locked(bucket)
                    reqs, shed = self._admit_locked(bucket)
                    self._inflight += len(reqs)
            if pick is None or pick[0] == "hold":
                return resolved
            if pick[0] == "defer":
                if self._stop_evt.is_set():
                    return resolved
                time.sleep(
                    min(max(pick[1] - time.perf_counter(), 0.0), 0.05)
                )
                resolved += self._resolve_shed(self._collect_expired())
                continue
            resolved += self._resolve_shed(shed)
            if not reqs:
                continue  # everything admitted this round was shed
            cfg = api.plan_key(use_plan)
            if cfg.width != "oracle":
                try:
                    self._executable(use_plan)
                except CompileFailedError as e:
                    return resolved + self._on_compile_failure(
                        use_plan, reqs, e
                    )
            with self._cond:
                dispatch_idx = self._dispatch_seq
                self._dispatch_seq += 1
            pad = 0 if cfg.width == "oracle" else self.batch_slots - len(reqs)
            if step is not None:
                step.annotate(dispatch_index=dispatch_idx)
                step.attrs.update(
                    bucket=_bucket_key_str(bucket.key), width=cfg.width,
                    batch=len(reqs),
                    pad=pad, probing=probing,
                    requests=[r.span.trace_id for r in reqs if r.span],
                )
            t0 = time.perf_counter()
            for r in reqs:
                if r.attempts == 0:  # first attempt: the queue wait
                    self._h_queue_wait.observe(t0 - r.t_submit)
                if r.span is not None:
                    r.span.event(
                        "dispatch", dispatch_index=dispatch_idx,
                        backend=cfg.backend, batch=len(reqs),
                        attempt=r.attempts, probing=probing,
                        step_id=None if step is None else step.trace_id,
                    )
            try:
                if step is None:
                    za, zb = self._pack(cfg, reqs)
                else:
                    with step.child("engine.pack") as phase:
                        za, zb = self._pack(cfg, reqs)
                        phase.attrs["bytes"] = za.nbytes + zb.nbytes
                with self._cond:  # another dispatch's requests in flight
                    overlapped = self._inflight > len(reqs)
                if overlapped:
                    self._m_overlapped.inc()
                if step is not None:
                    step.attrs["overlapped"] = overlapped
                self._tls.step, self._tls.dispatch_index = step, dispatch_idx
                try:
                    out = np.asarray(self.executor(use_plan, za, zb))
                finally:
                    self._tls.step = self._tls.dispatch_index = None
            except Exception as e:  # noqa: BLE001 — any dispatch failure
                resolved += self._on_dispatch_failure(
                    bucket, use_plan, probing, reqs, e
                )
                if step is not None:
                    step.finish("failed", error=type(e).__name__,
                                phase=step.failed_child or step.name)
                return resolved
            # Compilation happened before t0, so this is service time.
            exec_s = time.perf_counter() - t0
            if step is None:
                resolved += self._on_dispatch_success(
                    bucket, probing, reqs, out, pad, dispatch_idx, exec_s
                )
            else:
                with step.child("engine.resolve"):
                    resolved += self._on_dispatch_success(
                        bucket, probing, reqs, out, pad, dispatch_idx, exec_s
                    )
            return resolved

    def _pack(self, cfg: api.PlanConfig, reqs) -> tuple:
        """The batch's operands on the host: the requests' rows, padded
        with zero polynomials to ``batch_slots`` (device widths)."""
        if cfg.width == "oracle":
            return (np.stack([r.za for r in reqs]),
                    np.stack([r.zb for r in reqs]))
        za = np.zeros((self.batch_slots, cfg.n, cfg.seg_count), np.int64)
        zb = np.zeros_like(za)
        for i, r in enumerate(reqs):
            za[i] = r.za
            zb[i] = r.zb
        return za, zb

    def _on_dispatch_success(self, bucket, probing, reqs, out, pad,
                             dispatch_idx, exec_s) -> int:
        now = time.perf_counter()
        for i, r in enumerate(reqs):
            if r.span is not None:
                r.span.finish(
                    "resolved", latency_s=now - r.t_submit,
                    dispatch_index=dispatch_idx,
                )
            r.future._resolve(out[i], now - r.t_submit,
                              dispatch_index=dispatch_idx)
        with self._cond:
            self._inflight -= len(reqs)
            bucket.failures = 0
            bucket.not_before = 0.0
            if probing and bucket.level > 0:
                bucket.level = 0  # probe succeeded: breaker closes
                self._m["breaker_recovered"].inc()
                if self.span_log is not None:
                    self.span_log.event(
                        "breaker_recovered", engine=self.name,
                        bucket=_bucket_key_str(bucket.key),
                    )
            bucket.ewma_service_s = (
                exec_s if bucket.ewma_service_s == 0.0
                else 0.75 * bucket.ewma_service_s + 0.25 * exec_s
            )
            self._m["dispatches"].inc()
            self._m_width_dispatches.labels(
                engine=self.name, width=bucket.key.width
            ).inc()
            self._m["served"].inc(len(reqs))
            self._m["padded_slots"].inc(pad)
            for r in reqs:
                self._latencies.append(now - r.t_submit)
                self._h_latency.observe(now - r.t_submit)
            self._cond.notify_all()
        return len(reqs)

    def _on_dispatch_failure(self, bucket, use_plan, probing, reqs,
                             exc) -> int:
        """Fail or requeue exactly the popped requests — never lose
        them.  Non-probe failures advance the bucket's backoff and (at
        the threshold) its circuit breaker; probe failures just restart
        the cool-down without burning request retry budget."""
        now = time.perf_counter()
        failed: list[_Request] = []
        with self._cond:
            self._inflight -= len(reqs)
            self._m["dispatch_failures"].inc()
            for r in reqs:
                if not probing:
                    r.attempts += 1
                if r.attempts > self.max_retries:
                    failed.append(r)
                else:
                    bucket.push(r)
                    self._m["retried"].inc()
                    if r.span is not None:
                        r.span.event(
                            "retry", attempt=r.attempts,
                            error=type(exc).__name__, probing=probing,
                        )
            self._m["failed"].inc(len(failed))
            if probing:
                bucket.opened_at = now  # stay degraded, restart cooldown
            else:
                bucket.failures += 1
                if (bucket.failures >= self.breaker_threshold
                        and self._degrade_locked(bucket, now)):
                    pass  # breaker opened: retry immediately on fallback
                else:
                    bucket.not_before = now + min(
                        self.backoff_base_s * 2 ** (bucket.failures - 1),
                        1.0,
                    )
            self._cond.notify_all()
        backend = api.plan_key(use_plan).backend
        for r in failed:
            if r.span is not None:
                r.span.finish(
                    "failed", backend=backend, attempts=r.attempts,
                    error=type(exc).__name__, latency_s=now - r.t_submit,
                )
            err = BackendFailedError(
                f"request seq {r.seq} failed after {r.attempts} dispatch "
                f"attempts (last backend {backend!r}): {exc}",
                request_seq=r.seq, backend=backend, attempts=r.attempts,
            )
            err.__cause__ = exc
            r.future._fail(err, latency_s=now - r.t_submit)
        return len(failed)

    def _on_compile_failure(self, use_plan, reqs, exc) -> int:
        """Fail exactly the popped requests with the typed compile error:
        no retry, no backoff and no breaker, since recompiling the same
        program fails the same way."""
        now = time.perf_counter()
        with self._cond:
            self._inflight -= len(reqs)
            self._m["failed"].inc(len(reqs))
            self._cond.notify_all()
        backend = api.plan_key(use_plan).backend
        for r in reqs:
            if r.span is not None:
                r.span.finish(
                    "failed", backend=backend, attempts=r.attempts,
                    error=type(exc).__name__, latency_s=now - r.t_submit,
                )
            err = CompileFailedError(
                f"request seq {r.seq}: {exc}", request_seq=r.seq,
                backend=backend, attempts=r.attempts,
            )
            err.__cause__ = exc.__cause__
            r.future._fail(err, latency_s=now - r.t_submit)
        return len(reqs)

    def _degrade_locked(self, bucket: _Bucket, now: float) -> bool:
        """Open the bucket's breaker: activate (building if needed) the
        next plan down the fallback chain.  False when exhausted."""
        if bucket.level + 1 >= len(bucket.chain):
            nxt = self._fallback_plan(bucket.chain[bucket.level])
            if nxt is None:
                return False
            bucket.chain.append(nxt)
        bucket.level += 1
        bucket.failures = 0
        bucket.opened_at = now
        bucket.not_before = 0.0
        self._m["breaker_opened"].inc()
        if self.span_log is not None:
            self.span_log.event(
                "breaker_open", engine=self.name,
                bucket=_bucket_key_str(bucket.key),
                level=bucket.level,
                backend=api.plan_key(bucket.active_plan).backend,
            )
        return True

    def run_until_idle(self) -> int:
        """Drain every bucket.  Synchronous mode: drives :meth:`step`
        and returns the number of requests resolved.  With the
        background dispatcher running: blocks until the queue and
        in-flight work drain (returns 0; see ``stats``)."""
        if self._threads:
            with self._cond:
                while self._has_work_locked():
                    if self._loop_error is not None:
                        raise RuntimeError(
                            "engine dispatcher thread died"
                        ) from self._loop_error
                    self._cond.wait(0.01)
            return 0
        total = 0
        while True:
            total += self.step()
            with self._cond:
                if not self._has_work_locked():
                    return total

    def serve(self, requests) -> list[np.ndarray]:
        """Convenience closed loop: submit ``(plan, za, zb)`` triples,
        drain, return results in submission order."""
        futs = [self.submit(pl, za, zb) for pl, za, zb in requests]
        self.run_until_idle()
        return [f.result() for f in futs]

    # -- async front end -----------------------------------------------
    def start(self) -> "PolymulEngine":
        """Launch the background dispatcher (idempotent): two worker
        threads, each driving :meth:`step`, so that one batch's host
        work runs while the other batch is on the device.  A worker
        starts a dispatch beside the other's only on a full, healthy
        bucket (:meth:`_select_locked`); otherwise it waits.  While
        running, submissions are served without the caller driving
        ``step()``, and new futures block in ``result()``."""
        if self._threads:
            return self
        self._stop_evt.clear()
        self._loop_error = None
        self._threads = [
            threading.Thread(
                target=self._dispatch_loop,
                name=f"polymul-engine-dispatch-{i}", daemon=True,
            )
            for i in range(self._WORKERS)
        ]
        for t in self._threads:
            t.start()
        return self

    @property
    def running(self) -> bool:
        return bool(self._threads)

    def _dispatch_loop(self) -> None:
        try:
            while not self._stop_evt.is_set():
                if self.step():
                    continue
                with self._cond:  # idle or held: wait for a change
                    pick = self._select_locked(time.perf_counter())
                    if (not self._stop_evt.is_set()
                            and (pick is None or pick[0] == "hold")):
                        self._cond.wait(0.02)
        except BaseException as e:  # surfaced by run_until_idle/stop
            with self._cond:
                if self._loop_error is None:
                    self._loop_error = e
                self._cond.notify_all()
            raise

    def stop(self, *, drain: bool = True,
             timeout: float | None = None) -> None:
        """Stop the dispatcher's workers, joining both (within
        ``timeout`` seconds in all); with ``drain`` (default) first
        wait until every queued request has resolved."""
        if not self._threads:
            return
        if drain:
            self.run_until_idle()
        self._stop_evt.set()
        with self._cond:
            self._cond.notify_all()
        t_end = None if timeout is None else time.perf_counter() + timeout
        for t in self._threads:
            t.join(None if t_end is None
                   else max(t_end - time.perf_counter(), 0.0))
        self._threads = []
        if self._loop_error is not None:
            err, self._loop_error = self._loop_error, None
            raise RuntimeError("engine dispatcher thread died") from err

    def __enter__(self) -> "PolymulEngine":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.stop(drain=exc_type is None)
        return False

    # -- probes --------------------------------------------------------
    @property
    def stats(self) -> dict:
        """Live counter view: ``{stat: int}`` read from this engine's
        children in the metrics registry (see ``_STAT_KEYS`` for the
        vocabulary).  A fresh plain dict per access — exporters and the
        registry itself hold the canonical series."""
        return {k: int(c.value) for k, c in self._m.items()}

    def snapshot(self) -> dict:
        """Point-in-time stats: the counters plus queue depth, in-flight
        count, p50/p99 submit-to-result latency (ms, over the last
        ``latency_window`` served requests) and per-bucket active
        backends — what the soak driver and CLIs gate on/report.

        The snapshot is a FROZEN wire contract: its key set is exactly
        ``SNAPSHOT_KEYS`` at ``schema_version`` =
        ``SNAPSHOT_SCHEMA_VERSION`` (each key documented there), pinned
        by a regression test so dashboards can't silently break.  It is
        an exporter view over the metrics registry — the same numbers
        are scrapeable via :func:`repro.obs.to_prometheus`."""
        with self._cond:
            snap = dict(self.stats)
            snap["schema_version"] = SNAPSHOT_SCHEMA_VERSION
            snap["engine"] = self.name
            snap["queue_depth"] = self._pending_locked()
            snap["inflight"] = self._inflight
            self._g_queue_depth.set(snap["queue_depth"])
            self._g_inflight.set(snap["inflight"])
            if self._latencies:
                lat = np.asarray(self._latencies) * 1e3
                snap["latency_p50_ms"] = float(np.percentile(lat, 50))
                snap["latency_p99_ms"] = float(np.percentile(lat, 99))
            else:
                snap["latency_p50_ms"] = None
                snap["latency_p99_ms"] = None
            snap["degraded_buckets"] = sum(
                1 for b in self._buckets.values() if b.level > 0
            )
            snap["bucket_backends"] = {
                _bucket_key_str(c): api.plan_key(b.active_plan).backend
                for c, b in self._buckets.items()
            }
        return snap

    def reset_stats(self) -> None:
        """Zero every counter/histogram series of THIS engine and drop
        the latency window (benchmark warm-up hygiene)."""
        with self._cond:
            for c in self._m.values():
                c.reset()
            self._m_packed_readbacks.reset()
            self._m_overlapped.reset()
            for (engine, _), child in self._m_width_dispatches.children():
                if engine == self.name:
                    child.reset()
            self._h_latency.reset()
            self._h_queue_wait.reset()
            self._latencies.clear()

    @property
    def trace_count(self) -> int:
        """Compilations of the engine executor so far; equals the
        number of distinct PlanConfigs dispatched (the bucket contract —
        breaker degradation adds one per newly-activated fallback)."""
        return len(self._trace_log)

    @property
    def traced_configs(self) -> tuple:
        return tuple(self._trace_log)
