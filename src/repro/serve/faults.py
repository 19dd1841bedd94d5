"""Deterministic, seedable fault injection for the serving engine.

The robustness claims of :class:`repro.serve.crypto_engine.PolymulEngine`
— exactly-once resolution, bounded retries, circuit breaking onto a
bit-exact fallback backend — are only worth stating if they hold under
*actual* faults.  This module supplies them on a reproducible schedule:
a :class:`FaultInjector` wraps ``engine.executor`` (the single funnel
every dispatch attempt passes through) and can **raise** a transient
error, **delay** the dispatch, or **corrupt** the returned limbs,
according to a list of :class:`FaultRule` triggers driven by one seeded
``numpy`` generator.

Design points:

* **Deterministic.**  All randomness comes from ``seed``, one draw per
  (rule, call) in the order calls enter the injector.  Installed on an
  engine, the injector indexes each call by the engine's
  ``dispatch_index`` for it (read from the calling thread), the index
  the engine stamps on every future the call resolves; wrapped around
  a bare function, it counts calls itself.  So the injector's ``log``
  can be joined against resolved futures after the fact, also when the
  engine's two dispatcher workers enter calls out of index order.  That
  join is how the soak driver *detects* injected corruption rather than
  merely surviving it.
* **Raise beats corrupt.**  When several rules match one call, the
  first matching ``raise`` rule wins; otherwise every matching
  ``delay`` sleeps and every matching ``corrupt`` XORs the output.
* **Corruption is engine-invisible.**  A corrupt rule flips the low bit
  of the result limbs *after* the wrapped executor returns — the engine
  serves it as a success.  Catching it is the oracle spot-check's job
  (:func:`spot_check`), mirroring how a real silent-data-corruption
  event would have to be caught downstream.

Usage::

    inj = FaultInjector([
        FaultRule("raise", backend="pallas_fused_e2e", max_count=3),
        FaultRule("delay", rate=0.05, delay_s=0.01),
        FaultRule("corrupt", rate=0.01),
    ], seed=7)
    inj.install(eng)          # wraps eng.executor
    ... drive traffic ...
    corrupted = {i for i, kind, _ in inj.log if kind == "corrupt"}
    # futures with fut.dispatch_index in `corrupted` carry flipped limbs
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from repro import api
from repro.obs import metrics as obs_metrics

__all__ = ["FaultInjector", "FaultRule", "InjectedFault", "spot_check"]


class InjectedFault(RuntimeError):
    """The transient error a ``raise`` rule throws inside the executor.

    Deliberately a plain ``RuntimeError`` subclass — the engine must
    treat it like any unexpected dispatch failure; nothing in the
    retry/breaker path is allowed to special-case it."""

    def __init__(self, message: str, *, dispatch_index: int = -1) -> None:
        super().__init__(message)
        self.dispatch_index = dispatch_index


@dataclasses.dataclass
class FaultRule:
    """One trigger in an injection schedule.

    Parameters
    ----------
    kind:
        ``"raise"`` (throw :class:`InjectedFault`), ``"delay"`` (sleep
        ``delay_s`` before executing) or ``"corrupt"`` (XOR 1 into the
        returned limbs — silent data corruption).
    rate:
        Bernoulli firing probability per *eligible* call (default 1.0:
        fire on every eligible call).
    backend:
        Only fire on dispatches whose plan uses this backend (``None``:
        any).  This is how a soak pins faults to one chain level, e.g.
        "the fused-e2e kernel is broken, its fallbacks are fine".
    after / until:
        Eligible window in executor-call indices: ``after <= idx`` and
        (when ``until`` is set) ``idx < until``.
    max_count:
        Stop firing after this many hits (``None``: unbounded).
    at:
        Explicit call indices that *force* the rule to fire (still
        subject to ``backend``) regardless of ``rate`` — pins the
        schedule's must-happen events, e.g. "call 17 is corrupted".
    delay_s:
        Sleep length for ``delay`` rules.
    """

    kind: str
    rate: float = 1.0
    backend: str | None = None
    after: int = 0
    until: int | None = None
    max_count: int | None = None
    at: tuple = ()
    delay_s: float = 0.02
    fired: int = 0  # hits so far (mutated by the injector)

    def __post_init__(self):
        if self.kind not in ("raise", "delay", "corrupt"):
            raise ValueError(
                f"FaultRule kind must be raise/delay/corrupt, "
                f"got {self.kind!r}"
            )
        if not 0.0 <= self.rate <= 1.0:
            raise ValueError(f"rate must be in [0, 1], got {self.rate}")


class FaultInjector:
    """Wraps an engine executor with a seeded fault schedule.

    Attributes
    ----------
    calls:
        Executor calls seen so far.  A call's index is the engine's
        ``dispatch_index`` of it when installed on an engine, else the
        count of calls before it.
    log:
        ``(call_index, kind, backend)`` for every fault fired — the
        ground truth the soak driver joins against resolved futures.
    """

    def __init__(self, rules, *, seed: int = 0):
        self.rules = list(rules)
        self.rng = np.random.default_rng(seed)
        self.calls = 0
        self.log: list[tuple[int, str, str]] = []
        self._tls = None  # the engine's thread-local, once installed
        self._lock = threading.Lock()  # calls may come from two threads
        self._counter = obs_metrics.registry().counter(
            "repro_faults_injected_total",
            "faults fired by the injection schedule",
            ("kind", "backend"),
        )

    def _matches(self, rule: FaultRule, idx: int, backend: str) -> bool:
        if rule.backend is not None and backend != rule.backend:
            return False
        if rule.max_count is not None and rule.fired >= rule.max_count:
            return False
        if idx in rule.at:
            return True
        if idx < rule.after:
            return False
        if rule.until is not None and idx >= rule.until:
            return False
        # One rng draw per (rule, call) in rule order: the schedule is a
        # pure function of (rules, seed, call sequence).
        return bool(self.rng.random() < rule.rate)

    def _fire(self, rule: FaultRule, idx: int, backend: str) -> None:
        rule.fired += 1
        self.log.append((idx, rule.kind, backend))
        self._counter.labels(kind=rule.kind, backend=backend).inc()

    def wrap(self, fn):
        """The wrapped executor: ``fn`` with faults injected per the
        schedule.  Pure pass-through once every rule is exhausted."""

        def _injected(pl, za, zb):
            backend = api.plan_key(pl).backend
            with self._lock:
                idx = getattr(self._tls, "dispatch_index", None)
                if idx is None:
                    idx = self.calls
                self.calls += 1
                hits = [
                    r for r in self.rules if self._matches(r, idx, backend)
                ]
                delays = [r for r in hits if r.kind == "delay"]
                for r in delays:
                    self._fire(r, idx, backend)
                raised = next((r for r in hits if r.kind == "raise"), None)
                if raised is not None:
                    self._fire(raised, idx, backend)
            for r in delays:
                time.sleep(r.delay_s)
            if raised is not None:
                raise InjectedFault(
                    f"injected transient fault at dispatch {idx} "
                    f"(backend {backend!r})",
                    dispatch_index=idx,
                )
            out = fn(pl, za, zb)
            for r in hits:
                if r.kind == "corrupt":
                    with self._lock:
                        self._fire(r, idx, backend)
                    out = np.asarray(out) ^ 1  # silent low-bit flip
            return out

        return _injected

    def install(self, engine) -> "FaultInjector":
        """Wrap ``engine.executor`` in place.  Each call is then indexed
        by the engine's ``dispatch_index`` for it, which the engine
        keeps on the calling thread's ``_tls`` while the call runs."""
        self._tls = engine._tls
        engine.executor = self.wrap(engine.executor)
        return self

    def indices(self, kind: str) -> set:
        """Call indices at which faults of ``kind`` fired."""
        return {i for i, k, _ in self.log if k == kind}

    def quiesce(self, kind: str | None = None) -> None:
        """Exhaust matching rules (all of them when ``kind`` is None):
        each rule's ``max_count`` is pinned to its fired count, so it
        never fires again.  The soak driver calls this before its
        recovery phase so breaker probes deterministically succeed."""
        for r in self.rules:
            if kind is None or r.kind == kind:
                r.max_count = r.fired


def spot_check(pl, za, zb, limbs, *, use_oracle: bool = False) -> bool:
    """Does a served result match ground truth?  ``za``/``zb``: the
    request's ``(n, S)`` segments; ``limbs``: the future's ``(n, L)``
    result.  Recomputes through :func:`api.polymul` on the request's
    *original* plan (bit-exact across the degradation chain), or — with
    ``use_oracle`` — through the host bigint schoolbook oracle,
    independent of every device datapath.  This is the detection arm of
    the fault harness: a ``corrupt`` rule's flipped limbs make it
    return ``False``."""
    if use_oracle:
        from repro.core import bigint
        from repro.core import polymul as core_polymul

        cfg = api.plan_key(pl)
        a_ints = bigint.limbs_to_ints(np.asarray(za), cfg.v)
        b_ints = bigint.limbs_to_ints(np.asarray(zb), cfg.v)
        ref_ints = core_polymul.oracle_multiply(a_ints, b_ints, pl.params)
        return api.from_limbs(pl, limbs) == ref_ints
    ref = np.asarray(api.polymul(pl, np.asarray(za)[None],
                                 np.asarray(zb)[None]))[0]
    return bool(np.array_equal(np.asarray(limbs), ref))
