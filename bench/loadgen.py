"""The one load generator.  A traffic file (``traffic/<name>.json``)
holds only parameters; this module reads them and drives a submit
function for a fixed window on the host clock.

* ``{"kind": "closed", "callers": C}``: C callers, each submitting its
  next product when its last one returns.  One thread plays all of them:
  it waits on the oldest outstanding request and then resubmits, which
  is exact because the engine resolves one bucket's requests in order.
  A request's latency runs from its submission.
* ``{"kind": "open", "rate_per_s": R, "burst": K}``: queries arrive as a
  Poisson process of R / K queries per second, each bringing K products
  at once.  The gaps are the exponential distribution's quantiles, the
  same set for every seed, shuffled by the seed, so every run offers
  exactly R * seconds products.  A request's latency runs from the
  moment it was due, so a late generator counts against the system;
  how late the generator ran is returned beside the latencies.

Every request due (open) or submitted (closed) inside the window is
counted and waited for, up to ``wait_s`` past the window's close.
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Callable

import numpy as np

KINDS = ("closed", "open")


@dataclasses.dataclass(slots=True)
class Request:
    k: int  # index of the product: picks its operands
    t_due: float
    t_sub: float
    t_done: float | None = None  # None: failed or never resolved
    error: str | None = None


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    requests: list[Request]

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def completed_in_window(self) -> int:
        return sum(1 for r in self.requests
                   if r.t_done is not None and r.t_done <= self.t_end)

    def latencies_ms(self) -> np.ndarray:
        return np.array([(r.t_done - r.t_due) * 1e3 for r in self.requests
                         if r.t_done is not None])

    def lateness_ms(self) -> np.ndarray:
        return np.array([(r.t_sub - r.t_due) * 1e3 for r in self.requests])


def validate(traffic: dict) -> None:
    kind = traffic.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of {KINDS}")
    if kind == "closed" and int(traffic["callers"]) < 1:
        raise ValueError("a closed loop needs at least one caller")
    if kind == "open" and not (float(traffic["rate_per_s"]) > 0
                               and int(traffic.get("burst", 1)) >= 1):
        raise ValueError("an open loop needs rate_per_s > 0 and burst >= 1")


def arrivals(rate_per_s: float, burst: int, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Offsets in [0, seconds) of the queries of an open loop."""
    count = max(1, round(rate_per_s * seconds / burst))
    u = (np.arange(count) + 0.5) / count
    gaps = rng.permutation(-np.log1p(-u))
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return starts * (seconds / gaps.sum())


def drive(traffic: dict, submit: Callable, seconds: float,
          rng: np.random.Generator, on_done: Callable,
          *, wait_s: float = 60.0,
          annotate: Callable[[], contextlib.AbstractContextManager] | None = None
          ) -> Window:
    """Run one window.  ``submit(k)`` submits product ``k`` and returns
    its future; ``on_done(request, value)`` sees each counted request
    that completed.  ``annotate()``, where given, wraps each submit."""
    validate(traffic)
    wrap = annotate or contextlib.nullcontext

    def _submit(k):
        with wrap():
            return submit(k)

    def _wait(req, fut, deadline):
        try:
            value = fut.result(timeout=max(deadline - time.perf_counter(), 0.0))
        except Exception as e:  # noqa: BLE001 - every failure is recorded
            req.error = f"{type(e).__name__}: {e}"
            return
        req.t_done = time.perf_counter()
        on_done(req, value)

    if traffic["kind"] == "closed":
        return _closed(int(traffic["callers"]), seconds, wait_s, _submit, _wait)
    offsets = arrivals(float(traffic["rate_per_s"]), int(traffic.get("burst", 1)),
                       seconds, rng)
    return _open(offsets, int(traffic.get("burst", 1)), seconds, wait_s,
                 _submit, _wait)


def _closed(callers, seconds, wait_s, submit, wait) -> Window:
    reqs: list[Request] = []
    out: deque = deque()

    def send():
        t = time.perf_counter()
        req = Request(k=len(reqs), t_due=t, t_sub=t)
        reqs.append(req)
        out.append((req, submit(req.k)))

    t0 = time.perf_counter()
    t_end = t0 + seconds
    for _ in range(callers):
        send()
    while out:
        req, fut = out.popleft()
        wait(req, fut, t_end + wait_s)
        if time.perf_counter() < t_end:
            send()
    return Window(t0, t_end, reqs)


def _open(offsets, burst, seconds, wait_s, submit, wait) -> Window:
    reqs: list[Request] = []
    handoff: queue.SimpleQueue = queue.SimpleQueue()
    t0 = time.perf_counter() + 0.01
    t_end = t0 + seconds
    errors: list[BaseException] = []

    def generate():
        try:
            for off in offsets:
                due = t0 + off
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                for _ in range(burst):
                    req = Request(k=len(reqs), t_due=due, t_sub=time.perf_counter())
                    reqs.append(req)
                    handoff.put((req, submit(req.k)))
        except BaseException as e:  # noqa: BLE001 - re-raised by the caller
            errors.append(e)
        finally:
            handoff.put(None)

    gen = threading.Thread(target=generate, name="bench-open-loop", daemon=True)
    gen.start()
    while (item := handoff.get()) is not None:
        wait(*item, t_end + wait_s)
    gen.join()
    if errors:
        raise errors[0]
    return Window(t0, t_end, reqs)
