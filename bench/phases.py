"""The engine's dispatch phases, as its span log and a profiler trace
record them when it traces (``--trace 1``): ``engine.step`` for one
dispatch attempt and, inside it, one span per phase (``PHASES``).

* :func:`median_ms` reads a phase's median duration from the span log,
  for the ``engine.<phase>_ms_p50`` readers.
* :func:`idle_by_phase` splits the device's idle time in a profiler
  trace by the phase the dispatcher was in: each stretch between two
  operations on a TPU's ``XLA Ops`` line is cut by the host intervals
  of the ``engine.step`` and phase annotations, and each piece goes to
  the innermost one over it: a phase, else ``engine.step.self`` (the
  step's own time: picking and admitting the batch), else
  ``host.other`` (outside every dispatch).  The stretches are the idle
  gaps of ``trace.reduce_profile``, over the same window, so the pieces
  add up to its ``window_s - busy_s``.
"""
from __future__ import annotations

import bisect

import numpy as np

STEP = "engine.step"
PHASES = ("engine.pack", "engine.upload", "engine.launch", "engine.device_wait",
          "engine.readback", "engine.resolve")  # in dispatch order


def median_ms(records, name: str) -> float | None:
    """Median duration in ms of the window's spans called ``name`` that
    closed ``ok``, or None where the program records none."""
    ms = [(s["t_end"] - s["t_start"]) * 1e3 for s in records["spans"]
          if s["name"] == name and s["status"] == "ok"]
    return float(np.percentile(ms, 50)) if ms else None


def _innermost(intervals) -> tuple[list[float], list[str | None]]:
    """Cut the host timeline at every boundary of ``intervals`` (start,
    end, name), nested as one thread's spans are, and name each piece by
    the innermost interval over it (the one that started last), else
    ``None``.  Returns the cuts, with -inf and +inf at the ends, and the
    names of the pieces between consecutive cuts."""
    cuts = [float("-inf"), *sorted({t for s, e, _ in intervals for t in (s, e)}),
            float("inf")]
    names: list[str | None] = [None] * (len(cuts) - 1)
    for s, e, name in sorted(intervals, key=lambda x: (x[0], -x[1])):  # outer first
        for i in range(bisect.bisect_left(cuts, s), bisect.bisect_left(cuts, e)):
            names[i] = name
    return cuts, names


def idle_by_phase(pd) -> dict[str, float]:
    """Idle seconds of the device by the dispatch phase the host was in,
    averaged over the trace's TPUs (module docstring); empty where the
    trace holds no ``engine.step`` annotation."""
    planes = list(pd.planes)
    marks = [(ev.start_ns, ev.end_ns, ev.name)
             for p in planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events
             if ev.name == STEP or ev.name in PHASES]
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not marks or not devices:
        return {}
    cuts, names = _innermost(marks)
    out: dict[str, float] = {}
    for plane in devices:
        ops = [line for line in plane.lines if line.name == "XLA Ops"]
        busy: list[list[float]] = []
        for s, e in sorted((ev.start_ns, ev.end_ns) for line in ops for ev in line.events):
            if busy and s <= busy[-1][1]:
                busy[-1][1] = max(busy[-1][1], e)
            else:
                busy.append([s, e])
        for (_, t), (e, _) in zip(busy, busy[1:]):
            i = bisect.bisect_right(cuts, t) - 1
            while t < e:
                nxt = min(e, cuts[i + 1])
                name = names[i]
                label = "host.other" if name is None else f"{STEP}.self" if name == STEP else name
                out[label] = out.get(label, 0.0) + (nxt - t) / 1e9 / len(devices)
                t, i = nxt, i + 1
    return out
