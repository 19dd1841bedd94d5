"""Reduce a profiler trace (``.xplane.pb``) of a stretch of the window
to the numbers the per-layer readers take.

* Device busy: the union of the intervals of the operations on each
  TPU's ``XLA Ops`` line, over the stretch from its first operation to
  its last, averaged over the devices.
* Scope time: the device time of each operation, counted under the
  ``parentt.<scope>`` name its metadata carries (the innermost of the
  pipeline's stages, else the innermost scope).  A TPU trace names an
  operation by its HLO instruction, so the names come from the compiled
  program's HLO text (:func:`scopes_from_hlo`); a fusion without
  metadata of its own takes its fused computation's.  Where XLA fuses
  across a scope boundary the fused operation goes to the scope its
  metadata names; operations that name none are ``unattributed``.
* Modules: how many times a program ran on the device (``XLA
  Modules``), that is how many dispatches the stretch holds.
* Idle gaps: the stretches between operations in which none ran,
  each named by the host annotation that covers its middle
  (``engine.dispatch`` before ``client.submit``), or ``host.other``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

_SCOPE = re.compile(r"parentt\.(\w+)")
_STAGES = ("decompose", "cascade", "compose", "fused_e2e")
_INSTR = re.compile(r"\s*(ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
ANNOTATIONS = ("engine.dispatch", "client.submit")  # in order of precedence


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    modules: int
    scope_s: dict[str, float]
    ops: list[tuple[str, float]]  # (name, seconds), most first
    gaps: list[tuple[str, float, int]]  # (label, seconds, count), most first

    def unattributed_share(self) -> float:
        total = sum(self.scope_s.values())
        return self.scope_s.get("unattributed", 0.0) / total if total else 0.0

    def breakdown(self) -> dict:
        return {
            "device_ops": [[name, s] for name, s in self.ops[:10]],
            "idle_gaps": [[f"{label} x{count}", s]
                          for label, s, count in self.gaps[:10]],
        }


def _stage(text: str) -> str | None:
    found = _SCOPE.findall(text)
    stages = [f for f in found if f in _STAGES]
    return (stages or found or [None])[-1]


def scopes_from_hlo(text: str) -> dict[str, str]:
    """Instruction name -> scope, from a compiled program's HLO text."""
    scopes: dict[str, str] = {}
    root_scope: dict[str, str] = {}  # computation -> its root's scope
    calls: dict[str, str] = {}  # instruction -> the computation it calls
    comp = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head and "=" not in line.split("{")[0]:
            comp = head.group(2)
            continue
        instr = _INSTR.match(line)
        if not instr:
            continue
        name = instr.group(2)
        op = _OP_NAME.search(line)
        scope = _stage(op.group(1)) if op else None
        if scope:
            scopes[name] = scope
            if instr.group(1) or comp not in root_scope:
                root_scope[comp] = scope
        callee = _CALLS.search(line)
        if callee:
            calls[name] = callee.group(1)
    for name, callee in calls.items():
        if name not in scopes and callee in root_scope:
            scopes[name] = root_scope[callee]
    return scopes


def _scope_of(event, scopes: dict[str, str]) -> str:
    instr = _INSTR.match(event.name + " =")
    if instr and instr.group(2) in scopes:
        return scopes[instr.group(2)]
    names = [v for _, v in event.stats if isinstance(v, str)]
    for text in [event.name, *names]:
        found = _stage(text)
        if found:
            return found
    return "unattributed"


def _union(intervals, lo, hi) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_profile(pd, scopes: dict[str, str] | None = None) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``; ``scopes`` maps HLO
    instruction names to scopes (:func:`scopes_from_hlo`).  The window
    of each device runs from its first operation's start to its last
    one's end, so the profiler's own start and stop stay outside it."""
    scopes = scopes or {}
    planes = list(pd.planes)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError(f"no TPU device plane in the trace: {[p.name for p in planes]}")
    annots = [(ev.start_ns, ev.end_ns, ev.name)
              for p in planes if p.name.startswith("/host:")
              for line in p.lines for ev in line.events if ev.name in ANNOTATIONS]

    window = busy_total = 0.0
    modules = 0
    scope_ns: dict[str, float] = {}
    op_ns: dict[str, float] = {}
    gaps: dict[str, list[float]] = {}
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops = list(lines["XLA Ops"].events) if "XLA Ops" in lines else []
        if "XLA Modules" in lines:
            modules += sum(1 for _ in lines["XLA Modules"].events)
        if not ops:
            continue
        intervals = [(ev.start_ns, ev.end_ns) for ev in ops]
        lo = min(s for s, _ in intervals)
        hi = max(e for _, e in intervals)
        window += hi - lo
        busy = _union(intervals, lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for ev in ops:
            scope = _scope_of(ev, scopes)
            scope_ns[scope] = scope_ns.get(scope, 0.0) + ev.duration_ns
            instr = _INSTR.match(ev.name + " =")
            key = f"{scope}:{instr.group(2) if instr else ev.name}"
            op_ns[key] = op_ns.get(key, 0.0) + ev.duration_ns
        for (_, s), (e, _) in zip(busy, busy[1:]):
            mid = (s + e) / 2
            covering = {name for a, b, name in annots if a <= mid <= b}
            label = next((a for a in ANNOTATIONS if a in covering), "host.other")
            gaps.setdefault(label, []).append(e - s)
    n_dev = len(devices)
    return Reduced(
        window_s=window / n_dev / 1e9,
        busy_s=busy_total / n_dev / 1e9,
        modules=modules,
        scope_s={k: v / 1e9 for k, v in scope_ns.items()},
        ops=sorted(((k, v / 1e9) for k, v in op_ns.items()), key=lambda x: -x[1]),
        gaps=sorted(((k, sum(v) / n_dev / 1e9, len(v)) for k, v in gaps.items()),
                    key=lambda x: -x[1]),
    )


def reduce_dir(outdir: str, scopes: dict[str, str] | None = None) -> Reduced:
    """Reduce the one ``.xplane.pb`` the profiler wrote under ``outdir``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {outdir}, found {found}")
    return reduce_profile(ProfileData.from_file(found[0]), scopes)
