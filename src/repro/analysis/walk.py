"""Generic recursive jaxpr traversal, shared by the verifier passes and
the structural counters in :mod:`repro.kernels.ops`.

Dependency-free within the repo (jax-version tolerant, attribute-
probing) so both the kernels layer and the analysis passes can import
it without cycles.
"""
from __future__ import annotations

from typing import Any, Iterator, Optional, Tuple


def subjaxprs(eqn: Any) -> Iterator[Tuple[str, Any]]:
    """Yield ``(label, jaxpr-like)`` for every sub-jaxpr of one eqn:
    jit/closed-call bodies, cond branches, pallas kernel bodies.  The
    yielded object may be a ClosedJaxpr or a raw Jaxpr."""
    params = getattr(eqn, "params", None) or {}
    for key in ("jaxpr", "call_jaxpr"):
        inner = params.get(key)
        if inner is not None and hasattr(getattr(inner, "jaxpr", inner), "eqns"):
            yield key, inner
            break
    for i, br in enumerate(params.get("branches", ()) or ()):
        if hasattr(getattr(br, "jaxpr", br), "eqns"):
            yield f"branch{i}", br


def raw(jaxpr_like: Any) -> Any:
    """Unwrap a ClosedJaxpr to its raw Jaxpr (identity for raw Jaxprs)."""
    return getattr(jaxpr_like, "jaxpr", jaxpr_like)


def iter_eqns(
    jaxpr_like: Any, path: Tuple[str, ...] = ()
) -> Iterator[Tuple[Tuple[str, ...], Any]]:
    """Depth-first ``(path, eqn)`` over a jaxpr and every nested body."""
    for eqn in raw(jaxpr_like).eqns:
        yield path, eqn
        for label, inner in subjaxprs(eqn):
            name = getattr(eqn.primitive, "name", "?")
            yield from iter_eqns(inner, path + (f"{name}:{label}",))


def iter_consts(
    jaxpr_like: Any, path: Tuple[str, ...] = ()
) -> Iterator[Tuple[Tuple[str, ...], Any]]:
    """Depth-first ``(path, const)`` over the closure constants of a
    ClosedJaxpr and of every nested ClosedJaxpr (jit bodies, cond
    branches); pallas bodies are raw Jaxprs and carry no consts."""
    for const in getattr(jaxpr_like, "consts", ()) or ():
        yield path, const
    for eqn in raw(jaxpr_like).eqns:
        for label, inner in subjaxprs(eqn):
            name = getattr(eqn.primitive, "name", "?")
            yield from iter_consts(inner, path + (f"{name}:{label}",))


def iter_pallas_calls(
    jaxpr_like: Any, path: Tuple[str, ...] = ()
) -> Iterator[Tuple[Tuple[str, ...], Any]]:
    """Depth-first ``(path, eqn)`` over every ``pallas_call`` eqn."""
    for p, eqn in iter_eqns(jaxpr_like, path):
        if getattr(eqn.primitive, "name", "") == "pallas_call":
            yield p, eqn


def count_prim(jaxpr_like: Any, name: str, *, inside_pallas_only: bool = False) -> int:
    """Count primitive occurrences, optionally only under pallas bodies."""
    total = 0
    for path, eqn in iter_eqns(jaxpr_like):
        if getattr(eqn.primitive, "name", "") != name:
            continue
        if inside_pallas_only and not any(p.startswith("pallas_call:") for p in path):
            continue
        total += 1
    return total
