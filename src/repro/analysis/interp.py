"""Abstract interpreter over traced crypto jaxprs.

Walks a ``ClosedJaxpr`` (recursing through ``jit``, ``cond`` and
``pallas_call`` bodies) propagating :class:`repro.analysis.domain.AbsVal`
per value, and emits :class:`Finding` records for

* any integer intermediate whose interval cannot be proven to fit its
  lane dtype (``overflow``), or cannot be bounded at all (``unproven``);
* Shoup / Barrett preconditions that fail (``shoup-precondition``,
  ``barrett-precondition``).

Plain interval arithmetic cannot prove ``v*w - ((v*w')>>beta)*q`` lands
in ``[0, 2q)`` — the two products are correlated.  The interpreter
therefore recognizes the Shoup and Barrett reduction *patterns* through
value provenance, checks their preconditions against concretely
verified table tags (see :mod:`repro.analysis.passes`), and applies the
semantic bound.  Conditional subtracts (``jnp.where(x >= m, x - m, x)``)
are handled by branch refinement on ``select_n``, which is what walks
the Barrett output ``[0,4q)`` down to canonical through the repo's
select chains.  Pallas kernel bodies are executed with mutable ref
cells over an enumerated grid (``program_id`` seeded concretely), which
makes the channel-grid accumulator kernels exact.

Unhandled primitives or unproven preconditions degrade to TOP and a
finding — verification *fails closed*, it never silently passes.
"""
from __future__ import annotations

import dataclasses
import math
from fractions import Fraction
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis import domain as D
from repro.analysis.domain import AbsVal, QCtx

Path = Tuple[Any, ...]

_CMP_KINDS = ("ge", "gt", "le", "lt", "eq", "ne")

# Layout/view primitives: bounds pass through unchanged.  Element-
# aligned views (same value in every lane position relative to the
# broadcastable shape) keep the source identity so relational pattern
# matching sees through them; element-*selecting* views (slice/rev/
# transpose pick or reorder elements) must not alias their source.
_ALIGNED_VIEW_PRIMS = frozenset(
    {"broadcast_in_dim", "reshape", "squeeze", "copy", "device_put", "stop_gradient"}
)
_REINDEX_VIEW_PRIMS = frozenset({"slice", "rev", "transpose", "dynamic_slice", "gather"})
_VIEW_PRIMS = _ALIGNED_VIEW_PRIMS | _REINDEX_VIEW_PRIMS

_CALL_PRIMS = frozenset({"jit", "closed_call", "core_call", "custom_jvp_call"})

_CARR_CAP = 65536  # max elements for materialized concrete constant arrays


def _carr_view(prim: str, eqn: Any, arr: np.ndarray) -> Optional[Tuple[Any, ...]]:
    """Re-materialize a concrete constant array through an element-aligned
    view so weighted-reduction bounds stay exact; None when not feasible."""
    try:
        out_shape = tuple(eqn.outvars[0].aval.shape)
        if int(np.prod(out_shape, dtype=np.int64)) > _CARR_CAP:
            return None
        if prim == "broadcast_in_dim":
            dims = tuple(eqn.params.get("broadcast_dimensions", ()) or ())
            shaped = [1] * len(out_shape)
            for i, d in enumerate(dims):
                shaped[d] = arr.shape[i]
            expanded = np.broadcast_to(arr.reshape(tuple(shaped)), out_shape)
            return ("carr", np.ascontiguousarray(expanded))
        if prim in ("reshape", "squeeze"):
            return ("carr", arr.reshape(out_shape))
        return ("carr", arr)
    except Exception:
        return None


# Primitives whose int64 results belong to the mod-q *value stream* that the
# hand-kept ChannelTables envelope bookkeeping tracks in units of q.  The
# multiplier wires inside a Shoup/Barrett reduction (mul, shifts) run to
# ~2^63 by design and are audited in *bits* by the overflow check, not in
# units — counting them here would drown the inter-stage peak.
_STREAM_PRIMS = frozenset({"add", "sub", "select_n", "get", "concatenate", "pad"})


@dataclasses.dataclass
class Finding:
    severity: str  # "error" | "warning" | "info"
    code: str
    where: str
    message: str

    def as_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(eq=False)
class AnalysisContext:
    """Per-trace state shared by the interpreter and the passes."""

    qctx: QCtx
    beta: Optional[int]  # plan's Shoup beta (None => strict, no Shoup expected)
    q_set: frozenset[int]  # verified channel moduli (python ints)
    families: Dict[Tuple[Any, ...], Dict[str, Any]]  # Barrett/SAU family facts
    seed_const: Callable[[Any], AbsVal]  # abstraction for closure constants
    grid_cap: int = 64
    max_findings_per_code: int = 8
    registry: Any = None  # ConstRegistry (set by passes.build_context)
    findings: List[Finding] = dataclasses.field(default_factory=list)
    stream: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    prim_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    bounds_out: Optional[Dict[Path, Tuple[Optional[int], Optional[int]]]] = None
    _suppressed: Dict[str, int] = dataclasses.field(default_factory=dict)
    _seg_peak: int = 1

    def finding(self, severity: str, code: str, where: str, message: str) -> None:
        n = self._suppressed.get(code, 0)
        self._suppressed[code] = n + 1
        if n < self.max_findings_per_code:
            self.findings.append(Finding(severity, code, where, message))
        elif n == self.max_findings_per_code:
            self.findings.append(
                Finding(severity, code, where, f"(further '{code}' findings suppressed)")
            )

    @property
    def ok(self) -> bool:
        return not any(f.severity == "error" for f in self.findings)

    def note_units(self, u: int) -> None:
        if u > self._seg_peak:
            self._seg_peak = u

    def shoup_event(self, units_in: int, gs: bool) -> None:
        self.stream.append(
            {"units_in": units_in, "gs": gs, "peak_before": self._seg_peak}
        )
        self._seg_peak = 2  # the Shoup output itself: < 2q

    @property
    def tail_peak(self) -> int:
        return self._seg_peak


class Cell:
    """Mutable abstract state of one pallas ref."""

    __slots__ = ("val",)

    def __init__(self, val: Optional[AbsVal]) -> None:
        self.val = val


class RefVal:
    """Environment placeholder for a Ref-typed jaxpr var."""

    __slots__ = ("cell",)

    def __init__(self, cell: Cell) -> None:
        self.cell = cell


def _same(a: AbsVal, b: AbsVal) -> bool:
    if a is b or a.uid == b.uid:
        return True
    pa, pb = a.prov, b.prov
    return (
        pa is not None
        and pb is not None
        and pa[0] == "lit"
        and pb[0] == "lit"
        and pa[1] == pb[1]
    )


def _aff(av: AbsVal) -> Tuple[AbsVal, int, int]:
    """Affine view c*base with c in [c_lo, c_hi]; identity by default."""
    if av.aff is not None:
        return av.aff
    return (av, 1, 1)


def _apply_aff(out: AbsVal, base: AbsVal, c_lo: int, c_hi: int) -> AbsVal:
    """Intersect ``out`` with the interval of c*base and record the form."""
    if base.lo is not None and base.hi is not None:
        prods = [c_lo * base.lo, c_lo * base.hi, c_hi * base.lo, c_hi * base.hi]
        lo, hi = min(prods), max(prods)
        out.lo = lo if out.lo is None else max(out.lo, lo)
        out.hi = hi if out.hi is None else min(out.hi, hi)
    out.aff = (base, c_lo, c_hi)
    return out


def _aval_dtype(aval: Any) -> Optional[np.dtype]:
    dt = getattr(aval, "dtype", None)
    if dt is None:
        inner = getattr(aval, "inner_aval", None)
        dt = getattr(inner, "dtype", None)
    return np.dtype(dt) if dt is not None else None


def _aval_shape(aval: Any) -> Tuple[int, ...]:
    shp = getattr(aval, "shape", None)
    if shp is None:
        inner = getattr(aval, "inner_aval", None)
        shp = getattr(inner, "shape", ())
    return tuple(int(s) for s in (shp or ()))


def _is_ref(var: Any) -> bool:
    aval = getattr(var, "aval", None)
    return aval is not None and (
        hasattr(aval, "inner_aval") or type(aval).__name__ in ("AbstractRef", "MemRef")
    )


class _Interp:
    def __init__(self, ctx: AnalysisContext) -> None:
        self.ctx = ctx
        self._pid: Dict[int, AbsVal] = {}
        self._lit_cache: Dict[int, AbsVal] = {}
        # First-seen broadcast_dimensions per (src uid, output shape): a
        # second broadcast of the same source to the same shape along
        # *different* dims is not element-aligned with the first, so it
        # must not alias it.
        self._bcast: Dict[Tuple[int, Tuple[int, ...]], Tuple[int, ...]] = {}

    # ---------------------------------------------------------- plumbing

    def _lit(self, val: Any) -> AbsVal:
        try:
            arr = np.asarray(val)
            if arr.dtype == np.bool_:
                v = int(arr.reshape(-1)[0]) if arr.size == 1 else None
                return D.const(v) if v is not None else D.boolean()
            if np.issubdtype(arr.dtype, np.integer):
                if arr.size == 1:
                    v = int(arr.reshape(-1)[0])
                    av = D.const(v)
                    if v in self.ctx.q_set:
                        # A scalar literal equal to a registered modulus is
                        # per-channel code operating on its own channel's
                        # lanes (decompose slices each residue channel and
                        # bakes that channel's q_i as a literal), so for the
                        # elements it meets, q_elem == v exactly.  Seed it
                        # with the same q-linear forms the registered q
                        # arrays carry so select-chain refinement can recover
                        # canonical (1, -1) bounds.  The Shoup checker makes
                        # the identical assumption when it accepts literal q.
                        av = av.with_qlin(
                            Fraction(1), Fraction(0), self.ctx.qctx
                        ).with_qlo(Fraction(1), Fraction(0), self.ctx.qctx)
                        av.tag = ("q",)
                    elif 2 * v - 1 in self.ctx.q_set:
                        # (q+1)//2 baked as a literal: the div-by-2 constant
                        # of that channel (the wide digit-split path bakes
                        # both q and half as scalars instead of table leaves).
                        av = av.with_qlin(
                            Fraction(1, 2), Fraction(1, 2), self.ctx.qctx
                        ).with_qlo(Fraction(1, 2), Fraction(1, 2), self.ctx.qctx)
                        av.tag = ("half",)
                    return av
                av = D.from_ints(int(arr.min()), int(arr.max()))
                if arr.size <= _CARR_CAP:
                    av.prov = ("carr", np.asarray(arr))
                return av
        except (TypeError, ValueError):
            pass
        return D.top()

    def _read(self, env: Dict[Any, Any], atom: Any) -> Any:
        if hasattr(atom, "val"):  # jax.core.Literal (Vars carry no .val)
            key = id(atom)
            if key not in self._lit_cache:
                self._lit_cache[key] = self._lit(atom.val)
            return self._lit_cache[key]
        got = env.get(atom)
        if got is None:
            got = D.top()
            env[atom] = got
        return got

    def _check(self, var: Any, av: Any, prim: str, where: Path, out_idx: int) -> None:
        if isinstance(av, RefVal):
            return
        dt = _aval_dtype(getattr(var, "aval", None))
        if dt is None or not np.issubdtype(dt, np.integer):
            return
        if self.ctx.bounds_out is not None:
            self.ctx.bounds_out[where + (out_idx,)] = (av.lo, av.hi)
        info = np.iinfo(dt)
        loc = "/".join(str(w) for w in where) + f" [{prim}]"
        if av.lo is None or av.hi is None:
            self.ctx.finding(
                "error", "unproven", loc, f"{dt} intermediate has unbounded interval"
            )
        elif av.lo < info.min or av.hi > info.max:
            self.ctx.finding(
                "error",
                "overflow",
                loc,
                f"{dt} intermediate in [{av.lo}, {av.hi}] exceeds "
                f"[{info.min}, {info.max}]",
            )
        if (
            av.qa is not None
            and np.dtype(dt) == np.int64
            and av.lo is not None
            and av.lo >= 0
            and prim in _STREAM_PRIMS
        ):
            u = D.units_of_q(av, self.ctx.qctx)
            if u is not None:
                self.ctx.note_units(u)

    # ---------------------------------------------------------- main loop

    def run(
        self,
        jaxpr: Any,
        consts: Sequence[Any],
        args: Sequence[Any],
        where: Path,
    ) -> List[Any]:
        env: Dict[Any, Any] = {}
        for var, av in zip(jaxpr.constvars, consts):
            env[var] = av
        for var, av in zip(jaxpr.invars, args):
            env[var] = av
        for idx, eqn in enumerate(jaxpr.eqns):
            prim = eqn.primitive.name
            self.ctx.prim_counts[prim] = self.ctx.prim_counts.get(prim, 0) + 1
            ins = [self._read(env, x) for x in eqn.invars]
            outs = self._apply(prim, eqn, ins, where + (idx,))
            for oi, (var, av) in enumerate(zip(eqn.outvars, outs)):
                env[var] = av
                self._check(var, av, prim, where + (idx,), oi)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _apply(self, prim: str, eqn: Any, ins: List[Any], where: Path) -> List[Any]:
        ctx = self.ctx
        qctx = ctx.qctx
        if prim in _VIEW_PRIMS:
            src = ins[0]
            if not isinstance(src, AbsVal):
                return [src]
            if prim in _REINDEX_VIEW_PRIMS:
                out = src.view(fresh=True)
                if out.prov is not None and out.prov[0] == "carr":
                    # The concrete array no longer matches the reindexed
                    # layout; drop it rather than mis-align weighted sums.
                    out.prov = None
                return [out]
            fresh = False
            if prim == "broadcast_in_dim":
                params = getattr(eqn, "params", None) or {}
                shape = tuple(params.get("shape", ()) or ())
                dims = tuple(params.get("broadcast_dimensions", ()) or ())
                prior = self._bcast.setdefault((src.uid, shape), dims)
                fresh = prior != dims
            out = src.view(fresh=fresh)
            if out.prov is not None and out.prov[0] == "carr":
                out.prov = _carr_view(prim, eqn, out.prov[1])
            return [out]
        if prim == "convert_element_type":
            dt = _aval_dtype(eqn.outvars[0].aval)
            src = ins[0]
            if dt is not None and dt == np.bool_:
                out = D.boolean()
                out.prov = src.prov
                return [out]
            return [src.view()]
        if prim == "add":
            out = D.add(ins[0], ins[1], qctx)
            d2 = self._try_div2(ins[0], ins[1], out)
            if d2 is not None:
                return [d2]
            ba, ca_lo, ca_hi = _aff(ins[0])
            bb, cb_lo, cb_hi = _aff(ins[1])
            if ba.uid == bb.uid:
                out = _apply_aff(out, ba, ca_lo + cb_lo, ca_hi + cb_hi)
            return [out]
        if prim == "sub":
            pat = self._try_shoup(ins[0], ins[1], where)
            if pat is None:
                pat = self._try_barrett(ins[0], ins[1], where)
            if pat is None:
                pat = self._try_sau_sub(ins[0], ins[1])
            if pat is not None:
                pat.prov = ("sub", ins[0], ins[1])
                return [pat]
            out = D.sub(ins[0], ins[1], qctx)
            ba, ca_lo, ca_hi = _aff(ins[0])
            bb, cb_lo, cb_hi = _aff(ins[1])
            if ba.uid == bb.uid:
                out = _apply_aff(out, ba, ca_lo - cb_hi, ca_hi - cb_lo)
            return [out]
        if prim == "mul":
            out = D.mul(ins[0], ins[1], qctx)
            for x, y in ((ins[0], ins[1]), (ins[1], ins[0])):
                if y.is_singleton() and y.lo is not None:
                    bx, cx_lo, cx_hi = _aff(x)
                    cs = sorted((cx_lo * y.lo, cx_hi * y.lo))
                    out = _apply_aff(out, bx, cs[0], cs[1])
                    break
            return [out]
        if prim == "neg":
            out = D.neg(ins[0])
            b, c_lo, c_hi = _aff(ins[0])
            return [_apply_aff(out, b, -c_hi, -c_lo)]
        if prim == "shift_left":
            out = D.shift_left(ins[0], ins[1], qctx)
            if ins[1].is_singleton() and ins[1].lo is not None:
                b, c_lo, c_hi = _aff(ins[0])
                sh = 1 << ins[1].lo
                out = _apply_aff(out, b, c_lo * sh, c_hi * sh)
            return [out]
        if prim in ("shift_right_arithmetic", "shift_right_logical"):
            if prim == "shift_right_logical" and (ins[0].lo is None or ins[0].lo < 0):
                return [D.top()]
            return [D.shift_right(ins[0], ins[1], qctx)]
        if prim == "and":
            return [D.bit_and(ins[0], ins[1])]
        if prim in ("or", "xor"):
            return [D.bit_or(ins[0], ins[1])]
        if prim == "not":
            out = D.boolean()
            out.prov = ("not", ins[0])
            return [out]
        if prim == "rem":
            return [D.rem(ins[0], ins[1], qctx)]
        if prim in _CMP_KINDS:
            return [D.compare(prim, ins[0], ins[1])]
        if prim == "select_n":
            return [self._select_n(ins)]
        if prim == "min":
            lo = None if ins[0].lo is None or ins[1].lo is None else min(ins[0].lo, ins[1].lo)
            his = [h for h in (ins[0].hi, ins[1].hi) if h is not None]
            return [AbsVal(lo, min(his) if his else None)]
        if prim == "max":
            los = [l for l in (ins[0].lo, ins[1].lo) if l is not None]
            hi = None if ins[0].hi is None or ins[1].hi is None else max(ins[0].hi, ins[1].hi)
            return [AbsVal(max(los) if los else None, hi)]
        if prim == "reduce_sum":
            return [self._reduce_sum(eqn, ins)]
        if prim in ("reduce_max", "reduce_min", "reduce_and", "reduce_or"):
            return [ins[0].view()]
        if prim == "pad":
            return [D.join(ins[0], ins[1], self.ctx.qctx)]
        if prim == "concatenate":
            out = ins[0]
            for other in ins[1:]:
                out = D.join(out, other, self.ctx.qctx)
            return [out]
        if prim == "iota":
            shape = _aval_shape(eqn.outvars[0].aval)
            dim = int(eqn.params.get("dimension", 0))
            size = shape[dim] if shape else 1
            return [D.from_ints(0, max(0, size - 1))]
        if prim in _CALL_PRIMS:
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if inner is None:
                return self._unknown(prim, eqn, where)
            inner_jaxpr = getattr(inner, "jaxpr", inner)
            inner_consts = [ctx.seed_const(c) for c in getattr(inner, "consts", [])]
            name = eqn.params.get("name", prim)
            return self.run(inner_jaxpr, inner_consts, ins, where + (name,))
        if prim == "pallas_call":
            return self._pallas_call(eqn, ins, where)
        if prim == "cond":
            return self._cond(eqn, ins, where)
        if prim == "program_id":
            axis = int(eqn.params.get("axis", 0))
            return [self._pid.get(axis, D.top())]
        if prim == "get":
            ref = ins[0]
            if isinstance(ref, RefVal):
                if ref.cell.val is None:
                    ctx.finding(
                        "error",
                        "uninitialized-ref",
                        "/".join(str(w) for w in where),
                        "read of ref before any write",
                    )
                    return [D.top()]
                return [ref.cell.val.view()]
            return self._unknown(prim, eqn, where)
        if prim == "swap":
            ref = ins[0]
            if isinstance(ref, RefVal):
                new = next((x for x in ins[1:] if isinstance(x, AbsVal)), D.top())
                old = ref.cell.val
                full = len(ins) == 2
                if full or old is None:
                    ref.cell.val = new
                else:
                    ref.cell.val = D.join(old, new, self.ctx.qctx)
                return [old if old is not None else new.view()]
            return self._unknown(prim, eqn, where)
        return self._unknown(prim, eqn, where)

    def _unknown(self, prim: str, eqn: Any, where: Path) -> List[Any]:
        self.ctx.finding(
            "error",
            "unproven-prim",
            "/".join(str(w) for w in where),
            f"no abstract transfer for primitive '{prim}'",
        )
        return [D.top() for _ in eqn.outvars]

    # ---------------------------------------------------------- select_n

    def _select_n(self, ins: List[AbsVal]) -> AbsVal:
        pred, *cases = ins
        feas = list(range(len(cases)))
        if pred.lo is not None and pred.hi is not None:
            feas = [i for i in feas if pred.lo <= i <= pred.hi]
            if not feas:  # infeasible pred abstraction; stay sound
                feas = list(range(len(cases)))
        refined = [self._refine(pred, i, cases[i]) for i in feas]
        out = refined[0]
        for other in refined[1:]:
            out = D.join(out, other, self.ctx.qctx)
        out.prov = ("select_n", pred, *cases)
        return out

    def _refine(self, pred: AbsVal, idx: int, case: AbsVal) -> AbsVal:
        prov = pred.prov
        if prov is None or prov[0] not in ("ge", "gt", "le", "lt") or idx > 1:
            return case
        kind, u, w = prov[0], prov[1], prov[2]
        truth = idx == 1
        # Normalize to u >= w + d ("ge") or u <= w - d ("le").
        rel: Tuple[str, int]
        if kind == "ge":
            rel = ("ge", 0) if truth else ("le", 1)
        elif kind == "gt":
            rel = ("ge", 1) if truth else ("le", 0)
        elif kind == "le":
            rel = ("le", 0) if truth else ("ge", 1)
        else:  # lt
            rel = ("le", 1) if truth else ("ge", 0)
        qctx = self.ctx.qctx
        if _same(case, u):
            if rel[0] == "ge" and w.lo is not None:
                return D.clamp_min(case, w.lo + rel[1], qctx)
            if rel[0] == "le":
                out = case
                if w.hi is not None:
                    out = D.clamp_max(out, w.hi - rel[1], qctx)
                if w.qa is not None and w.qb is not None:
                    out = out.with_qlin(w.qa, w.qb - rel[1], qctx)
                    out.prov = case.prov
                return out
            return case
        cp = case.prov
        if (
            cp is not None
            and cp[0] == "sub"
            and _same(cp[1], u)
            and _same(cp[2], w)
        ):
            if rel[0] == "ge":  # case = u - w >= d
                return D.clamp_min(case, rel[1], qctx)
            return D.clamp_max(case, -rel[1], qctx)  # u - w <= -d
        if cp is not None and cp[0] == "add":
            # case = u + m under a relation on u (e.g. sub_mod's
            # ``where(d < 0, d + q, d)``: d <= -1  =>  d + q <= q - 1).
            for uu, m in ((cp[1], cp[2]), (cp[2], cp[1])):
                if not _same(uu, u):
                    continue
                out = case
                if rel[0] == "le" and w.hi is not None:
                    if m.hi is not None:
                        out = D.clamp_max(out, w.hi - rel[1] + m.hi, qctx)
                    if m.qa is not None and m.qb is not None:
                        out = out.with_qlin(m.qa, m.qb + w.hi - rel[1], qctx)
                elif rel[0] == "ge" and w.lo is not None and m.lo is not None:
                    out = D.clamp_min(out, w.lo + rel[1] + m.lo, qctx)
                return out
        return case

    # ---------------------------------------------------------- patterns

    def _try_div2(self, a: AbsVal, b: AbsVal, out: AbsVal) -> Optional[AbsVal]:
        """``div2_mod``: ``(x >> 1) + (x & 1) * half`` — exact halving mod
        q.  Summing the two halves independently loses the parity
        correlation (even uses only the shift, odd adds ``(q+1)/2`` to
        ``(x-1)/2``), which inflates the bound to ``x/2 + q/2 + 1/2`` and
        compounds across inverse-NTT stages.  The odd branch dominates:
        ``out <= (x + q)/2 <= ((qa+1)/2)*q + qb/2``."""
        qctx = self.ctx.qctx
        for sh, prod in ((a, b), (b, a)):
            ps, pp = sh.prov, prod.prov
            if not (ps and ps[0] == "shift_right" and pp and pp[0] == "mul"):
                continue
            x, s = ps[1], ps[2]
            if not (s.is_singleton() and s.lo == 1):
                continue
            for par, h in ((pp[1], pp[2]), (pp[2], pp[1])):
                if not (h.tag and h.tag[0] == "half"):
                    continue
                pq = par.prov
                if not (pq and pq[0] == "and"):
                    continue
                for x2, one in ((pq[1], pq[2]), (pq[2], pq[1])):
                    if not (one.is_singleton() and one.lo == 1 and _same(x2, x)):
                        continue
                    if x.lo is None or x.lo < 0 or x.qa is None or x.qb is None:
                        continue
                    res = out.with_qlin((x.qa + 1) / 2, x.qb / 2, qctx)
                    res = res.with_qlo(Fraction(0), Fraction(0), qctx)
                    res.prov = ("add", a, b)
                    return res
        return None

    def _try_shoup(self, a: AbsVal, b: AbsVal, where: Path) -> Optional[AbsVal]:
        pa, pb = a.prov, b.prov
        if not (pa and pa[0] == "mul" and pb and pb[0] == "mul"):
            return None
        for v, w in ((pa[1], pa[2]), (pa[2], pa[1])):
            for k, qv in ((pb[1], pb[2]), (pb[2], pb[1])):
                pk = k.prov
                if not (pk and pk[0] == "shift_right"):
                    continue
                p, beta = pk[1], pk[2]
                if not beta.is_singleton() or beta.lo is None:
                    continue
                pp = p.prov
                if not (pp and pp[0] == "mul"):
                    continue
                for v2, wsh in ((pp[1], pp[2]), (pp[2], pp[1])):
                    if not _same(v2, v):
                        continue
                    out = self._shoup_checked(v, w, wsh, qv, beta.lo, where)
                    if out is not None:
                        return out
        return None

    def _shoup_checked(
        self, v: AbsVal, w: AbsVal, wsh: AbsVal, qv: AbsVal, beta: int, where: Path
    ) -> Optional[AbsVal]:
        ctx = self.ctx
        if not (
            w.tag
            and w.tag[0] == "twiddle"
            and wsh.tag
            and wsh.tag[0] == "shoup"
            and w.tag[1:] == wsh.tag[1:]
        ):
            return None
        q_hi: Optional[int] = None
        if qv.tag and qv.tag[0] == "q":
            q_hi = ctx.qctx.q_max
        elif qv.prov and qv.prov[0] == "lit" and int(qv.prov[1]) in ctx.q_set:
            q_hi = int(qv.prov[1])
        if q_hi is None or ctx.beta is None or beta != ctx.beta:
            return None
        loc = "/".join(str(x) for x in where)
        if v.lo is None or v.lo < 0 or v.hi is None or v.hi > (1 << beta):
            ctx.finding(
                "error",
                "shoup-precondition",
                loc,
                f"Shoup multiplicand in [{v.lo}, {v.hi}] not within [0, 2^{beta}]",
            )
            return None
        out = AbsVal(0, 2 * q_hi - 1).with_qlin(Fraction(2), Fraction(-1), ctx.qctx)
        units = D.units_of_q(v, ctx.qctx) or 0
        gs = bool(v.prov and v.prov[0] == "select_n")
        ctx.shoup_event(units, gs)
        return out

    def _try_barrett(self, a: AbsVal, b: AbsVal, where: Path) -> Optional[AbsVal]:
        pb = b.prov
        if not (pb and pb[0] == "mul"):
            return None
        for khat, qv in ((pb[1], pb[2]), (pb[2], pb[1])):
            pk = khat.prov
            if not (pk and pk[0] == "shift_right"):
                continue
            m, s2 = pk[1], pk[2]
            pm = m.prov
            if not (pm and pm[0] == "mul"):
                continue
            for x2, eps in ((pm[1], pm[2]), (pm[2], pm[1])):
                px = x2.prov
                if not (px and px[0] == "shift_right"):
                    continue
                x3, s1 = px[1], px[2]
                if not _same(x3, a):
                    continue
                out = self._barrett_checked(a, eps, qv, s1, s2, where)
                if out is not None:
                    return out
        return None

    def _barrett_checked(
        self,
        x: AbsVal,
        eps: AbsVal,
        qv: AbsVal,
        s1: AbsVal,
        s2: AbsVal,
        where: Path,
    ) -> Optional[AbsVal]:
        ctx = self.ctx
        if not s1.is_singleton() or s1.lo is None:
            return None
        s1v = s1.lo
        q_hi: Optional[int] = None
        if qv.tag and qv.tag[0] == "q":
            q_hi = ctx.qctx.q_max
        elif qv.prov and qv.prov[0] == "lit":
            q_hi = int(qv.prov[1])
        if q_hi is None:
            return None
        c_min: Optional[int] = None
        if eps.tag and eps.tag[0] == "brt":
            fam = ctx.families.get(eps.tag)
            if fam is None or fam["s1"] != s1v:
                return None
            if s2.is_singleton() and s2.lo is not None:
                if not (fam["s2_lo"] <= s2.lo <= fam["s2_hi"]):
                    return None
                c_min = s1v + s2.lo
            elif s2.tag == ("brt_s2",) + eps.tag[1:]:
                c_min = s1v + fam["s2_lo"]
            else:
                return None
        elif (
            eps.prov
            and eps.prov[0] == "lit"
            and qv.prov
            and qv.prov[0] == "lit"
            and s2.is_singleton()
            and s2.lo is not None
        ):
            c = s1v + s2.lo
            if int(eps.prov[1]) != (1 << c) // int(qv.prov[1]):
                return None
            c_min = c
        else:
            return None
        loc = "/".join(str(w) for w in where)
        if x.lo is None or x.lo < 0 or x.hi is None or x.hi >= (1 << c_min):
            ctx.finding(
                "error",
                "barrett-precondition",
                loc,
                f"Barrett input in [{x.lo}, {x.hi}] not within [0, 2^{c_min})",
            )
            return None
        return AbsVal(0, 4 * q_hi - 1).with_qlin(Fraction(4), Fraction(-1), ctx.qctx)

    def _try_sau_sub(self, a: AbsVal, b: AbsVal) -> Optional[AbsVal]:
        """``sau_sum - x`` where sau_sum = c*x with family-verified c."""
        pa = a.prov
        if not (pa and pa[0] == "sau" and _same(pa[1], b)):
            return None
        fam = self.ctx.families.get(pa[2])
        if fam is None or b.lo is None or b.lo < 0 or b.hi is None:
            return None
        return AbsVal((fam["c_lo"] - 1) * b.lo, (fam["c_hi"] - 1) * b.hi)

    def _reduce_sum(self, eqn: Any, ins: List[AbsVal]) -> AbsVal:
        a = ins[0]
        shape = _aval_shape(eqn.invars[0].aval)
        axes = eqn.params.get("axes", ())
        count = 1
        for ax in axes:
            count *= shape[ax] if ax < len(shape) else 1
        pa = a.prov
        if pa and pa[0] == "mul":
            # Weighted digit recompose: sum_k x_k * w_k with w a concrete
            # constant array (powers of the limb base).  Per-output bound is
            # x.hi times the exact per-output weight sum, not count * max.
            for s, t in ((pa[1], pa[2]), (pa[2], pa[1])):
                pt = t.prov
                if (
                    pt
                    and pt[0] == "carr"
                    and isinstance(s, AbsVal)
                    and s.lo is not None
                    and s.lo >= 0
                    and s.hi is not None
                ):
                    try:
                        arr = np.broadcast_to(pt[1], tuple(shape))
                    except ValueError:
                        continue
                    if int(arr.min()) >= 0:
                        wsum = arr.astype(object).sum(axis=tuple(axes))
                        wmax = int(np.max(wsum)) if getattr(wsum, "ndim", 0) else int(wsum)
                        wmin = int(np.min(wsum)) if getattr(wsum, "ndim", 0) else int(wsum)
                        return AbsVal(s.lo * wmin, s.hi * wmax)
            for s, t in ((pa[1], pa[2]), (pa[2], pa[1])):
                pt = t.prov
                if (
                    s.tag
                    and s.tag[0] == "sau_s"
                    and pt
                    and pt[0] == "shift_left"
                    and isinstance(pt[1], AbsVal)
                ):
                    xbase, e = pt[1], pt[2]
                    if e.tag == ("sau_e",) + s.tag[1:]:
                        key = ("sau",) + s.tag[1:]
                        fam = self.ctx.families.get(key)
                        if (
                            fam is not None
                            and xbase.lo is not None
                            and xbase.lo >= 0
                            and xbase.hi is not None
                        ):
                            return AbsVal(
                                fam["c_lo"] * xbase.lo,
                                fam["c_hi"] * xbase.hi,
                                prov=("sau", xbase, key),
                            )
        return D.reduce_sum(a, max(count, 1))

    # ---------------------------------------------------------- control

    def _cond(self, eqn: Any, ins: List[Any], where: Path) -> List[Any]:
        index, *ops = ins
        branches = eqn.params["branches"]
        if isinstance(index, AbsVal) and index.is_singleton() and index.lo is not None:
            k = min(max(index.lo, 0), len(branches) - 1)
            br = branches[k]
            consts = [self.ctx.seed_const(c) for c in getattr(br, "consts", [])]
            return self.run(getattr(br, "jaxpr", br), consts, ops, where + (f"br{k}",))
        # Unknown predicate: run every branch on copies, join states/outputs.
        cells = [op.cell for op in ops if isinstance(op, RefVal)]
        saved = [c.val for c in cells]
        all_outs: List[List[Any]] = []
        all_states: List[List[Optional[AbsVal]]] = []
        for k, br in enumerate(branches):
            for c, v in zip(cells, saved):
                c.val = v
            consts = [self.ctx.seed_const(c) for c in getattr(br, "consts", [])]
            outs = self.run(getattr(br, "jaxpr", br), consts, ops, where + (f"br{k}",))
            all_outs.append(outs)
            all_states.append([c.val for c in cells])
        for i, c in enumerate(cells):
            vals = [st[i] for st in all_states if st[i] is not None]
            if len(vals) < len(all_states):
                c.val = None if not vals else vals[0]
            else:
                acc = vals[0]
                for v in vals[1:]:
                    acc = D.join(acc, v, self.ctx.qctx)
                c.val = acc
        joined: List[Any] = []
        for outs in zip(*all_outs):
            acc = outs[0]
            for other in outs[1:]:
                if isinstance(acc, AbsVal) and isinstance(other, AbsVal):
                    acc = D.join(acc, other, self.ctx.qctx)
            joined.append(acc)
        return joined or [D.top() for _ in eqn.outvars]

    def _pallas_call(self, eqn: Any, ins: List[Any], where: Path) -> List[Any]:
        ctx = self.ctx
        params = eqn.params
        body = params.get("jaxpr")
        if body is None:
            return self._unknown("pallas_call", eqn, where)
        body_jaxpr = getattr(body, "jaxpr", body)
        gm = params.get("grid_mapping")
        grid = tuple(int(g) for g in (getattr(gm, "grid", None) or ()))
        n_out = getattr(gm, "num_outputs", None)
        if n_out is None:
            n_out = len(eqn.outvars)
        n_in = getattr(gm, "num_inputs", None)
        if n_in is None:
            n_in = len(body_jaxpr.invars) - n_out
        in_seeds = [x for x in ins if isinstance(x, AbsVal)][:n_in]
        if len(in_seeds) < n_in:
            in_seeds += [D.top()] * (n_in - len(in_seeds))
        in_cells = [Cell(None) for _ in range(n_in)]
        out_cells = [Cell(None) for _ in range(n_out)]
        body_args: List[Any] = [RefVal(c) for c in in_cells] + [
            RefVal(c) for c in out_cells
        ]
        extra = len(body_jaxpr.invars) - len(body_args)
        if extra > 0:
            body_args += [D.top()] * extra
        body_consts = [ctx.seed_const(c) for c in getattr(body, "consts", [])]
        total = 1
        for g in grid:
            total *= g
        steps: List[Optional[Tuple[int, ...]]]
        if grid and total <= ctx.grid_cap:
            steps = [tuple(ix) for ix in np.ndindex(*grid)]
        else:
            steps = [None]
            if grid:
                ctx.finding(
                    "warning",
                    "grid-not-enumerated",
                    "/".join(str(w) for w in where),
                    f"grid {grid} exceeds enumeration cap {ctx.grid_cap}; "
                    "ref state joined across steps",
                )
        saved_pid = self._pid
        for step in steps:
            if step is None:
                self._pid = {
                    ax: D.from_ints(0, max(0, g - 1)) for ax, g in enumerate(grid)
                }
            else:
                self._pid = {ax: D.const(v) for ax, v in enumerate(step)}
            for cell, seed in zip(in_cells, in_seeds):
                cell.val = seed.view()
            self.run(body_jaxpr, body_consts, body_args, where + ("kernel",))
        self._pid = saved_pid
        outs: List[Any] = []
        for i, cell in enumerate(out_cells):
            if cell.val is None:
                ctx.finding(
                    "error",
                    "unproven",
                    "/".join(str(w) for w in where),
                    f"pallas output {i} never written",
                )
                outs.append(D.top())
            else:
                outs.append(cell.val.view())
        return outs[: len(eqn.outvars)]


def analyze_closed_jaxpr(
    closed: Any, args: Sequence[AbsVal], ctx: AnalysisContext, where: str = "trace"
) -> List[Any]:
    """Run the abstract interpreter over a ClosedJaxpr; findings and the
    Shoup-event stream accumulate on ``ctx``; returns output AbsVals."""
    interp = _Interp(ctx)
    consts = [ctx.seed_const(c) for c in closed.consts]
    return interp.run(closed.jaxpr, consts, list(args), (where,))
