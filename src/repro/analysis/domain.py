"""Interval + q-linear abstract domain for the kernel verifier.

Every traced value is abstracted by an :class:`AbsVal`:

* exact python-int absolute bounds ``[lo, hi]`` (``None`` = unbounded in
  that direction — arbitrary precision, so 2**63 boundaries are exact);
* an optional elementwise **q-linear** upper bound ``x <= qa*q + qb``
  where ``q`` is the element's *own* RNS channel modulus.  Plain
  intervals cannot express "below 2q in every channel" once moduli
  differ across the channel axis; the q-linear term is exactly the
  "units of q" currency of the hand-kept window bookkeeping
  (:func:`repro.core.modmath.lazy_stage_bounds`), so the envelope
  comparison is a direct ``<=`` on these coefficients;
* a matching elementwise q-linear **lower** bound ``x >= la*q + lb``.
  Needed for the conditional-add in ``sub_mod``: ``d = x - y`` with
  canonical x, y satisfies ``d >= -(q_elem - 1)`` *per element*, so
  ``d + q_elem >= 1`` — a fact the absolute interval loses the moment
  the channel moduli differ (``lo(d) + q_min`` can be negative);
* a ``tag`` marking verified host constants (twiddle/Shoup/modulus/...)
  that the pattern matchers in :mod:`repro.analysis.interp` require;
* a ``prov`` provenance tuple ``(prim, *operand AbsVals)`` recorded for
  comparison/arithmetic primitives so the Shoup/Barrett patterns and
  the conditional-subtract refinement can be matched *across* jaxpr
  scopes (jnp ``where`` lands inside ``jit("_where")`` sub-jaxprs, so
  def-use matching by eqn within one scope would not see the compare).

Soundness rule for the q-linear term: it survives only channel-
preserving elementwise ops (add/sub by a bounded term, singleton
shifts, refinement).  Multiplying two q-linear values, reducing over an
axis, or mixing channels drops it to the absolute interval.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from fractions import Fraction
from typing import Iterator, Optional, Tuple

_UIDS: Iterator[int] = itertools.count(1)

Tag = Tuple[object, ...]
Prov = Tuple[object, ...]


@dataclasses.dataclass(eq=False)
class QCtx:
    """Channel-modulus context: the range the per-element ``q`` can take."""

    q_min: int
    q_max: int


@dataclasses.dataclass(eq=False)
class AbsVal:
    lo: Optional[int]
    hi: Optional[int]
    qa: Optional[Fraction] = None  # x <= qa*q_elem + qb  (requires qa is not None)
    qb: Optional[Fraction] = None
    tag: Optional[Tag] = None
    prov: Optional[Prov] = None
    # Affine form: value == c * base elementwise, c in [aff[1], aff[2]].
    # Set by the interpreter for shift/mul-by-singleton/add/sub chains so
    # SAU accumulations like ``-x + sum(s_j * (x << e_j))`` keep their
    # exact (nonnegative) coefficient instead of a sign-lost interval.
    aff: Optional[Tuple["AbsVal", int, int]] = None
    la: Optional[Fraction] = None  # x >= la*q_elem + lb (requires la is not None)
    lb: Optional[Fraction] = None
    uid: int = dataclasses.field(default_factory=lambda: next(_UIDS))

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        q = f" <= {self.qa}q{self.qb:+}" if self.qa is not None else ""
        t = f" tag={self.tag}" if self.tag else ""
        return f"AbsVal[{self.lo}, {self.hi}]{q}{t}"

    @property
    def bounded(self) -> bool:
        return self.lo is not None and self.hi is not None

    def is_singleton(self) -> bool:
        return self.lo is not None and self.lo == self.hi

    def with_qlin(self, qa: Fraction, qb: Fraction, qctx: QCtx) -> "AbsVal":
        """Attach/replace the q-linear upper bound, tightening hi with it."""
        qhi = _floor_frac(qa * qctx.q_max + qb) if qa >= 0 else _floor_frac(qa * qctx.q_min + qb)
        hi = qhi if self.hi is None else min(self.hi, qhi)
        return AbsVal(self.lo, hi, qa, qb, self.tag, self.prov, self.aff, self.la, self.lb)

    def with_qlo(self, la: Fraction, lb: Fraction, qctx: QCtx) -> "AbsVal":
        """Attach/replace the q-linear lower bound, tightening lo with it."""
        qlo = _ceil_frac(la * qctx.q_min + lb) if la >= 0 else _ceil_frac(la * qctx.q_max + lb)
        lo = qlo if self.lo is None else max(self.lo, qlo)
        return AbsVal(lo, self.hi, self.qa, self.qb, self.tag, self.prov, self.aff, la, lb)

    def view(self, *, fresh: bool = False) -> "AbsVal":
        """A layout view: same bounds/tag/prov.  Element-aligned views
        (broadcast/reshape/squeeze) keep the identity so relational
        pattern matching (``_same``) sees through them; element-selecting
        views (slice/rev/transpose) pass ``fresh=True``."""
        out = AbsVal(
            self.lo, self.hi, self.qa, self.qb, self.tag, self.prov,
            self.aff, self.la, self.lb,
        )
        if not fresh:
            out.uid = self.uid
        return out


def const(v: int) -> AbsVal:
    return AbsVal(int(v), int(v), prov=("lit", int(v)))


def top() -> AbsVal:
    return AbsVal(None, None)


def boolean() -> AbsVal:
    return AbsVal(0, 1)


def from_ints(lo: int, hi: int) -> AbsVal:
    return AbsVal(int(lo), int(hi))


def _floor_frac(x: Fraction) -> int:
    return math.floor(x)


def _ceil_frac(x: Fraction) -> int:
    return math.ceil(x)


def _add_b(a: Optional[int], b: Optional[int]) -> Optional[int]:
    return None if a is None or b is None else a + b


def _neg_b(a: Optional[int]) -> Optional[int]:
    return None if a is None else -a


def units_of_q(av: AbsVal, qctx: QCtx) -> Optional[int]:
    """The bookkeeping currency: smallest integer ``k`` provable to
    satisfy ``x < k*q_elem`` (ceil of the bound in units of q)."""
    if av.qa is not None and av.qb is not None:
        # x <= qa*q + qb.  If qb <= 0 this is < qa*q (for qa integral it
        # means k = qa); in general k = ceil(qa + qb/q_min) over q range.
        if av.qb <= 0:
            return max(1, _ceil_frac(av.qa))
        return max(1, _ceil_frac(av.qa + Fraction(av.qb) / qctx.q_min))
    if av.hi is not None:
        # x <= hi  =>  x < hi + 1 <= k * q_min with k = ceil((hi+1)/q_min)
        return max(1, -((av.hi + 1) // -qctx.q_min))
    return None


def join(a: AbsVal, b: AbsVal, qctx: Optional[QCtx] = None) -> AbsVal:
    lo = None if a.lo is None or b.lo is None else min(a.lo, b.lo)
    hi = None if a.hi is None or b.hi is None else max(a.hi, b.hi)
    qa = qb = None
    if a.qa is not None and b.qa is not None and a.qb is not None and b.qb is not None:
        qa, qb = max(a.qa, b.qa), max(a.qb, b.qb)
    elif qctx is not None:
        # One-sided q-linear upper survives the join when it dominates the
        # other side's constant bound on every channel (pad with zeros,
        # concatenate with a small literal).  Never widen qb toward the
        # other side's hi: that would loosen the units-of-q accounting.
        for x, y in ((a, b), (b, a)):
            if x.qa is not None and x.qb is not None and y.qa is None and y.hi is not None:
                worst = x.qa * qctx.q_min + x.qb if x.qa >= 0 else x.qa * qctx.q_max + x.qb
                if Fraction(y.hi) <= worst:
                    qa, qb = x.qa, x.qb
                break
    tag = a.tag if a.tag == b.tag else None
    out = AbsVal(lo, hi, qa, qb, tag)
    if a.la is not None and b.la is not None and a.lb is not None and b.lb is not None:
        out.la, out.lb = min(a.la, b.la), min(a.lb, b.lb)
    elif qctx is not None:
        for x, y in ((a, b), (b, a)):
            if x.la is not None and x.lb is not None and y.la is None and y.lo is not None:
                worst = x.la * qctx.q_max + x.lb if x.la >= 0 else x.la * qctx.q_min + x.lb
                if Fraction(y.lo) >= worst:
                    out.la, out.lb = x.la, x.lb
                break
    return out


def _eff_up(x: AbsVal) -> Optional[Tuple[Fraction, Fraction]]:
    """Elementwise q-linear upper form, falling back to the global hi
    (``x <= 0*q + hi`` holds per element too)."""
    if x.qa is not None and x.qb is not None:
        return (x.qa, x.qb)
    if x.hi is not None:
        return (Fraction(0), Fraction(x.hi))
    return None


def _eff_lo(x: AbsVal) -> Optional[Tuple[Fraction, Fraction]]:
    if x.la is not None and x.lb is not None:
        return (x.la, x.lb)
    if x.lo is not None:
        return (Fraction(0), Fraction(x.lo))
    return None


def add(a: AbsVal, b: AbsVal, qctx: QCtx) -> AbsVal:
    out = AbsVal(_add_b(a.lo, b.lo), _add_b(a.hi, b.hi))
    # Materialize a q-linear *upper* form only when one operand carries a
    # genuine one — synthesizing (0, hi) forms here would flood the
    # units-of-q envelope stream with transient wide products.
    if a.qa is not None and b.qa is not None and a.qb is not None and b.qb is not None:
        out = out.with_qlin(a.qa + b.qa, a.qb + b.qb, qctx)
    elif a.qa is not None and a.qb is not None and b.hi is not None:
        out = out.with_qlin(a.qa, a.qb + b.hi, qctx)
    elif b.qa is not None and b.qb is not None and a.hi is not None:
        out = out.with_qlin(b.qa, b.qb + a.hi, qctx)
    # Lower forms never feed the envelope stream: combine freely.
    ea, eb = _eff_lo(a), _eff_lo(b)
    if ea is not None and eb is not None:
        out = out.with_qlo(ea[0] + eb[0], ea[1] + eb[1], qctx)
    out.prov = ("add", a, b)
    return out


def sub(a: AbsVal, b: AbsVal, qctx: QCtx) -> AbsVal:
    out = AbsVal(_add_b(a.lo, _neg_b(b.hi)), _add_b(a.hi, _neg_b(b.lo)))
    # Upper bound of a - b wants b's *lower* bound; prefer its q-linear
    # form (same channel) over the channel-mixing absolute lo.
    if a.qa is not None and a.qb is not None:
        eb_lo = _eff_lo(b)
        if eb_lo is not None:
            out = out.with_qlin(a.qa - eb_lo[0], a.qb - eb_lo[1], qctx)
    ea_lo, eb_up = _eff_lo(a), _eff_up(b)
    if ea_lo is not None and eb_up is not None:
        out = out.with_qlo(ea_lo[0] - eb_up[0], ea_lo[1] - eb_up[1], qctx)
    out.prov = ("sub", a, b)
    return out


def neg(a: AbsVal) -> AbsVal:
    out = AbsVal(_neg_b(a.hi), _neg_b(a.lo), prov=("neg", a))
    if a.la is not None and a.lb is not None:
        out.qa, out.qb = -a.la, -a.lb
    if a.qa is not None and a.qb is not None:
        out.la, out.lb = -a.qa, -a.qb
    return out


def _mul_b(a: Optional[int], b: Optional[int]) -> Optional[int]:
    if a is None or b is None:
        return None  # treated as unbounded by the caller
    return a * b


def mul(a: AbsVal, b: AbsVal, qctx: QCtx) -> AbsVal:
    if a.bounded and b.bounded:
        assert a.lo is not None and a.hi is not None
        assert b.lo is not None and b.hi is not None
        prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        out = AbsVal(min(prods), max(prods))
    else:
        # Unbounded on some side: only the all-nonnegative case keeps a
        # useful lower bound.
        lo = 0 if (a.lo is not None and a.lo >= 0 and b.lo is not None and b.lo >= 0) else None
        out = AbsVal(lo, None)
    # q-linear survives scaling by a *small* exact nonnegative constant
    # (x2 for 2q, small radix factors).  Data-sized factors would
    # manufacture astronomically loose q-linear forms on multiplier wires
    # which pollute the units-of-q envelope stream; those products are
    # bounded by the interval alone and re-derived by the Shoup/Barrett
    # pattern matchers where it matters.
    for x, y in ((a, b), (b, a)):
        if (
            x.qa is not None
            and x.qb is not None
            and x.lo is not None
            and x.lo >= 0
            and y.qa is None
            and y.lo is not None
            and 0 <= y.lo <= 16
            and y.is_singleton()
        ):
            out = out.with_qlin(x.qa * y.lo, x.qb * y.lo, qctx)
            break
    # q-linear *lower* survives scaling by an exact nonnegative constant
    # (2q = mul(q, 2) must keep q-elementwise lower 2*q_elem, or the
    # lazy-restore add (u - t) + 2q picks up cross-channel slack).
    for x, y in ((a, b), (b, a)):
        if (
            x.la is not None
            and x.lb is not None
            and y.la is None
            and y.lo is not None
            and y.lo >= 0
            and y.is_singleton()
        ):
            out = out.with_qlo(x.la * y.lo, x.lb * y.lo, qctx)
            break
    out.prov = ("mul", a, b)
    return out


def shift_left(a: AbsVal, s: AbsVal, qctx: QCtx) -> AbsVal:
    if s.lo is None or s.hi is None or s.lo < 0:
        return top()
    lo = None
    hi = None
    if a.lo is not None:
        lo = a.lo << (s.lo if a.lo >= 0 else s.hi)
    if a.hi is not None:
        hi = a.hi << (s.hi if a.hi >= 0 else s.lo)
    out = AbsVal(lo, hi)
    if (a.qa is not None and a.qb is not None and s.is_singleton()
            and a.lo is not None and a.lo >= 0):
        out = out.with_qlin(a.qa * (1 << s.lo), a.qb * (1 << s.lo), qctx)
    if a.la is not None and a.lb is not None and s.is_singleton():
        out = out.with_qlo(a.la * (1 << s.lo), a.lb * (1 << s.lo), qctx)
    out.prov = ("shift_left", a, s)
    return out


def shift_right(a: AbsVal, s: AbsVal, qctx: QCtx) -> AbsVal:
    if s.lo is None or s.hi is None or s.lo < 0:
        return top()
    lo = None
    hi = None
    if a.lo is not None:
        lo = a.lo >> (s.hi if a.lo >= 0 else s.lo)
    if a.hi is not None:
        hi = a.hi >> (s.lo if a.hi >= 0 else s.hi)
    out = AbsVal(lo, hi)
    if (
        a.qa is not None
        and a.qb is not None
        and s.is_singleton()
        and a.lo is not None
        and a.lo >= 0
        and s.lo is not None
    ):
        out = out.with_qlin(a.qa / (1 << s.lo), a.qb / (1 << s.lo), qctx)
    out.prov = ("shift_right", a, s)
    return out


def bit_and(a: AbsVal, b: AbsVal) -> AbsVal:
    if a.lo is not None and a.lo >= 0 and b.lo is not None and b.lo >= 0:
        his = [h for h in (a.hi, b.hi) if h is not None]
        return AbsVal(0, min(his) if his else None, prov=("and", a, b))
    return top()


def _pow2_ceil(x: int) -> int:
    return 1 << max(x, 1).bit_length()


def bit_or(a: AbsVal, b: AbsVal) -> AbsVal:
    if (
        a.lo is not None
        and a.lo >= 0
        and b.lo is not None
        and b.lo >= 0
        and a.hi is not None
        and b.hi is not None
    ):
        return AbsVal(0, _pow2_ceil(max(a.hi, b.hi)) - 1, prov=("or", a, b))
    return top()


def rem(a: AbsVal, b: AbsVal, qctx: QCtx) -> AbsVal:
    """jnp ``%`` with a positive divisor (sign follows the divisor)."""
    if b.lo is None or b.lo <= 0 or b.hi is None:
        return top()
    out = AbsVal(0 if (a.lo is not None and a.lo >= 0) else -(b.hi - 1), b.hi - 1)
    if b.qa is not None and b.qb is not None and out.lo is not None and out.lo >= 0:
        out = out.with_qlin(b.qa, b.qb - 1, qctx)
    out.prov = ("rem", a, b)
    return out


def reduce_sum(a: AbsVal, count: int) -> AbsVal:
    """Sum of ``count`` elements each in ``a`` (q-linear dropped: the
    reduced axis may mix channels)."""
    lo = None if a.lo is None else a.lo * count
    hi = None if a.hi is None else a.hi * count
    return AbsVal(lo, hi, prov=("reduce_sum", a))


def compare(kind: str, a: AbsVal, b: AbsVal) -> AbsVal:
    """Comparison → boolean abstract value, folded when decidable."""
    out = boolean()
    t: Optional[bool] = None
    if a.bounded and b.bounded:
        assert a.lo is not None and a.hi is not None
        assert b.lo is not None and b.hi is not None
        if kind == "ge":
            t = True if a.lo >= b.hi else (False if a.hi < b.lo else None)
        elif kind == "gt":
            t = True if a.lo > b.hi else (False if a.hi <= b.lo else None)
        elif kind == "le":
            t = True if a.hi <= b.lo else (False if a.lo > b.hi else None)
        elif kind == "lt":
            t = True if a.hi < b.lo else (False if a.lo >= b.hi else None)
        elif kind == "eq":
            if a.is_singleton() and b.is_singleton() and a.lo == b.lo:
                t = True
            elif a.hi < b.lo or a.lo > b.hi:
                t = False
        elif kind == "ne":
            if a.is_singleton() and b.is_singleton() and a.lo == b.lo:
                t = False
            elif a.hi < b.lo or a.lo > b.hi:
                t = True
    if t is True:
        out = const(1)
    elif t is False:
        out = const(0)
    out.prov = (kind, a, b)
    return out


def _dominates_le(qa1: Fraction, qb1: Fraction, qa2: Fraction, qb2: Fraction, qctx: QCtx) -> bool:
    """qa1*q + qb1 <= qa2*q + qb2 for every q in [q_min, q_max]."""
    return (
        qa1 * qctx.q_min + qb1 <= qa2 * qctx.q_min + qb2
        and qa1 * qctx.q_max + qb1 <= qa2 * qctx.q_max + qb2
    )


def clamp_max(a: AbsVal, hi: int, qctx: QCtx) -> AbsVal:
    """``a`` with the *elementwise-proven* upper bound ``hi`` applied
    (callers only pass bounds that hold per element, so the constant
    form may also replace a weaker q-linear upper bound)."""
    out = AbsVal(a.lo, hi if a.hi is None else min(a.hi, hi), a.qa, a.qb, a.tag)
    out.prov, out.la, out.lb = a.prov, a.la, a.lb
    ch = Fraction(hi)
    if (
        out.qa is not None
        and out.qb is not None
        and _dominates_le(Fraction(0), ch, out.qa, out.qb, qctx)
    ):
        out.qa, out.qb = Fraction(0), ch
    return out


def clamp_min(a: AbsVal, lo: int, qctx: Optional[QCtx] = None) -> AbsVal:
    """``a`` with the elementwise-proven lower bound ``lo`` applied."""
    out = AbsVal(lo if a.lo is None else max(a.lo, lo), a.hi, a.qa, a.qb, a.tag)
    out.prov, out.la, out.lb = a.prov, a.la, a.lb
    cl = Fraction(lo)
    if (
        qctx is not None
        and out.la is not None
        and out.lb is not None
        and _dominates_le(out.la, out.lb, Fraction(0), cl, qctx)
    ):
        out.la, out.lb = Fraction(0), cl
    return out
