"""The plan/execute front door: ONE entry point for the paper's whole
feed-forward multiplier, with modulus-width dispatch as an internal
plan-time decision instead of a user-facing class choice.

Usage::

    import repro

    pl = repro.plan(n=4096, t=6, v=30)          # paper's preferred point
    limbs = repro.polymul(pl, za, zb)           # (..., n, S) -> (..., n, L)

    pl45 = repro.plan(n=4096, t=4, v=45)        # wide-word alternative:
    limbs = repro.polymul(pl45, za, zb)         # same signature, same
                                                # base-2^w output limbs

Width dispatch (resolved once, inside :func:`plan`):

* ``v <= 31``  — the int64 datapath (``jnp`` / ``pallas`` /
  ``pallas_fused`` / ``pallas_fused_e2e`` backends, radix-2 or
  lane-aligned four-step schedules; ``"auto"`` is ``jnp``, and on a TPU
  the Pallas backends are refused at plan time because Mosaic cannot
  compile their int64 kernels);
* ``31 < v <= 46`` — the digit-split wide datapath (the paper's t=4 /
  v=45 configuration, :mod:`repro.core.wide`), pure-jnp;
* ``v > 46``   — the host Python-bigint oracle (exact for any width;
  eager-only, cannot be traced).

All three widths share one contract: segments in (``(..., n, S)``
base-``2^v``), product limbs out (``(..., n, L)`` base-``2^w`` with
``w = plan.config.w``), bit-exact against the bigint oracle.

Plan/execute semantics
----------------------
:func:`plan` performs *every* resolution that used to travel as loose
kwargs (``backend``, ``schedule``, ``row_blk``, ``use_sau``) and freezes
the result into a hashable :class:`PlanConfig`.  The returned
:class:`Plan` is a registered JAX pytree:

* **leaves** — the device-resident constants (twiddle/Shoup/SAU/Barrett
  tables, RNS decompose/compose arrays), uploaded once per ``(n, t, v)``
  and shared across plans via the params cache;
* **static aux** — the ``PlanConfig`` plus the host-side parameter
  object (python ints for the kernels' closed-over constants).

So ``jax.jit(polymul)`` treats a plan as an ordinary argument: two plans
with the same config flatten to the same treedef and the jitted function
does **not** retrace; ``jax.vmap``/``shard_map`` thread batch axes of
``za``/``zb`` through with ``in_axes=None`` for the plan (no table
rebuilds, no re-uploads).  Tested by ``tests/test_api.py``.

Leaf use is load-bearing for **both** device widths: the wide width
consumes the leaves directly, and the int64 width executes through
:mod:`repro.kernels.ops` with its table bindings rebuilt from the
Plan's pytree leaves (:func:`_bound_params` — a lightweight view over
``params`` whose device arrays are the plan's leaves, with the channel
count re-derived from the leaf shapes).  So ``jax.tree.map`` /
``device_put`` / sharding of an int64 plan's leaves redirects the
kernels too — the property the serving layer's ``model``-axis
``shard_map`` of :func:`negacyclic_mul` relies on to keep each shard's
NTT/Shoup/CRT tables resident next to its RNS channels
(:mod:`repro.serve.crypto_engine`).  The only constants that stay baked
into kernel closures are the per-channel SAU decompose *circuits*
(python-int shift/add networks — the paper's specialized hardware, not
tables).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bigint
from repro.core import ntt as ntt_mod
from repro.core import polymul as polymul_mod
from repro.core import schedule as schedule_mod
from repro.core import wide as wide_mod
from repro.core.params import (
    BACKENDS,
    SCHEDULES,
    ParenttParams,
    make_params,
    validate_backend,
)
from repro.core.schedule import ScheduleSpec
from repro.errors import PlanError, UnknownKnobError, UnservableConfigError
from repro.kernels import ops as ops_mod

__all__ = [
    "BACKENDS",
    "SCHEDULES",
    "WIDTHS",
    "Plan",
    "PlanConfig",
    "PlanError",
    "ScheduleSpec",
    "UnknownKnobError",
    "UnservableConfigError",
    "plan",
    "plan_from_params",
    "plan_key",
    "execute",
    "polymul",
    "polymul_ints",
    "ntt",
    "intt",
    "negacyclic_mul",
    "decompose",
    "compose",
    "to_segments",
    "from_limbs",
]

# Width paths, in increasing modulus width (see module docstring).
WIDTHS = ("int64", "wide", "oracle")

# The oracle path has no kernel backend; this sentinel is the only value
# PlanConfig.backend takes for width="oracle".
ORACLE_BACKEND = "oracle"

_V_MIN, _V_MAX = 8, 60


def width_for(v: int) -> str:
    """The datapath a modulus width rides: the int64 kernels need
    q_i < 2^31 (residue products fit int64), the digit-split wide path
    serves the 46-bit fold window, bigger moduli fall back to the exact
    host oracle."""
    if v <= 31:
        return "int64"
    if v <= 46:
        return "wide"
    return "oracle"


# --------------------------------------------------------------------------
# PlanConfig: every knob, resolved once, hashable (jit-static-safe)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanConfig:
    """Frozen, fully-resolved execution config — the static aux data of a
    :class:`Plan`.  No ``"auto"`` survives into a PlanConfig: ``backend``
    is a concrete string and ``schedule`` a fully-resolved (hashable)
    :class:`repro.core.schedule.ScheduleSpec` — kind, hierarchical tile
    chain, row block and VMEM accounting are all frozen here, so jit
    keys, :func:`plan_key` buckets and verifier presets see one
    canonical form and executing never re-resolves."""

    n: int
    t: int
    v: int
    width: str  # "int64" | "wide" | "oracle"
    backend: str  # BACKENDS entry, or "oracle" for the oracle width
    schedule: ScheduleSpec  # concrete spec (kind + splits + row_blk)
    row_blk: int | None
    channel_grid: bool | None  # fused-e2e RNS-channel grid axis (None = kernel default)
    use_sau: bool
    # derived I/O format (duplicated from the RnsPlan for self-description)
    seg_count: int  # S: base-2^v segments per input coefficient
    w: int  # output limb width (base 2^w)
    L: int  # output limb count


# --------------------------------------------------------------------------
# Plan: pytree of device constants + static config
# --------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)  # identity eq: leaves are arrays
class Plan:
    """An executable multiplier plan (see module docstring).

    ``consts`` holds the device-resident constant arrays (the pytree
    leaves); ``config`` and ``params`` ride in the static aux data.
    Build with :func:`plan` — the constructor performs no validation.
    """

    config: PlanConfig
    params: ParenttParams
    consts: dict[str, Any]

    # -- convenience ---------------------------------------------------
    @property
    def n(self) -> int:
        return self.config.n

    @property
    def t(self) -> int:
        return self.config.t

    @property
    def v(self) -> int:
        return self.config.v

    @property
    def q(self) -> int:
        return self.params.q

    # -- pytree protocol ----------------------------------------------
    def tree_flatten(self) -> tuple[Any, Any]:
        keys = tuple(sorted(self.consts))
        return tuple(self.consts[k] for k in keys), (self.config, self.params, keys)

    @classmethod
    def tree_unflatten(cls, aux: Any, leaves: Any) -> "Plan":
        config, params, keys = aux
        return cls(config=config, params=params, consts=dict(zip(keys, leaves)))


# --------------------------------------------------------------------------
# leaf-bound execution views: the int64 ops layer reads its device tables
# through these, so the Plan's pytree leaves are the dataflow (DESIGN §7)
# --------------------------------------------------------------------------


class _LeafBound:
    """Attribute view of a host params/tables/plan object with selected
    attributes (the device-resident ``*_d`` arrays, plus the channel
    count ``t``) rebound to a Plan's pytree leaves.

    Everything else — python-int constants, shapes, SAU circuits —
    delegates to the wrapped base object, which stays the stable,
    identity-hashable value for jit-static kernel arguments
    (:func:`repro.kernels.ops.unbind` recovers it).  Under ``shard_map``
    the leaves arrive shard-local, so ``t`` is re-derived from the leaf
    shapes and every kernel runs on exactly its shard's RNS channels.
    """

    __slots__ = ("_base", "_over")

    def __init__(self, base: Any, over: dict[str, Any]) -> None:
        self._base = base
        self._over = over

    def __getattr__(self, name: str) -> Any:  # called only when not found on self
        over = object.__getattribute__(self, "_over")
        if name in over:
            return over[name]
        return getattr(object.__getattribute__(self, "_base"), name)

    def __repr__(self) -> str:
        return f"_LeafBound({self._base!r}, over={sorted(self._over)})"


# ChannelTables / RnsPlan attribute stems whose ``<stem>_d`` device arrays
# live in an int64 plan's leaf dict (as "ntt_<stem>" / "rns_<stem>").
_CT_LEAF_STEMS = (
    "qs", "fwd", "inv", "half", "mul_eps", "fs_row_fwd", "fs_row_inv",
    "fwd_shoup", "inv_shoup", "fs_row_fwd_shoup", "fs_row_inv_shoup",
)
# Tuple-valued stems (one device array per hierarchical sub level); each
# level flattens to its own leaf "ntt_<stem><level>" so the pytree stays
# array-leaved and shard_map/device_put treat every level independently.
_CT_TUPLE_STEMS = (
    "fs_sub_fwd", "fs_sub_inv", "fs_sub_fwd_shoup", "fs_sub_inv_shoup",
)
_RNS_LEAF_STEMS = ("qs", "beta_pows", "qi_tilde", "qi_star_limbs", "q_limbs")


def _bound_params(pl: Plan) -> Any:
    """A ParenttParams view whose NTT/RNS device tables are THIS plan's
    pytree leaves (int64 width; other widths return the params as-is).

    Cached per Plan instance: eager plans are long-lived so their view
    is built once; under jit, ``tree_unflatten`` makes a fresh Plan per
    trace, so tracer-bearing views never outlive their trace.
    """
    if pl.config.width != "int64":
        return pl.params
    cached = pl.__dict__.get("_bound_params_cache")
    if cached is not None:
        return cached
    c = pl.consts
    t_local = int(c["ntt_qs"].shape[0])
    ct_over = {"t": t_local}
    for stem in _CT_LEAF_STEMS:
        leaf = c.get("ntt_" + stem)
        if leaf is not None:
            ct_over[stem + "_d"] = leaf
    for stem in _CT_TUPLE_STEMS:
        levels = []
        while (leaf := c.get(f"ntt_{stem}{len(levels)}")) is not None:
            levels.append(leaf)
        if levels:
            ct_over[stem + "_d"] = tuple(levels)
    rns_over = {"t": t_local}
    for stem in _RNS_LEAF_STEMS:
        rns_over[stem + "_d"] = c["rns_" + stem]
    params = pl.params
    bound = _LeafBound(
        params,
        {
            "t": t_local,
            "tables": _LeafBound(params.tables, ct_over),
            "plan": _LeafBound(params.plan, rns_over),
        },
    )
    object.__setattr__(pl, "_bound_params_cache", bound)
    return bound


# --------------------------------------------------------------------------
# plan(): resolve everything once
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _int64_consts(params: ParenttParams) -> dict[str, Any]:
    """Device constants of the int64 datapath as a named leaf dict.  The
    arrays are the very same device buffers ChannelTables/RnsPlan
    uploaded at construction — building a Plan never re-uploads."""
    ct, rp = params.tables, params.plan
    out = {}
    for name in _CT_LEAF_STEMS:
        dev = getattr(ct, name + "_d")
        if dev is not None:
            out["ntt_" + name] = dev
    for name in _CT_TUPLE_STEMS:
        dev = getattr(ct, name + "_d")
        if dev is not None:
            for lvl, arr in enumerate(dev):
                out[f"ntt_{name}{lvl}"] = arr
    out["rns_qs"] = rp.qs_d
    out["rns_beta_pows"] = rp.beta_pows_d
    out["rns_qi_tilde"] = rp.qi_tilde_d
    out["rns_qi_star_limbs"] = rp.qi_star_limbs_d
    out["rns_q_limbs"] = rp.q_limbs_d
    return out


@functools.lru_cache(maxsize=None)
def _wide_consts(params: ParenttParams) -> dict[str, Any]:
    """Device constants of the digit-split wide datapath: stacked
    per-channel twiddle tables plus POST_W-limb CRT constants, uploaded
    once per params object (cached)."""
    rp = params.plan
    tabs = [ntt_mod.make_tables(int(q), params.n) for q in rp.qs]
    W = wide_mod.POST_W
    L14 = -(-(rp.q.bit_length() + rp.t.bit_length()) // W)
    return {
        "wide_fwd": jnp.asarray(np.stack([tb.fwd for tb in tabs])),
        "wide_inv": jnp.asarray(np.stack([tb.inv for tb in tabs])),
        "wide_beta_pows": rp.beta_pows_d,
        "wide_qi_tilde": rp.qi_tilde_d,
        "wide_qi_star_limbs": jnp.asarray(
            bigint.ints_to_limbs(
                [rp.q // int(qi) for qi in rp.qs], W, L14
            )
        ),
        "wide_q_limbs": jnp.asarray(bigint.int_to_limbs(rp.q, W, L14)),
        # per-channel special-prime constants as (t,)-leading leaves, so a
        # shard_map channel slice carries its own q_i = 2^v - beta_i and
        # shard-local ChannelSpecs can be rebuilt (see _wide_exec_specs)
        "wide_qs": rp.qs_d,
        "wide_betas": jnp.asarray(
            [p.beta for p in params.primes], dtype=jnp.int64
        ),
    }


@functools.lru_cache(maxsize=None)
def _wide_specs(params: ParenttParams) -> tuple[Any, ...]:
    return tuple(wide_mod.from_special(p) for p in params.primes)


def _wide_exec_specs(pl: Plan) -> tuple[Any, ...]:
    """The per-channel specs THIS call should execute.

    Full-width plans (the common case, including jit traces of unsharded
    plans) return the cached host :class:`repro.core.wide.WideSpec`
    tuple, keyed by global channel index.  Under ``shard_map`` the
    plan's leaves arrive as a channel slice, so the host tuple would be
    mis-keyed; the ``wide_qs``/``wide_betas`` leaves travel with the
    slice, and rebuilding :class:`repro.core.wide.ChannelSpec` views
    from them IS the channel-offset view — shard-local index i reads the
    globally-correct q_i/beta_i.
    """
    qs = pl.consts.get("wide_qs")
    t_local = None if qs is None else int(qs.shape[0])
    if t_local is None or t_local == pl.params.t:
        return _wide_specs(pl.params)
    betas = pl.consts["wide_betas"]
    v = pl.config.v
    return tuple(
        wide_mod.ChannelSpec(q=qs[i], v=v, beta=betas[i])
        for i in range(t_local)
    )


def _consts_for(params: ParenttParams, width: str) -> dict[str, Any]:
    if width == "int64":
        return _int64_consts(params)
    if width == "wide":
        return _wide_consts(params)
    return {}  # oracle: host bigints, nothing device-resident


def _resolve_backend(width: str, backend: str) -> str:
    if backend == "auto":
        return ORACLE_BACKEND if width == "oracle" else "jnp"
    if width == "int64":
        return ops_mod.check_servable(validate_backend(backend))
    if width == "wide":
        if backend != "jnp":
            raise UnservableConfigError(
                f"the wide (v in (31, 46]) datapath is pure-jnp: "
                f"backend={backend!r} is not available (use 'auto' or 'jnp')",
                knob="backend", value=backend, alternatives=("auto", "jnp"),
            )
        return backend
    if backend != ORACLE_BACKEND:
        raise UnservableConfigError(
            f"v > 46 is served by the host bigint oracle only: "
            f"backend={backend!r} is not available (use 'auto' or 'oracle')",
            knob="backend", value=backend,
            alternatives=("auto", ORACLE_BACKEND),
        )
    return backend


def _check_wide_envelope(width: str, t: int, v: int) -> None:
    """Wide inverse-CRT envelope: the t-fold sum of y(<2^v) x
    limb(<2^POST_W) contributions must stay inside int64 — reject at
    plan time, never corrupt at execution time."""
    if width == "wide" and t * (1 << (v + wide_mod.POST_W)) > (1 << 63):
        raise UnservableConfigError(
            f"t={t} channels of v={v}-bit moduli overflow the wide "
            f"datapath's int64 inverse-CRT accumulator (need "
            f"t * 2^(v+{wide_mod.POST_W}) <= 2^63); use fewer/narrower "
            f"channels",
            knob="t", value=t, alternatives=(),
        )


def _resolve_spec(
    width: str,
    n: int,
    schedule,
    *,
    tiling=None,
    row_blk: int | None = None,
    params: ParenttParams | None = None,
) -> ScheduleSpec:
    """Resolve the schedule knobs into a concrete :class:`ScheduleSpec`.

    Called twice by :func:`plan`: once with ``params=None`` as the cheap
    pre-params pass (vocabulary + hierarchical-chain servability, so bad
    combos fail before the prime search), and once after ``make_params``
    for the full VMEM-budget row-block resolution (which needs S, L and
    the lazy-reduction flag off the built tables).  The wide and oracle
    widths have no kernel schedule — they serve radix2 with no tile
    accounting (``row_blk=0``)."""
    if width != "int64":
        kind = getattr(schedule, "kind", schedule)
        if kind not in ("auto", "radix2"):
            raise UnservableConfigError(
                f"the {width} datapath serves schedule='radix2' only, "
                f"got {schedule!r}",
                knob="schedule", value=schedule,
                alternatives=("auto", "radix2"),
            )
        if tiling is not None:
            raise UnservableConfigError(
                f"tiling= is a kernel-schedule hint; the {width} datapath "
                f"has no Pallas tile schedule",
                knob="tiling", value=tiling, alternatives=(),
            )
        return ScheduleSpec(kind="radix2")
    if params is None:
        return schedule_mod.concrete_spec(n, schedule)
    ct = params.tables
    return schedule_mod.resolve_spec(
        n, schedule, tiling=tiling, row_blk=row_blk,
        seg_count=params.plan.seg_count, limb_count=params.plan.L,
        lazy=ct is not None and ct.lazy_window is not None,
    )


def _tuning_winner(tuning: Any, n: int, t: int, v: int) -> dict[str, Any] | None:
    """Resolve the ``tuning=`` knob into the table's winner-knob dict for
    this workload (or ``None`` for no tuning / no matching entry).

    ``"off"``/``None`` disables lookup; ``"auto"`` consults the committed
    :data:`repro.tune.table.DEFAULT_TABLE_PATH` and degrades silently to
    the static defaults when the file or entry is missing; an explicit
    path (or a :class:`repro.tune.table.TuningTable`) must exist and
    validate.  Entries are keyed by device kind + ``(n, t, v, batch)``;
    the lookup returns the smallest-batch entry for ``(n, t, v)``.
    """
    if tuning is None or tuning == "off":
        return None
    from repro.tune import table as table_mod  # deferred: keep plan() light

    if isinstance(tuning, table_mod.TuningTable):
        tab: Any = tuning
    elif tuning == "auto":
        tab = table_mod.load_default()
        if tab is None:
            return None
    elif isinstance(tuning, str):
        tab = table_mod.load_cached(tuning)
    else:
        raise UnknownKnobError(
            f"tuning must be 'auto', 'off', a table path or a TuningTable, "
            f"got {tuning!r}",
            knob="tuning", value=tuning, alternatives=("auto", "off"),
        )
    winner = tab.lookup(n=n, t=t, v=v)
    return dict(winner) if winner is not None else None


def plan(
    n: int = 4096,
    t: int = 6,
    v: int = 30,
    *,
    backend: str = "auto",
    schedule="auto",
    tiling=None,
    row_blk: int | None = None,
    channel_grid: bool | None = None,
    use_sau: bool = True,
    tuning: Any = "off",
) -> Plan:
    """Build an executable plan: search/validate primes, precompute and
    upload every table, and resolve all execution knobs into a frozen
    :class:`PlanConfig`.

    ``backend="auto"`` picks the pure-jnp datapath for v <= 46 and the
    host oracle beyond; a Pallas backend asked for on a TPU raises
    :class:`repro.errors.UnservableConfigError` (its int64 kernels do
    not compile there, see :func:`repro.kernels.ops.check_servable`).

    ``schedule`` accepts
    ``"auto"`` (lane-aligned four-step for n >= 256), ``"radix2"``,
    ``"four_step"``, ``"four_step:h"`` (asserts the hierarchical
    depth >= 2 chain, available from n = 8192) or an explicit
    :class:`ScheduleSpec`; whichever is given, the config freezes one
    fully-resolved spec — tile chain, row block and VMEM accounting
    included.  ``tiling`` is an optional hint: an int is a row-block
    request, a tuple of per-level ``(columns, rows)`` pairs asserts the
    expected tile chain.  ``channel_grid`` pins the fused-e2e kernel's
    RNS-channel grid axis (True = grid over channels, False = unrolled,
    None = kernel default); it is a knob of ``backend="pallas_fused_e2e"``
    only.

    ``tuning`` consults the profile-driven tuning table
    (:mod:`repro.tune`): ``"off"`` (default) keeps the static defaults,
    ``"auto"`` uses the committed ``TUNING_default.json``, and a path (or
    a ``TuningTable``) uses that table.  Resolution order is **explicit
    knob > tuning table > static default** — the table only fills knobs
    still at their defaults (``backend="auto"``, ``schedule="auto"``,
    ``row_blk=None``, ``channel_grid=None``), and the winner lands in the
    frozen :class:`PlanConfig` like any hand-set knob, so jit keys,
    :func:`plan_key` buckets and the verifier see it first-class.

    Invalid knobs raise :class:`repro.errors.UnknownKnobError` and
    structurally valid but unservable combinations (four_step on a tiny
    n, a Pallas backend on the wide width, a row block that overflows
    VMEM, ...) raise :class:`repro.errors.UnservableConfigError` — both
    ``ValueError`` subclasses, both at plan time, never mid-execution.
    """
    if not isinstance(n, int) or n < 4 or n & (n - 1):
        raise UnknownKnobError(
            f"n must be a power of two >= 4, got n={n!r}",
            knob="n", value=n, alternatives=(),
        )
    if not isinstance(t, int) or t < 1:
        raise UnknownKnobError(
            f"t must be a positive int, got t={t!r}",
            knob="t", value=t, alternatives=(),
        )
    if not isinstance(v, int) or not (_V_MIN <= v <= _V_MAX):
        raise UnknownKnobError(
            f"v must be an int in [{_V_MIN}, {_V_MAX}], got v={v!r} "
            f"(the paper's configs are v=30 and v=45)",
            knob="v", value=v, alternatives=(),
        )
    tuned = _tuning_winner(tuning, n, t, v)
    if tuned is not None:
        # explicit knob > tuning table > static default: the table fills
        # only knobs the caller left at their defaults.
        if backend == "auto" and tuned.get("backend"):
            backend = tuned["backend"]
        if (
            isinstance(schedule, str)
            and schedule == "auto"
            and tuned.get("schedule")
        ):
            schedule = tuned["schedule"]
        if row_blk is None and tiling is None and tuned.get("row_blk") is not None:
            row_blk = tuned["row_blk"]
        if channel_grid is None and tuned.get("channel_grid") is not None:
            channel_grid = tuned["channel_grid"]
    if row_blk is not None and (not isinstance(row_blk, int) or row_blk < 1):
        raise UnknownKnobError(
            f"row_blk must be >= 1, got {row_blk}",
            knob="row_blk", value=row_blk, alternatives=(1, 2, 4, 8),
        )
    if channel_grid is not None and not isinstance(channel_grid, bool):
        raise UnknownKnobError(
            f"channel_grid must be True, False or None, got {channel_grid!r}",
            knob="channel_grid", value=channel_grid, alternatives=(True, False, None),
        )
    width = width_for(v)
    # resolve the cheap knobs BEFORE the prime search so bad combos fail fast
    backend = _resolve_backend(width, backend)
    if channel_grid is not None and backend != "pallas_fused_e2e":
        raise UnservableConfigError(
            f"channel_grid= schedules the fused-e2e kernel's RNS-channel "
            f"grid axis; backend={backend!r} has no such grid "
            f"(use backend='pallas_fused_e2e' or leave channel_grid=None)",
            knob="channel_grid", value=channel_grid, alternatives=(None,),
        )
    _resolve_spec(width, n, schedule, tiling=tiling)
    _check_wide_envelope(width, t, v)
    params = make_params(n=n, t=t, v=v, row_blk=row_blk)
    spec = _resolve_spec(
        width, n, schedule, tiling=tiling, row_blk=row_blk, params=params
    )
    cfg = PlanConfig(
        n=n, t=t, v=v, width=width, backend=backend, schedule=spec,
        row_blk=row_blk, channel_grid=channel_grid, use_sau=use_sau,
        seg_count=params.plan.seg_count, w=params.plan.w, L=params.plan.L,
    )
    return Plan(config=cfg, params=params, consts=_consts_for(params, width))


def plan_from_params(
    params: ParenttParams,
    *,
    backend: str | None = None,
    use_sau: bool = True,
) -> Plan:
    """Adapter for the legacy class front doors: wrap an existing
    :class:`ParenttParams` (honouring its ``backend``/``schedule``/
    ``row_blk`` fields) into a :class:`Plan`."""
    width = width_for(params.v)
    if width == "int64":
        backend = ops_mod.check_servable(ops_mod.resolve_backend(params, backend))
    else:
        backend = _resolve_backend(width, backend or "auto")
    spec = _resolve_spec(
        width, params.n, params.schedule, row_blk=params.row_blk,
        params=params,
    )
    _check_wide_envelope(width, params.t, params.v)
    cfg = PlanConfig(
        n=params.n, t=params.t, v=params.v, width=width, backend=backend,
        schedule=spec, row_blk=params.row_blk, channel_grid=None,
        use_sau=use_sau,
        seg_count=params.plan.seg_count, w=params.plan.w, L=params.plan.L,
    )
    return Plan(config=cfg, params=params, consts=_consts_for(params, width))


# --------------------------------------------------------------------------
# shape contracts (the wide/oracle mirrors of kernels/ops.py's checks)
# --------------------------------------------------------------------------


def _require_plan(pl: Plan, fn: str) -> PlanConfig:
    if not isinstance(pl, Plan):
        raise TypeError(
            f"{fn}: first argument must be a repro.api.Plan "
            f"(build one with repro.plan(...)), got {type(pl).__name__}"
        )
    return pl.config


def _check_residues(
    x: Any, cfg: PlanConfig, fn: str, t: int | None = None
) -> None:
    t_want = cfg.t if t is None else t  # shard-local channel count under mesh
    if x.ndim < 2 or x.shape[0] != t_want or x.shape[-1] != cfg.n:
        raise ValueError(
            f"{fn}: expected residues (t={t_want}, ..., n={cfg.n}), "
            f"got shape {tuple(x.shape)}"
        )


def _check_poly_segments(z: Any, cfg: PlanConfig, fn: str, name: str) -> None:
    if z.ndim < 2 or z.shape[-2] != cfg.n or z.shape[-1] != cfg.seg_count:
        raise ValueError(
            f"{fn}: expected {name} segments (..., n={cfg.n}, "
            f"S={cfg.seg_count}), got shape {tuple(z.shape)}"
        )


def _no_tracers(cfg: PlanConfig, fn: str, *arrays: Any) -> None:
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        raise ValueError(
            f"{fn}: width={cfg.width!r} plans execute on the host "
            "(exact Python bigints) and cannot be traced — call the api "
            "eagerly, outside jit/vmap"
        )


# --------------------------------------------------------------------------
# execute: polymul (the single entry point) + the stage functions
# --------------------------------------------------------------------------


def polymul(pl: Plan, za: Any, zb: Any) -> jax.Array:
    """za, zb: ``(..., n, S)`` base-2^v segment arrays -> ``(..., n, L)``
    base-2^w limbs of ``a * b mod (x^n + 1, q)`` — the whole Fig-10
    pipeline (decompose -> per-channel no-shuffle NTT cascade ->
    inverse CRT) on whichever datapath the plan resolved.

    jit/vmap/shard_map-native for the int64 and wide widths (the plan is
    a pytree; pass it with ``in_axes=None`` under vmap).  The oracle
    width is host-only and raises under tracing.
    """
    cfg = _require_plan(pl, "polymul")
    if cfg.width == "int64":
        # The profiler stage scopes nest under this root:
        # parentt.polymul/parentt.{decompose,cascade,compose,fused_e2e}
        # (obs stage profiling, DESIGN.md §12).
        with jax.named_scope("parentt.polymul"):
            return ops_mod.fused_polymul_e2e(
                za, zb, _bound_params(pl), backend=cfg.backend,
                use_sau=cfg.use_sau, schedule=cfg.schedule,
                channel_grid=cfg.channel_grid,
            )
    _check_poly_segments(za, cfg, "polymul", "za")
    _check_poly_segments(zb, cfg, "polymul", "zb")
    if za.shape != zb.shape:
        raise ValueError(
            f"polymul: operand shapes differ: {tuple(za.shape)} vs "
            f"{tuple(zb.shape)}"
        )
    if cfg.width == "wide":
        ra = _wide_decompose(pl, za)
        rb = _wide_decompose(pl, zb)
        specs = _wide_exec_specs(pl)
        rp = wide_mod.negacyclic_mul_channels(
            ra, rb, pl.consts["wide_fwd"], pl.consts["wide_inv"], specs
        )
        return _wide_compose(pl, rp)
    return _oracle_polymul(pl, za, zb)


def ntt(pl: Plan, a: Any) -> jax.Array:
    """a: ``(t, ..., n)`` residues -> forward NTT per RNS channel
    (natural-order in, bit-reversed out — the no-shuffle convention)."""
    cfg = _require_plan(pl, "ntt")
    if cfg.width == "int64":
        return ops_mod.ntt_forward(
            a, _bound_params(pl), backend=cfg.backend, schedule=cfg.schedule
        )
    if cfg.width == "wide":
        specs = _wide_exec_specs(pl)
        _check_residues(a, cfg, "ntt", t=len(specs))
        return wide_mod.ntt_channels(a, pl.consts["wide_fwd"], specs)
    raise ValueError(
        "ntt: the oracle width has no device transform; v > 46 plans "
        "serve polymul/decompose/compose on the host only"
    )


def intt(pl: Plan, a: Any) -> jax.Array:
    """a: ``(t, ..., n)`` bit-reversed spectra -> natural-order residues."""
    cfg = _require_plan(pl, "intt")
    if cfg.width == "int64":
        return ops_mod.ntt_inverse(
            a, _bound_params(pl), backend=cfg.backend, schedule=cfg.schedule
        )
    if cfg.width == "wide":
        specs = _wide_exec_specs(pl)
        _check_residues(a, cfg, "intt", t=len(specs))
        return wide_mod.intt_channels(a, pl.consts["wide_inv"], specs)
    raise ValueError(
        "intt: the oracle width has no device transform; v > 46 plans "
        "serve polymul/decompose/compose on the host only"
    )


def negacyclic_mul(pl: Plan, a: Any, b: Any) -> jax.Array:
    """``(t, ..., n) x (t, ..., n)`` -> per-channel negacyclic products
    (the residue-domain cascade — what the BFV layer runs per product)."""
    cfg = _require_plan(pl, "negacyclic_mul")
    if cfg.width == "int64":
        return ops_mod.negacyclic_mul(
            a, b, _bound_params(pl), backend=cfg.backend, schedule=cfg.schedule
        )
    if cfg.width == "wide":
        specs = _wide_exec_specs(pl)
        _check_residues(a, cfg, "negacyclic_mul", t=len(specs))
        _check_residues(b, cfg, "negacyclic_mul", t=len(specs))
        if a.shape != b.shape:
            raise ValueError(
                f"negacyclic_mul: operand shapes differ: {tuple(a.shape)} "
                f"vs {tuple(b.shape)}"
            )
        return wide_mod.negacyclic_mul_channels(
            a, b, pl.consts["wide_fwd"], pl.consts["wide_inv"], specs
        )
    raise ValueError(
        "negacyclic_mul: the oracle width has no device transform; "
        "v > 46 plans serve polymul/decompose/compose on the host only"
    )


def decompose(pl: Plan, z: Any) -> jax.Array:
    """z: ``(..., S)`` base-2^v segments -> residues ``(t, ...)``."""
    cfg = _require_plan(pl, "decompose")
    if cfg.width == "int64":
        return ops_mod.rns_decompose(
            z, _bound_params(pl), backend=cfg.backend, use_sau=cfg.use_sau
        )
    if z.ndim < 1 or z.shape[-1] != cfg.seg_count:
        raise ValueError(
            f"decompose: expected base-2^{cfg.v} segments "
            f"(..., S={cfg.seg_count}), got shape {tuple(z.shape)}"
        )
    if cfg.width == "wide":
        return wide_mod.decompose_channels(
            z, _wide_exec_specs(pl), pl.consts["wide_beta_pows"]
        )
    _no_tracers(cfg, "decompose", z)
    rp = pl.params.plan
    zn = np.asarray(z)
    flat = zn.reshape(-1, zn.shape[-1])
    out = np.empty((cfg.t, flat.shape[0]), dtype=np.int64)
    for r in range(flat.shape[0]):
        x = bigint.limbs_to_int(flat[r], cfg.v)
        for i in range(cfg.t):
            out[i, r] = x % int(rp.qs[i])
    return jnp.asarray(out.reshape((cfg.t,) + zn.shape[:-1]))


def compose(pl: Plan, residues: Any) -> jax.Array:
    """residues: ``(t, ...)`` -> ``(..., L)`` base-2^w limbs of the
    CRT-composed value (canonical, < q)."""
    cfg = _require_plan(pl, "compose")
    if cfg.width == "int64":
        return ops_mod.rns_compose(
            residues, _bound_params(pl), backend=cfg.backend
        )
    t_want = len(_wide_exec_specs(pl)) if cfg.width == "wide" else cfg.t
    if residues.ndim < 1 or residues.shape[0] != t_want:
        raise ValueError(
            f"compose: expected residues (t={t_want}, ...), got shape "
            f"{tuple(residues.shape)}"
        )
    if cfg.width == "wide":
        return _wide_compose(pl, residues)
    _no_tracers(cfg, "compose", residues)
    rp = pl.params.plan
    rn = np.asarray(residues)
    flat = rn.reshape(cfg.t, -1)
    out = np.empty((flat.shape[1], cfg.L), dtype=np.int64)
    for r in range(flat.shape[1]):
        acc = 0
        for i in range(cfg.t):
            qi = int(rp.qs[i])
            y = (int(flat[i, r]) * int(rp.qi_tilde[i])) % qi
            acc = (acc + y * (rp.q // qi)) % rp.q
        out[r] = bigint.int_to_limbs(acc, cfg.w, cfg.L)
    return jnp.asarray(out.reshape(rn.shape[1:] + (cfg.L,)))


# --------------------------------------------------------------------------
# wide-width internals
# --------------------------------------------------------------------------


def _wide_decompose(pl: Plan, z: Any) -> jax.Array:
    return wide_mod.decompose_channels(
        z, _wide_exec_specs(pl), pl.consts["wide_beta_pows"]
    )


def _wide_compose(pl: Plan, residues: Any) -> jax.Array:
    cfg = pl.config
    limbs14 = wide_mod.compose_channels(
        residues,
        _wide_exec_specs(pl),
        pl.consts["wide_qi_tilde"],
        pl.consts["wide_qi_star_limbs"],
        pl.consts["wide_q_limbs"],
    )
    out = wide_mod.repack_limbs(limbs14, wide_mod.POST_W, cfg.w)
    assert out.shape[-1] == cfg.L, (out.shape, cfg.L)
    return out


# --------------------------------------------------------------------------
# oracle-width internals (host, exact, eager-only)
# --------------------------------------------------------------------------


def _oracle_polymul(pl: Plan, za: Any, zb: Any) -> jax.Array:
    cfg = pl.config
    _no_tracers(cfg, "polymul", za, zb)
    za_n, zb_n = np.asarray(za), np.asarray(zb)
    lead = za_n.shape[:-2]
    a3 = za_n.reshape((-1,) + za_n.shape[-2:])
    b3 = zb_n.reshape((-1,) + zb_n.shape[-2:])
    out = np.empty((a3.shape[0], cfg.n, cfg.L), dtype=np.int64)
    for r in range(a3.shape[0]):
        a_ints = [bigint.limbs_to_int(a3[r, j], cfg.v) for j in range(cfg.n)]
        b_ints = [bigint.limbs_to_int(b3[r, j], cfg.v) for j in range(cfg.n)]
        p_ints = polymul_mod.oracle_multiply(a_ints, b_ints, pl.params)
        out[r] = bigint.ints_to_limbs(p_ints, cfg.w, cfg.L)
    return jnp.asarray(out.reshape(lead + (cfg.n, cfg.L)))


# --------------------------------------------------------------------------
# host <-> device format helpers + int convenience
# --------------------------------------------------------------------------


def to_segments(pl: Plan, xs: Any) -> jax.Array:
    """Python ints (length n) -> ``(n, S)`` base-2^v segment array."""
    cfg = _require_plan(pl, "to_segments")
    return jnp.asarray(
        bigint.ints_to_limbs(xs, cfg.v, cfg.seg_count)
    )


def from_limbs(pl: Plan, limbs: Any) -> list[int]:
    """``(..., L)`` base-2^w output limbs -> flat list of Python ints."""
    cfg = _require_plan(pl, "from_limbs")
    return bigint.limbs_to_ints(np.asarray(limbs), cfg.w)


# One module-level jitted executor shared by every plan: the Plan pytree
# is an ordinary argument, so same-config calls hit one compiled entry.
_polymul_jit = jax.jit(polymul)

# Donating twin for serving hot loops: the operand buffers are handed to
# XLA for reuse (the engine builds fresh padded slot buffers per
# dispatch, so nothing ever reads them back).
_polymul_jit_donating = jax.jit(polymul, donate_argnums=(1, 2))


def plan_key(pl: Plan) -> PlanConfig:
    """The hashable bucket/cache key of a plan: its frozen
    :class:`PlanConfig`.  Two plans with equal keys are interchangeable
    executables (same treedef, same shared device tables), so serving
    layers key jit caches and batch buckets on this
    (:class:`repro.serve.crypto_engine.PolymulEngine`)."""
    return _require_plan(pl, "plan_key")


def execute(pl: Plan, za: Any, zb: Any, *, donate: bool = False) -> jax.Array:
    """Jitted :func:`polymul` through the shared module-level executor —
    the serving layer's execute hook.  One compiled entry per distinct
    :func:`plan_key`; ``donate=True`` additionally donates the operand
    buffers to XLA (callers must not reuse ``za``/``zb`` afterwards —
    the batching engine's padded slot buffers are built fresh per
    dispatch, which is exactly this contract; backends without donation
    support, e.g. CPU, warn and copy).  Oracle-width plans fall back to
    the eager host path."""
    cfg = _require_plan(pl, "execute")
    if cfg.width == "oracle":
        return polymul(pl, za, zb)
    if donate:
        return _polymul_jit_donating(pl, za, zb)
    return _polymul_jit(pl, za, zb)


def polymul_ints(pl: Plan, a: Any, b: Any) -> list[int]:
    """Host convenience: Python-int coefficient lists in, Python-int
    product coefficients out, through the plan's full device pipeline
    (or the host oracle for the oracle width)."""
    cfg = _require_plan(pl, "polymul_ints")
    za, zb = to_segments(pl, a), to_segments(pl, b)
    if cfg.width == "oracle":
        limbs = polymul(pl, za, zb)
    else:
        limbs = _polymul_jit(pl, za, zb)
    return from_limbs(pl, limbs)
