"""Backend-dispatch layer: ONE switch selects the datapath for the whole
stack (``repro.plan``/``repro.polymul``, the BFV layer, benchmarks,
examples).

Backends
--------
* ``"jnp"``          — pure-jnp reference datapath (vmapped channel NTTs,
  SAU/Barrett RNS pre/post).  Always available; the oracle the kernels
  are validated against.
* ``"pallas"``       — per-stage Pallas kernels: NTT(a), NTT(b), the
  pointwise product and the iNTT are separate ``pallas_call``s, so the
  NTT-domain product round-trips HBM between stages (the Fig 11(a)-style
  baseline for the fusion win).
* ``"pallas_fused"`` — the paper's contribution-1 datapath: the whole
  NTT -> ⊙ -> iNTT cascade runs inside one kernel and the NTT-domain
  product never leaves VMEM; decompose/compose are still separate
  kernels.
* ``"pallas_fused_e2e"`` — the paper's complete feed-forward datapath
  (Fig 10): decompose -> cascade -> compose in ONE kernel via
  :func:`fused_polymul_e2e`; residue polynomials never exist in HBM.
  Stage-level entry points (``ntt_forward``, ``rns_decompose``, ...)
  have no single-kernel equivalent, so under this backend they degrade
  to the closest kernel datapath (the cascade to ``pallas_fused``,
  everything else to ``pallas``) — only the e2e product gets the full
  fusion.

The backend is threaded through :class:`repro.core.params.ParenttParams`
(``make_params(..., backend=...)``) and may be overridden per call with
the ``backend=`` keyword.  The legacy ``use_pallas=`` bool is kept as a
deprecated alias (True -> the Pallas path, False -> ``"jnp"``).

The public front door, :mod:`repro.api`, resolves backend/schedule ONCE
at plan time into a frozen ``PlanConfig`` and calls these dispatchers
with concrete values — per-call resolution here exists for the legacy
entry points and degrades to validation when the value is already
concrete.

Pallas kernels run in interpret mode off-TPU.  On a TPU none of them
compiles yet: every Pallas datapath computes on int64, and Mosaic (the
TPU's Pallas compiler) has no 64-bit vector layout.  So on a TPU the
Pallas backends are refused when they are resolved
(:func:`check_servable`, an :class:`~repro.errors.UnservableConfigError`
at plan time) and ``"auto"`` is ``"jnp"`` everywhere.  The ``"jnp"``
backend is also what the dry-run lowering uses on the 512-device mesh,
where interpret-mode python loops would bloat the HLO.

Shape contracts (match :mod:`repro.core.rns` / :mod:`repro.core.ntt`;
violations raise immediately so a backend mismatch fails loudly):

* residues are ``(t, ..., n)`` — RNS channel leading, coefficients last;
* segment arrays are ``(..., S)`` with ``S = plan.seg_count``;
* limb arrays are ``(..., L)``.

The Pallas kernels internally operate on flattened ``(t, rows, n)`` /
``(rows, S)`` tiles; this layer folds/unfolds the batch dims, so callers
may pass any leading shape (``repro.decompose`` passes ``(..., n, S)``).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core import modmath
from repro.core import ntt as ntt_mod
from repro.core import rns as rns_mod
from repro.core import schedule as schedule_mod
from repro.core.params import (
    BACKENDS,
    SCHEDULES,
    ParenttParams,
    validate_backend,
)
from repro.analysis import walk as walk_mod
from repro.errors import UnservableConfigError
from repro.kernels import crt as crt_kernels
from repro.kernels import ntt as ntt_kernels

__all__ = [
    "BACKENDS",
    "SCHEDULES",
    "PALLAS_BACKENDS",
    "check_servable",
    "unbind",
    "resolve_backend",
    "resolve_schedule",
    "ntt_forward",
    "ntt_inverse",
    "negacyclic_mul",
    "rns_decompose",
    "rns_compose",
    "fused_polymul_e2e",
    "hbm_traffic_model",
    "count_pallas_launches",
    "transform_cost_model",
    "count_reduction_selects",
]


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def unbind(obj: Any) -> Any:
    """The stable host object behind a leaf-bound view (see
    ``repro.api._LeafBound``), or ``obj`` itself.

    The api layer hands this dispatch layer params/tables/plan *views*
    whose device arrays are a Plan's pytree leaves (possibly tracers, so
    sharding the leaves is load-bearing).  Kernel wrappers that take a
    plan as a jit-STATIC argument need the underlying identity-hashable
    host object instead — a fresh view (or one holding tracers) must
    never become a jit cache key."""
    return getattr(obj, "_base", obj)


def _stage_backend(backend: str, cascade: bool = False) -> str:
    """Per-stage datapath for a resolved backend: ``pallas_fused_e2e``
    has no standalone-stage kernels, so stage entry points degrade to the
    closest kernel path (see module docstring)."""
    if backend == "pallas_fused_e2e":
        return "pallas_fused" if cascade else "pallas"
    return backend


PALLAS_BACKENDS = ("pallas", "pallas_fused", "pallas_fused_e2e")


def check_servable(backend: str) -> str:
    """``backend`` if this process can compile it, else raise
    :class:`~repro.errors.UnservableConfigError`.  On a TPU every Pallas
    backend is refused: the kernels compute on int64 and Mosaic has no
    64-bit layout (``ZeroDivisionError`` in its block-mapping check for
    ``pallas``/``pallas_fused``, "only 32-bit scalars" or an unaligned
    per-channel block for ``pallas_fused_e2e``).  :func:`repro.plan`
    calls this, so the refusal comes at plan time and a compile failure
    never reaches a dispatch."""
    if backend in PALLAS_BACKENDS and _is_tpu():
        raise UnservableConfigError(
            f"backend={backend!r} does not compile for a TPU: its kernels "
            f"compute on int64 and Mosaic has no 64-bit vector layout "
            f"(use 'auto' or 'jnp')",
            knob="backend", value=backend, alternatives=("auto", "jnp"),
        )
    return backend


def resolve_backend(
    params: ParenttParams | None = None,
    backend: str | None = None,
    use_pallas: bool | None = None,
) -> str:
    """Pick the datapath: explicit ``backend`` > legacy ``use_pallas`` >
    ``params.backend`` > ``"jnp"``."""
    if backend is None and use_pallas is not None:
        backend = "pallas_fused" if use_pallas else "jnp"
    if backend is None:
        backend = getattr(params, "backend", None) or "jnp"
    return validate_backend(backend)


def resolve_schedule(
    params: ParenttParams, schedule=None
) -> schedule_mod.ScheduleSpec:
    """Pick the concrete NTT stage schedule: explicit ``schedule`` >
    ``params.schedule`` > ``"auto"`` (four_step when n >= 256).  Returns
    the resolved :class:`~repro.core.schedule.ScheduleSpec` (an already
    resolved spec — e.g. off a ``PlanConfig`` — passes through).  Unlike
    :func:`resolve_backend`, params is required — auto resolution needs
    the transform length."""
    if schedule is None:
        schedule = getattr(params, "schedule", None) or "auto"
    return schedule_mod.concrete_spec(params.n, schedule)


def _lazy_of(ct: ntt_mod.ChannelTables) -> tuple[int, int] | None:
    """(window, beta) for the Harvey lazy butterflies, or None when the
    table set has no Shoup constants (outside the 63-bit envelope)."""
    if ct.lazy_window is None or ct.mul_shifts is None:
        return None
    return (ct.lazy_window, ct.shoup_beta)


def _sched_tables(
    ct: ntt_mod.ChannelTables, schedule, lazy: tuple[int, int] | None, direction: str
) -> tuple[Any, Any, Any, Any]:
    """(table, shoup, row_tables, row_shoups) device arrays for one
    transform direction under (schedule, lazy) — the positional tail the
    kernel wrappers expect after their required args.  The row entries
    are per-level tuples (level 0 = the (t, n2, n1) tables, deeper
    levels the hierarchical sub-row tables, truncated to the schedule's
    depth); ``schedule`` is a concrete string or a resolved spec."""
    kind = getattr(schedule, "kind", schedule)
    four = kind == "four_step"
    if four and ct.fs_row_fwd is None:
        raise ValueError(
            f"four_step schedule unavailable for n={ct.n}: no row tables"
        )
    depth = getattr(schedule, "depth", 0) or (1 + len(ct.fs_sub_fwd))
    if direction == "fwd":
        tab, sh = ct.fwd_d, ct.fwd_shoup_d
        row = (ct.fs_row_fwd_d,) + tuple(ct.fs_sub_fwd_d[: depth - 1])
        rsh = (
            None
            if ct.fs_row_fwd_shoup_d is None
            else (ct.fs_row_fwd_shoup_d,)
            + tuple(ct.fs_sub_fwd_shoup_d[: depth - 1])
        )
    else:
        tab, sh = ct.inv_d, ct.inv_shoup_d
        row = (ct.fs_row_inv_d,) + tuple(ct.fs_sub_inv_d[: depth - 1])
        rsh = (
            None
            if ct.fs_row_inv_shoup_d is None
            else (ct.fs_row_inv_shoup_d,)
            + tuple(ct.fs_sub_inv_shoup_d[: depth - 1])
        )
    return (
        tab,
        sh if lazy is not None else None,
        row if four else None,
        rsh if (four and lazy is not None) else None,
    )


def _kernel_kw(
    params: ParenttParams, schedule: str, lazy: tuple[int, int] | None
) -> dict[str, Any]:
    assert params.tables is not None  # callers guard via _require_tables
    kw = dict(
        shifts=params.tables.mul_shifts,
        schedule=schedule,
        lazy=lazy,
        interpret=not _is_tpu(),
    )
    if params.row_blk is not None:
        kw["row_blk"] = params.row_blk
    return kw


# --------------------------------------------------------------------------
# shape contracts
# --------------------------------------------------------------------------


def _check_residues(x: Any, params: ParenttParams, fn: str) -> None:
    if x.ndim < 2 or x.shape[0] != params.t or x.shape[-1] != params.n:
        raise ValueError(
            f"{fn}: expected residues (t={params.t}, ..., n={params.n}), "
            f"got shape {tuple(x.shape)}"
        )


def _check_segments(z: Any, params: ParenttParams, fn: str) -> None:
    S = params.plan.seg_count
    if z.ndim < 1 or z.shape[-1] != S:
        raise ValueError(
            f"{fn}: expected base-2^{params.v} segments (..., S={S}), "
            f"got shape {tuple(z.shape)}"
        )


def _require_tables(params: ParenttParams, fn: str) -> ntt_mod.ChannelTables:
    if params.tables is None:
        raise ValueError(
            f"{fn}: params (n={params.n}, t={params.t}, v={params.v}) have no "
            "int64-safe NTT tables (v > 31); use polymul.oracle_multiply or "
            "repro.plan(..., v=45) (the wide width resolves at plan time)"
        )
    return params.tables


def _fold_rows(x: Any) -> tuple[Any, tuple[int, ...]]:
    """(t, ..., n) -> ((t, rows, n), unfold)"""
    t, n = x.shape[0], x.shape[-1]
    lead = x.shape[1:-1]
    return x.reshape(t, -1, n), lead


# --------------------------------------------------------------------------
# NTT / cascade dispatch
# --------------------------------------------------------------------------


def ntt_forward(a: Any, params: ParenttParams, *, backend: str | None = None,
                use_pallas: bool | None = None, schedule: str | None = None) -> Any:
    """a: (t, ..., n) -> forward NTT per RNS channel."""
    backend = _stage_backend(resolve_backend(params, backend, use_pallas))
    schedule = resolve_schedule(params, schedule)
    ct = _require_tables(params, "ntt_forward")
    _check_residues(a, params, "ntt_forward")
    with jax.named_scope("parentt.ntt_fwd"):
        if backend == "jnp":
            return ntt_mod.ntt_channels(a, ct, schedule)
        a3, lead = _fold_rows(a)
        lazy = _lazy_of(ct)
        fwd, sh, row, rsh = _sched_tables(ct, schedule, lazy, "fwd")
        out = ntt_kernels.ntt_channels_pallas(
            a3, ct.qs_d, fwd, ct.mul_eps_d, sh, row, rsh,
            **_kernel_kw(params, schedule, lazy),
        )
        return out.reshape(a.shape[:1] + lead + a.shape[-1:])


def ntt_inverse(a: Any, params: ParenttParams, *, backend: str | None = None,
                use_pallas: bool | None = None, schedule: str | None = None) -> Any:
    """a: (t, ..., n) bit-reversed spectra -> natural-order coefficients."""
    backend = _stage_backend(resolve_backend(params, backend, use_pallas))
    schedule = resolve_schedule(params, schedule)
    ct = _require_tables(params, "ntt_inverse")
    _check_residues(a, params, "ntt_inverse")
    with jax.named_scope("parentt.ntt_inv"):
        if backend == "jnp":
            return ntt_mod.intt_channels(a, ct, schedule)
        a3, lead = _fold_rows(a)
        lazy = _lazy_of(ct)
        inv, sh, row, rsh = _sched_tables(ct, schedule, lazy, "inv")
        out = ntt_kernels.intt_channels_pallas(
            a3, ct.qs_d, ct.half_d, inv, ct.mul_eps_d, sh, row, rsh,
            **_kernel_kw(params, schedule, lazy),
        )
        return out.reshape(a.shape[:1] + lead + a.shape[-1:])


def negacyclic_mul(a: Any, b: Any, params: ParenttParams, *,
                   backend: str | None = None,
                   use_pallas: bool | None = None,
                   schedule: str | None = None) -> Any:
    """(t, ..., n) x (t, ..., n) -> negacyclic products per RNS channel
    (the no-shuffle NTT -> ⊙ -> iNTT cascade)."""
    backend = _stage_backend(
        resolve_backend(params, backend, use_pallas), cascade=True
    )
    schedule = resolve_schedule(params, schedule)
    ct = _require_tables(params, "negacyclic_mul")
    _check_residues(a, params, "negacyclic_mul")
    _check_residues(b, params, "negacyclic_mul")
    if a.shape != b.shape:
        raise ValueError(
            f"negacyclic_mul: operand shapes differ: {tuple(a.shape)} vs "
            f"{tuple(b.shape)}"
        )
    with jax.named_scope("parentt.cascade"):
        if backend == "jnp":
            return ntt_mod.negacyclic_mul_channels(a, b, ct, schedule)
        a3, lead = _fold_rows(a)
        b3, _ = _fold_rows(b)
        lazy = _lazy_of(ct)
        kw = _kernel_kw(params, schedule, lazy)
        fwd, fsh, frow, frsh = _sched_tables(ct, schedule, lazy, "fwd")
        inv, ish, irow, irsh = _sched_tables(ct, schedule, lazy, "inv")
        if backend == "pallas_fused":
            out = ntt_kernels.fused_polymul_pallas(
                a3, b3, ct.qs_d, ct.half_d, fwd, inv, ct.mul_eps_d,
                fsh, ish, frow, irow, frsh, irsh, **kw,
            )
        else:  # "pallas": per-stage kernels, product round-trips HBM
            with jax.named_scope("parentt.ntt_fwd"):
                fa = ntt_kernels.ntt_channels_pallas(
                    a3, ct.qs_d, fwd, ct.mul_eps_d, fsh, frow, frsh, **kw
                )
                fb = ntt_kernels.ntt_channels_pallas(
                    b3, ct.qs_d, fwd, ct.mul_eps_d, fsh, frow, frsh, **kw
                )
            with jax.named_scope("parentt.pointwise"):
                q_b = ct.qs_d[:, None, None]
                eps_b = (
                    None if ct.mul_eps is None
                    else ct.mul_eps_d[:, None, None]
                )
                prod = modmath.mul_mod(fa, fb, q_b, eps_b, ct.mul_shifts)
            with jax.named_scope("parentt.ntt_inv"):
                out = ntt_kernels.intt_channels_pallas(
                    prod, ct.qs_d, ct.half_d, inv, ct.mul_eps_d,
                    ish, irow, irsh, **kw
                )
        return out.reshape(a.shape[:1] + lead + a.shape[-1:])


# --------------------------------------------------------------------------
# RNS pre/post dispatch
# --------------------------------------------------------------------------


def rns_decompose(z: Any, params: ParenttParams, *, backend: str | None = None,
                  use_pallas: bool | None = None, use_sau: bool = True) -> Any:
    """z: (..., S) base-2^v segments -> residues (t, ...)."""
    backend = _stage_backend(resolve_backend(params, backend, use_pallas))
    _check_segments(z, params, "rns_decompose")
    with jax.named_scope("parentt.decompose"):
        if backend == "jnp":
            fn = rns_mod.decompose_sau if use_sau else rns_mod.decompose
            return fn(z, params.plan)
        lead = z.shape[:-1]
        z2 = z.reshape(-1, z.shape[-1])
        out = crt_kernels.decompose_pallas(
            z2, plan=unbind(params.plan), interpret=not _is_tpu()
        )  # (t, rows)
        return out.reshape((params.t,) + lead)


def rns_compose(residues: Any, params: ParenttParams, *,
                backend: str | None = None,
                use_pallas: bool | None = None) -> Any:
    """residues: (t, ...) -> (..., L) base-2^w limbs of the composed value."""
    backend = _stage_backend(resolve_backend(params, backend, use_pallas))
    if residues.ndim < 1 or residues.shape[0] != params.t:
        raise ValueError(
            f"rns_compose: expected residues (t={params.t}, ...), got shape "
            f"{tuple(residues.shape)}"
        )
    with jax.named_scope("parentt.compose"):
        if backend == "jnp":
            return rns_mod.compose(residues, params.plan)
        lead = residues.shape[1:]
        r2 = residues.reshape(params.t, -1)
        rp = params.plan  # possibly a leaf-bound view: its *_d arrays
        # are plan leaves, passed as TRACED kernel operands below
        out = crt_kernels.compose_pallas(
            r2, plan=unbind(rp), qs=rp.qs_d, qi_tilde=rp.qi_tilde_d,
            star=rp.qi_star_limbs_d, q_limbs=rp.q_limbs_d,
            interpret=not _is_tpu(),
        )  # (rows, L)
        return out.reshape(lead + (params.plan.L,))


# --------------------------------------------------------------------------
# end-to-end dispatch (the whole Fig 10 pipeline behind one entry point)
# --------------------------------------------------------------------------


def fused_polymul_e2e(za: Any, zb: Any, params: ParenttParams, *,
                      backend: str | None = None,
                      use_pallas: bool | None = None, use_sau: bool = True,
                      schedule: str | None = None,
                      channel_grid: bool | None = None) -> Any:
    """za, zb: (..., n, S) segment arrays -> (..., n, L) product limbs:
    decompose -> per-channel NTT cascade -> compose.

    On ``backend="pallas_fused_e2e"`` all three steps run inside ONE
    ``pallas_call`` and the residue polynomials stay VMEM-resident (the
    paper's feed-forward datapath — two fewer HBM round-trips than
    ``pallas_fused``, see :func:`hbm_traffic_model`).  On every other
    backend this composes the three stage dispatchers, so callers can
    hold one entry point and switch datapaths with one string.
    ``use_sau`` selects Alg 2 vs generic decompose on the jnp path (the
    kernel paths always run the SAU circuits).  ``channel_grid`` pins
    the fused-e2e kernel's RNS-channel grid axis (None = the kernel's
    own default, ``t >= 2``); other backends have no such grid and
    ignore it (the api layer rejects the combination at plan time).
    """
    backend = resolve_backend(params, backend, use_pallas)
    schedule = resolve_schedule(params, schedule)
    for name, z in (("za", za), ("zb", zb)):
        if z.ndim < 2 or z.shape[-2] != params.n:
            raise ValueError(
                f"fused_polymul_e2e: expected {name} segments "
                f"(..., n={params.n}, S={params.plan.seg_count}), got shape "
                f"{tuple(z.shape)}"
            )
        _check_segments(z, params, "fused_polymul_e2e")
    if za.shape != zb.shape:
        raise ValueError(
            f"fused_polymul_e2e: operand shapes differ: {tuple(za.shape)} "
            f"vs {tuple(zb.shape)}"
        )
    if backend != "pallas_fused_e2e":
        ra = rns_decompose(za, params, backend=backend, use_sau=use_sau)
        rb = rns_decompose(zb, params, backend=backend, use_sau=use_sau)
        rp = negacyclic_mul(ra, rb, params, backend=backend, schedule=schedule)
        return rns_compose(rp, params, backend=backend)
    ct = _require_tables(params, "fused_polymul_e2e")
    plan = params.plan
    lead = za.shape[:-2]
    z3a = za.reshape((-1,) + za.shape[-2:])
    z3b = zb.reshape((-1,) + zb.shape[-2:])
    lazy = _lazy_of(ct)
    fwd, fsh, frow, frsh = _sched_tables(ct, schedule, lazy, "fwd")
    inv, ish, irow, irsh = _sched_tables(ct, schedule, lazy, "inv")
    with jax.named_scope("parentt.fused_e2e"):
        out = ntt_kernels.fused_e2e_polymul_pallas(
            z3a, z3b, fwd, inv, plan.qi_star_limbs_d, plan.q_limbs_d,
            fsh, ish, frow, irow, frsh, irsh,
            plan=unbind(plan), schedule=schedule, lazy=lazy,
            channel_grid=channel_grid,
            row_blk=params.row_blk, interpret=not _is_tpu(),
        )
    return out.reshape(lead + (params.n, plan.L))


def hbm_traffic_model(params: ParenttParams, rows: int,
                      backend: str | None = None,
                      schedule=None) -> dict[str, Any]:
    """Modeled HBM bytes crossing kernel/stage boundaries for ONE
    end-to-end multiply of ``rows`` polynomials (both operands in, limbs
    out), per backend.

    Counts every data tensor entering or leaving a ``pallas_call`` AS
    DISPATCHED ABOVE (device-resident tables excluded) — including the
    t-fold segment re-read of the per-channel specialized decompose
    circuits, which each scan all S segments.  For ``"jnp"`` there are
    no kernel launches; its row is the logical stage-boundary dataflow
    (XLA may fuse some of it), reported as the unfused reference bound.
    The ``kernel_launches`` numbers are structural claims about the
    dispatch and are cross-checked against the traced computation by
    :func:`count_pallas_launches` in the ``bench-smoke`` CI gate — a
    refactor that de-fuses a path cannot silently keep its old row.
    """
    backend = resolve_backend(params, backend)
    plan = params.plan
    t = params.t
    B = 8  # int64 lanes everywhere in the kernel datapaths
    seg = rows * params.n * plan.seg_count * B  # one operand's segments
    res = t * rows * params.n * B  # one full residue tensor
    limb = rows * params.n * plan.L * B  # composed product limbs
    if backend == "jnp":
        # logical stage boundaries: decompose out 2res, NTT/pointwise/
        # iNTT intermediates 8res, compose in res; no pallas launches
        launches, seg_in, total = 0, 2 * seg, 2 * seg + 12 * res + limb
    elif backend == "pallas":
        # decompose: t calls per operand, each reading all S segments
        # (2t seg in / 2res out); NTT x2 (res/res each); pointwise
        # between kernels (2res in / res out); iNTT (res/res); compose
        # (res in / limb out)
        launches = 2 * t + 4
        seg_in = 2 * t * seg
        total = seg_in + 12 * res + limb
    elif backend == "pallas_fused":
        # decompose 2t calls, fused cascade (2res in / res out), compose
        launches = 2 * t + 2
        seg_in = 2 * t * seg
        total = seg_in + 6 * res + limb
    else:  # pallas_fused_e2e: segments in, limbs out, nothing between
        launches, seg_in, total = 1, 2 * seg, 2 * seg + limb
    # schedule/tiling view of the same traffic (hierarchy-aware): how the
    # e2e bytes stream through VMEM — row_blk rows per grid step, each
    # step's working set bounded by the tile model the planner resolved
    # row_blk against.  Depth does NOT change HBM bytes (deeper levels
    # are VMEM reshapes); it changes the per-step tile, reported here.
    spec = resolve_schedule(params, schedule)
    row_blk = spec.row_blk or params.row_blk or ntt_kernels.DEFAULT_E2E_ROWS_CHGRID
    tile = schedule_mod.tile_bytes_model(
        spec.kind, params.n, spec.splits, row_blk, plan.seg_count, plan.L,
        lazy=params.tables is not None and params.tables.lazy_window is not None,
    )
    return {
        "backend": backend,
        "rows": rows,
        "hbm_bytes": total,
        "kernel_launches": launches,
        "segment_bytes_in": seg_in,
        "limb_bytes_out": limb,
        "intermediate_bytes": total - seg_in - limb,
        "schedule": str(spec),
        "schedule_depth": max(spec.depth, 1),
        "row_blk": row_blk,
        "grid_row_steps": -(-rows // row_blk),
        "vmem_tile_bytes": tile,
    }


def count_pallas_launches(params: ParenttParams, backend: str | None = None,
                          rows: int = 1) -> int:
    """Count ``pallas_call`` equations in the TRACED e2e multiply.

    This is the structural ground truth for
    ``hbm_traffic_model(...)['kernel_launches']``: the bench-smoke CI
    gate and the backend tests assert the two agree, so the traffic
    model cannot drift from what the dispatch actually launches (e.g. a
    future change splitting the fused e2e kernel back into stages).
    """
    S = params.plan.seg_count
    z = jnp.zeros((rows, params.n, S), jnp.int64)
    jaxpr = jax.make_jaxpr(
        lambda a, b: fused_polymul_e2e(a, b, params, backend=backend)
    )(z, z)
    return walk_mod.count_prim(jaxpr, "pallas_call")


# --------------------------------------------------------------------------
# stage-schedule cost model (reduction ops + lane alignment), with the
# same traced-jaxpr cross-check discipline as the HBM model above
# --------------------------------------------------------------------------


def transform_cost_model(params: ParenttParams, *, schedule=None,
                         direction: str = "fwd") -> dict[str, Any]:
    """Structural cost of ONE NTT transform under a schedule:

    * ``sublane_stages`` — stages whose butterfly pairs sit within the
      lane (minor) axis at distance < 128, i.e. stages that need lane
      shuffles/strided access on real TPU vregs.  Computed from
      :func:`repro.core.ntt.stage_lane_strides` (the schedule's
      structural definition); 0 for four_step at every n AND every
      hierarchy depth (deeper levels pair along reshaped sublane
      factors — the depth-agnosticity claim of DESIGN.md §10).
    * ``reduction_ops`` — conditional-subtract (jnp.where -> select_n)
      ops the transform traces to: 5 per stage strict, 1-2 per stage +
      an O(1) exit canonicalize under Harvey lazy reduction.  The
      bench-smoke gate cross-checks this number against the actual
      traced kernel via :func:`count_reduction_selects`, so the model
      cannot drift from the implementation.  The total stage count is
      log2(n) at any depth — the hierarchy regroups stages, it does not
      add butterflies.
    * ``vmem_transposes`` — physical tile transposes per transform: 1
      for four_step at ANY depth (only level 0 transposes; deeper
      levels are reshapes), 0 for radix2.
    """
    if direction not in ("fwd", "inv"):
        raise ValueError(f"direction must be 'fwd' or 'inv', got {direction!r}")
    spec = resolve_schedule(params, schedule)
    n = params.n
    stages = n.bit_length() - 1
    strides = ntt_mod.stage_lane_strides(n, spec)
    sublane = sum(1 for s in strides if 0 < s < 128)
    ct = params.tables
    lazy = None if ct is None else _lazy_of(ct)
    if lazy is not None:
        window = lazy[0]
        red = (
            modmath.lazy_selects_per_stage(window, inverse=direction == "inv")
            * stages
            + modmath.canonicalize_selects(window)
        )
    else:
        window = None
        red = modmath.STRICT_SELECTS_PER_STAGE * stages
    return {
        "schedule": spec.kind,
        "spec": spec,
        "depth": max(spec.depth, 1) if spec.kind == "four_step" else 0,
        "splits": spec.splits,
        "vmem_transposes": 1 if spec.kind == "four_step" else 0,
        "direction": direction,
        "stages": stages,
        "lane_strides": strides,
        "sublane_stages": sublane,
        "lazy_window": window,
        "reduction_ops": red,
        "strict_reduction_ops": modmath.STRICT_SELECTS_PER_STAGE * stages,
    }


def count_reduction_selects(params: ParenttParams, *,
                            schedule: str | None = None,
                            direction: str = "fwd", rows: int = 2) -> int:
    """Count conditional-subtract selects in the TRACED transform kernel.

    Traces ``ntt_forward``/``ntt_inverse`` on the ``pallas`` backend and
    counts ``select_n`` equations inside the ``pallas_call`` bodies —
    the structural ground truth for
    ``transform_cost_model(...)['reduction_ops']``, asserted equal by
    the bench-smoke CI gate and the schedule tests."""
    a = jnp.zeros((params.t, rows, params.n), jnp.int64)
    fn = ntt_forward if direction == "fwd" else ntt_inverse
    jaxpr = jax.make_jaxpr(
        lambda x: fn(x, params, backend="pallas", schedule=schedule)
    )(a)
    return walk_mod.count_prim(jaxpr, "select_n", inside_pallas_only=True)
