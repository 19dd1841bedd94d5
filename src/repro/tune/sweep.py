"""Compiled-mode sweep harness: enumerate servable candidate configs per
workload, measure each, pick the winner, cross-check the cost model.

A *workload* is ``(n, t, v, batch)``; a *candidate* is an assignment of
the four tunable plan knobs (``backend``, ``schedule``, ``row_blk``,
``channel_grid``).  For each candidate the harness:

1. builds the plan — :class:`repro.errors.PlanError` subclasses
   (UnknownKnobError / UnservableConfigError) PRUNE the candidate with
   the taxonomy's knob/alternatives recorded, they never abort a sweep;
2. dedupes by :func:`repro.api.plan_key` — ``backend="auto"`` and its
   resolution measure once;
3. measures warm-up-excluded compiled wall-clock through the AOT chain
   ``jax.jit(polymul).lower(...).compile()`` — a real XLA:CPU compile
   today (interpret-mode Pallas inlines kernel bodies into the traced
   program, so XLA compiles the full datapath), Mosaic/TPU or Triton/GPU
   transparently when that is the default backend — and keeps the
   optimized HLO for the cost model.  A candidate that fails to lower or
   compile is recorded as ``status="failed"`` with the compiler's error
   and is not timed: timing some other path in its place would credit
   the candidate with a speed it does not have.

The winner is the fastest measured config, with a stability bias: the
static default keeps the crown unless a challenger beats it by more
than :data:`WINNER_MARGIN` (so the tuned choice is never slower than
the default on the box that swept, and plan caches don't churn over
noise).  Winner knobs are recorded RESOLVED (concrete backend string,
canonical schedule string), so ``plan(tuning=<table>)`` reproduces the
measured :class:`repro.api.PlanConfig` exactly.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.errors import PlanError
from repro.obs import metrics as obs_metrics
from repro.tune import costcheck, table as table_mod

# A challenger must beat the static default by this factor to dethrone it.
WINNER_MARGIN = 0.02

_INT64_BACKENDS = ("jnp", "pallas", "pallas_fused", "pallas_fused_e2e")
_SCHEDULES = ("radix2", "four_step", "four_step:h")


@dataclasses.dataclass(frozen=True)
class Workload:
    n: int
    t: int
    v: int
    batch: int

    @property
    def key(self) -> str:
        return table_mod.workload_key(self.n, self.t, self.v, self.batch)

    @classmethod
    def from_key(cls, key: str) -> "Workload":
        return cls(**table_mod.parse_workload_key(key))


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One assignment of the tunable knobs (None = static default)."""

    backend: str = "auto"
    schedule: str = "auto"
    row_blk: int | None = None
    channel_grid: bool | None = None

    @property
    def name(self) -> str:
        rb = "-" if self.row_blk is None else str(self.row_blk)
        cg = "-" if self.channel_grid is None else ("1" if self.channel_grid else "0")
        return f"{self.backend}/{self.schedule}/rb{rb}/cg{cg}"

    def knobs(self) -> dict[str, Any]:
        return {
            "backend": self.backend,
            "schedule": self.schedule,
            "row_blk": self.row_blk,
            "channel_grid": self.channel_grid,
        }


DEFAULT_CANDIDATE = Candidate()


def default_candidates(v: int, *, quick: bool = False) -> tuple[Candidate, ...]:
    """The candidate grid for a modulus width.

    The static default is always first (the winner baseline).  The int64
    width sweeps backend x schedule, with ``row_blk``/``channel_grid``
    varied only where they reach a kernel (the fused-e2e path); the wide
    and oracle widths have one datapath, so only the schedule vocabulary
    exercises the pruner.  ``quick`` is the CI grid: two backends, a
    trimmed row-block set, and the hierarchical schedule kept in to
    demonstrate taxonomy pruning at small n.
    """
    width = api.width_for(v)
    out: list[Candidate] = [DEFAULT_CANDIDATE]
    if width != "int64":
        # one datapath; radix2 is the only servable schedule, the rest
        # exist to exercise (and document) the pruning path
        out.extend(Candidate(backend="jnp" if width == "wide" else "oracle",
                             schedule=s)
                   for s in ("radix2", "four_step"))
        return tuple(out)
    backends = ("jnp", "pallas_fused_e2e") if quick else _INT64_BACKENDS
    row_blks: tuple[int | None, ...] = (None, 2) if quick else (None, 1, 2, 8)
    channel_grids: tuple[bool | None, ...] = (None,) if quick else (None, False, True)
    for be in backends:
        for sched in _SCHEDULES:
            if be == "pallas_fused_e2e":
                for rb in row_blks:
                    for cg in channel_grids:
                        out.append(Candidate(be, sched, rb, cg))
            else:
                out.append(Candidate(be, sched))
    return tuple(out)


# --------------------------------------------------------------------------
# measurement
# --------------------------------------------------------------------------


def make_operands(
    wl: Workload, seg_count: int, *, seed: int = 0
) -> tuple[jax.Array, jax.Array]:
    rng = np.random.default_rng(seed)
    shape = (wl.batch, wl.n, seg_count)
    za = jnp.asarray(rng.integers(0, 1 << wl.v, size=shape, dtype=np.int64))
    zb = jnp.asarray(rng.integers(0, 1 << wl.v, size=shape, dtype=np.int64))
    return za, zb


def measure_plan(
    pl: api.Plan,
    za: Any,
    zb: Any,
    *,
    iters: int = 3,
    warmup: int = 1,
    fn: Callable[..., Any] = api.polymul,
) -> dict[str, Any]:
    """Warm-up-excluded wall-clock for one plan, preferring the AOT
    compiled executable.

    Returns ``us_per_poly`` (median over ``iters`` timed calls, divided
    by the batch), ``compile_s``, ``mode`` ("compiled" | "eager") and
    the optimized ``hlo`` text (compiled mode only).  Oracle-width plans
    run on the host and time the eager path; for every other plan a
    lowering or compile error propagates to the caller.
    """
    batch = int(np.shape(za)[0]) if np.ndim(za) >= 3 else 1
    compiled = None
    hlo = None
    compile_s = None
    if api.plan_key(pl).width != "oracle":
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(pl, za, zb).compile()
        compile_s = time.perf_counter() - t0
        hlo = compiled.as_text()
    run: Callable[[], Any] = (
        (lambda: compiled(pl, za, zb)) if compiled is not None
        else (lambda: fn(pl, za, zb))
    )
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(run())
    samples = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        samples.append(time.perf_counter() - t0)
    return {
        "us_per_poly": float(np.median(samples)) * 1e6 / batch,
        "compile_s": compile_s,
        "mode": "compiled" if compiled is not None else "eager",
        "hlo": hlo,
    }


def _config_summary(cfg: api.PlanConfig) -> dict[str, Any]:
    return {
        "backend": cfg.backend,
        "schedule": cfg.schedule.canonical,
        "schedule_detail": str(cfg.schedule),
        "row_blk": cfg.row_blk,
        "channel_grid": cfg.channel_grid,
    }


# --------------------------------------------------------------------------
# per-workload sweep
# --------------------------------------------------------------------------


def sweep_workload(
    wl: Workload,
    candidates: tuple[Candidate, ...] | None = None,
    *,
    quick: bool = False,
    iters: int = 3,
    warmup: int = 1,
    kind: str | None = None,
    log: Callable[[str], None] | None = None,
) -> dict[str, Any]:
    """Sweep one workload; returns the report entry (see module docs).

    The report's ``entry`` field is the tuning-table payload
    (``TuningTable.put(**entry)`` ready)."""
    kind = kind or table_mod.device_kind()
    if candidates is None:
        candidates = default_candidates(wl.v, quick=quick)
    say = log or (lambda _msg: None)

    plans: list[tuple[Candidate, api.Plan]] = []
    records: list[dict[str, Any]] = []
    seen: dict[api.PlanConfig, str] = {}
    for cand in candidates:
        rec: dict[str, Any] = {"name": cand.name, "knobs": cand.knobs()}
        try:
            pl = api.plan(
                n=wl.n, t=wl.t, v=wl.v, backend=cand.backend,
                schedule=cand.schedule, row_blk=cand.row_blk,
                channel_grid=cand.channel_grid,
            )
        except PlanError as e:
            rec.update(
                status="pruned",
                error=type(e).__name__,
                knob=e.knob,
                reason=str(e),
                alternatives=list(getattr(e, "alternatives", ()) or ()),
            )
            records.append(rec)
            continue
        cfg = api.plan_key(pl)
        rec["config"] = _config_summary(cfg)
        first = seen.get(cfg)
        if first is not None:
            rec.update(status="duplicate", same_as=first)
            records.append(rec)
            continue
        seen[cfg] = cand.name
        rec["status"] = "measured"
        plans.append((cand, pl))
        records.append(rec)

    if not plans:
        raise PlanError(
            f"sweep {wl.key}: every candidate was pruned — nothing servable",
            knob="workload", value=wl.key, alternatives=(),
        )

    # measure (default candidate is plans[0] by construction)
    seg_count = api.plan_key(plans[0][1]).seg_count
    za, zb = make_operands(wl, seg_count)
    by_name = {r["name"]: r for r in records}
    for cand, pl in plans:
        say(f"  measuring {cand.name} ...")
        rec = by_name[cand.name]
        try:
            m = measure_plan(pl, za, zb, iters=iters, warmup=warmup)
        except Exception as e:  # noqa: BLE001 — a candidate that does not compile
            rec.update(status="failed", error=type(e).__name__, reason=str(e))
            continue
        rec.update(
            us_per_poly=m["us_per_poly"],
            compile_s=m["compile_s"],
            mode=m["mode"],
        )
        if m["hlo"] is not None:
            rec.update(costcheck.predicted_cost(m["hlo"], kind))

    # candidate outcomes land in the process registry beside the serve
    # metrics, so a tuning run is scrapeable like any soak
    cand_counter = obs_metrics.registry().counter(
        "repro_tune_candidates_total",
        "sweep candidate outcomes",
        ("status",),
    )
    for r in records:
        cand_counter.labels(status=r["status"]).inc()

    measured = [r for r in records if r["status"] == "measured"]
    check = costcheck.cross_check(
        [
            {
                "name": r["name"],
                "measured_us": r.get("us_per_poly"),
                "model_us": r.get("model_us"),
            }
            for r in measured
        ]
    )

    if not measured or measured[0]["name"] != plans[0][0].name:
        raise PlanError(
            f"sweep {wl.key}: the default candidate failed to compile: "
            f"{by_name[plans[0][0].name].get('reason')}",
            knob="workload", value=wl.key, alternatives=(),
        )
    default_rec = measured[0]  # DEFAULT_CANDIDATE is always first
    winner_rec = min(measured, key=lambda r: r["us_per_poly"])
    if winner_rec["us_per_poly"] >= default_rec["us_per_poly"] * (1 - WINNER_MARGIN):
        winner_rec = default_rec  # stability bias: default keeps the crown
    winner_cfg = dict(winner_rec["config"])
    winner_cfg.pop("schedule_detail", None)

    entry = {
        "n": wl.n, "t": wl.t, "v": wl.v, "batch": wl.batch,
        "winner": winner_cfg,
        "winner_us": winner_rec["us_per_poly"],
        "default_us": default_rec["us_per_poly"],
        "mode": winner_rec["mode"],
        "candidates_measured": len(measured),
        "candidates_pruned": sum(1 for r in records if r["status"] == "pruned"),
        "rank_correlation": check["rank_correlation"],
    }
    return {
        "key": wl.key,
        "workload": dataclasses.asdict(wl),
        "device_kind": kind,
        "entry": entry,
        "winner": winner_rec["name"],
        "candidates": records,
        "costcheck": check,
    }


def sweep(
    workloads: list[Workload],
    *,
    quick: bool = False,
    iters: int = 3,
    warmup: int = 1,
    table: table_mod.TuningTable | None = None,
    log: Callable[[str], None] | None = None,
) -> tuple[table_mod.TuningTable, dict[str, Any]]:
    """Sweep several workloads into one table + one report dict.

    Pass an existing ``table`` to merge (entries for swept workloads are
    overwritten, everything else is kept — including other device
    kinds)."""
    kind = table_mod.device_kind()
    tab = table if table is not None else table_mod.TuningTable()
    say = log or (lambda _msg: None)
    report: dict[str, Any] = {
        "schema": "repro.tune.sweep-report/v1",
        "device_kind": kind,
        "quick": quick,
        "iters": iters,
        "warmup": warmup,
        "workloads": [],
    }
    for wl in workloads:
        say(f"sweep {wl.key} [{kind}] ...")
        res = sweep_workload(
            wl, quick=quick, iters=iters, warmup=warmup, kind=kind, log=log
        )
        tab.put(kind=kind, **res["entry"])
        report["workloads"].append(res)
        say(
            f"  -> winner {res['winner']} "
            f"({res['entry']['winner_us']:.1f} us/poly vs default "
            f"{res['entry']['default_us']:.1f}), rank-corr "
            f"{res['entry']['rank_correlation']}"
        )
    return tab, report
