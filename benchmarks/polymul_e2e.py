"""Paper Tables VI/VII: the end-to-end PaReNTT modular polynomial
multiplier at the paper's operating point (n=4096, 180-bit q, t=6/v=30).

Reported: BPP / latency cycle model at 240 MHz (the paper's clock), the
measured CPU wall-clock of the full jit pipeline through the PUBLIC
backend-dispatch layer for BOTH the ``jnp`` and ``pallas_fused``
datapaths, a bit-exactness check of the fused path against the Python
bigint oracle, and the 49.2x latency comparison against Roy [7]
re-derived from the cycle model.

Note on absolute numbers: off-TPU the Pallas kernels run in *interpret*
mode, so their wall-clock here measures the emulation, not the silicon;
the comparison that matters off-TPU is the HBM round-trip model (bytes
crossing kernel boundaries per backend, ``ops.hbm_traffic_model``) plus
bit-exactness of every kernel path.

``python -m benchmarks.polymul_e2e --ci-smoke --out BENCH_ci.json``
runs the small-preset interpret-mode smoke used by the ``bench-smoke``
CI job: it records wall-clock + modeled HBM bytes for all four
backends across BOTH stage schedules (radix2 / four_step), checks every
path bit-exact against the bigint oracle, verifies the
reduction-op/lane-alignment cost model against the traced kernels,
executes one n=4096 four-step fused-e2e point bit-exact against the
host-NTT bigint oracle (recording its frozen ScheduleSpec tile), and
exits non-zero if any fusion/lane/lazy invariant regressed.  With
``--baseline BENCH_seed.json`` it additionally diffs op counts and
modeled HBM bytes against the committed baseline, so the perf
trajectory is tracked in-repo instead of only as a build artifact.
``--compiled`` adds an AOT compiled wall-clock column
(``compiled_us_per_poly``, via ``repro.tune.sweep.measure_plan``)
beside the interpret numbers in the same record.
"""
import argparse
import json
import random
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

import repro
from repro import compile_cache
from repro.core import params as params_mod
from repro.core import polymul as pm
from repro.core import schedule as sched
from repro.kernels import ops as ops_mod

FREQ = 240e6  # paper's post-pipelining clock
CONCRETE_SCHEDULES = ("radix2", "four_step")


def _plan(p, backend: str = "auto", schedule: str = "auto"):
    """api plan for a params preset (one code path for every width)."""
    return repro.plan(
        n=p.n, t=p.t, v=p.v, backend=backend, schedule=schedule,
        row_blk=p.row_blk,
    )


# ONE jitted executor for every timing row: same-config plans hit the
# same compiled entry (the retrace-free property tests/test_api.py gates)
_MUL = jax.jit(repro.polymul)


def _time_plan(pl, za, zb, iters: int = 3) -> float:
    """us per polynomial through jax.jit(repro.polymul) on one plan."""
    batch = za.shape[0]
    jax.block_until_ready(_MUL(pl, za, zb))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(_MUL(pl, za, zb))
    return (time.perf_counter() - t0) / iters / batch * 1e6


def _cost_model_record(p) -> dict:
    """Per-schedule reduction-op/lane-alignment model for one preset,
    cross-checked against the traced kernels (fwd direction; the inverse
    is asserted by tests/test_schedules.py)."""
    out = {}
    for schedule in CONCRETE_SCHEDULES:
        fwd = ops_mod.transform_cost_model(p, schedule=schedule)
        inv = ops_mod.transform_cost_model(p, schedule=schedule, direction="inv")
        out[schedule] = {
            "sublane_stages": fwd["sublane_stages"],
            "lazy_window": fwd["lazy_window"],
            "reduction_ops_fwd": fwd["reduction_ops"],
            "reduction_ops_inv": inv["reduction_ops"],
            "strict_reduction_ops": fwd["strict_reduction_ops"],
            "traced_selects_fwd": ops_mod.count_reduction_selects(
                p, schedule=schedule
            ),
        }
    return out


def run(row_blk: int | None = None):
    out = []
    n = 4096
    bpp = sched.bpp_cycles(n)
    lat = sched.latency_cycles(n, t_pipe=152)  # paper reports 4246-4254
    out.append(
        (
            "tableVII_cycle_model",
            lat / FREQ * 1e6,
            f"bpp={bpp}cyc({bpp/FREQ*1e6:.1f}us) latency={lat}cyc "
            f"({lat/FREQ*1e6:.1f}us) paper=17.4-17.7us",
        )
    )
    roy_cycles = 196_003  # paper's normalized Roy [7] latency (§V-D)
    out.append(
        (
            "tableVII_vs_roy_hpca19",
            roy_cycles / 225e6 * 1e6,
            f"roy=871.1us ours={lat/FREQ*1e6:.1f}us "
            f"reduction={roy_cycles/225e6/(lat/FREQ):.1f}x (paper: 49.2x)",
        )
    )
    # bit-exactness gate: the fused Pallas path vs the Python bigint
    # oracle (and the schoolbook), at a size where the O(n^2) oracle is
    # fast.  Runs through the same public dispatch layer as the timing.
    pchk = params_mod.make_params(n=256, t=6, v=30, row_blk=row_blk)
    rchk = random.Random(0)
    ca = [rchk.randrange(pchk.q) for _ in range(pchk.n)]
    cb = [rchk.randrange(pchk.q) for _ in range(pchk.n)]
    fused_ints = repro.polymul_ints(_plan(pchk, backend="pallas_fused"), ca, cb)
    oracle_ints = pm.oracle_multiply(ca, cb, pchk)
    if fused_ints != oracle_ints or fused_ints != pm.schoolbook_negacyclic(ca, cb, pchk.q):
        raise AssertionError("pallas_fused != bigint oracle at n=256/t=6/v=30")
    out.append(
        (
            "fused_vs_bigint_oracle_n256",
            0.0,
            "pallas_fused bit-exact vs oracle_multiply + schoolbook (n=256, t=6, v=30)",
        )
    )
    e2e_ints = repro.polymul_ints(
        _plan(pchk, backend="pallas_fused_e2e"), ca, cb
    )
    if e2e_ints != oracle_ints:
        raise AssertionError("pallas_fused_e2e != bigint oracle at n=256/t=6/v=30")
    out.append(
        (
            "fused_e2e_vs_bigint_oracle_n256",
            0.0,
            "pallas_fused_e2e bit-exact vs oracle_multiply (n=256, t=6, v=30)",
        )
    )
    # HBM round-trip delta across all four backends (n=256, t=6, batch=4):
    # wall-clock through the public dispatch layer + the bytes-moved model
    # (what the paper's feed-forward datapath eliminates; exact by
    # construction of the dispatch layer, see ops.hbm_traffic_model).
    rng_s = np.random.default_rng(1)
    bs = 4
    zs = [
        jnp.asarray(
            rng_s.integers(0, 1 << 30, size=(bs, pchk.n, pchk.plan.seg_count))
        )
        for _ in range(2)
    ]
    base = ops_mod.hbm_traffic_model(pchk, rows=bs, backend="pallas")
    for bk in ops_mod.BACKENDS:
        us_bk = _time_plan(_plan(pchk, backend=bk), zs[0], zs[1])
        m = ops_mod.hbm_traffic_model(pchk, rows=bs, backend=bk)
        out.append(
            (
                f"hbm_roundtrips_n256_{bk}",
                us_bk,
                f"hbm_bytes={m['hbm_bytes']} ({m['kernel_launches']} kernel "
                f"launches, {m['intermediate_bytes']} intermediate) "
                f"vs 3-kernel path {base['hbm_bytes']}: "
                f"{base['hbm_bytes'] / m['hbm_bytes']:.2f}x less traffic",
            )
        )
    # per-schedule op-count + wall-clock columns: the lane-aligned
    # four-step schedule vs the flat radix-2 loop, both with the Harvey
    # lazy butterflies the cost model accounts for
    cmod = _cost_model_record(pchk)
    for schedule in CONCRETE_SCHEDULES:
        us_s = _time_plan(
            _plan(pchk, backend="pallas_fused", schedule=schedule),
            zs[0], zs[1],
        )
        c = cmod[schedule]
        out.append(
            (
                f"schedule_n256_{schedule}_pallas_fused",
                us_s,
                f"sublane_stages={c['sublane_stages']} "
                f"reduction_ops/transform={c['reduction_ops_fwd']} "
                f"(strict {c['strict_reduction_ops']}, lazy window "
                f"{c['lazy_window']}); traced={c['traced_selects_fwd']}",
            )
        )
    # measured: full pipeline (t=6, v=30, n=4096), both datapaths through
    # the public backend-dispatch layer
    p = params_mod.make_params(n=4096, t=6, v=30, row_blk=row_blk)
    rng = np.random.default_rng(0)
    batch = 4
    za = jnp.asarray(
        rng.integers(0, 1 << 30, size=(batch, n, p.plan.seg_count))
    )
    zb = jnp.asarray(rng.integers(0, 1 << 30, size=(batch, n, p.plan.seg_count)))
    us = _time_plan(_plan(p, backend="jnp"), za, zb)
    out.append(
        (
            "tableVI_measured_polymul_t6_v30_jnp",
            us,
            f"per 4096-coeff 180-bit modular polymul (backend=jnp, CPU, batch={batch})",
        )
    )
    us_fused = _time_plan(_plan(p, backend="pallas_fused"), za, zb)
    mode = "compiled" if jax.default_backend() == "tpu" else "interpret"
    out.append(
        (
            "tableVI_measured_polymul_t6_v30_pallas_fused",
            us_fused,
            f"per 4096-coeff 180-bit modular polymul (backend=pallas_fused, "
            f"{mode} mode, batch={batch})",
        )
    )
    # throughput in NTT-channel butterflies/s for context
    butterflies = 6 * 3 * (n // 2) * 12  # t * (2 NTT + iNTT) * n/2 * log n
    out.append(
        (
            "tableVI_butterfly_rate",
            0.0,
            f"{butterflies / (us/1e6) / 1e6:.1f}M butterflies/s on 1 CPU core",
        )
    )
    # Table VI's t=4 vs t=6 comparison, both measured in-JAX and both
    # through the SAME repro.polymul entry (t=4/v=45 resolves to the
    # digit-split wide datapath at plan time)
    pl4 = repro.plan(n=4096, t=4, v=45)
    za4 = jnp.asarray(
        rng.integers(0, 1 << 45, size=(batch, n, pl4.config.seg_count))
    )
    zb4 = jnp.asarray(
        rng.integers(0, 1 << 45, size=(batch, n, pl4.config.seg_count))
    )
    us4 = _time_plan(pl4, za4, zb4)
    out.append(
        (
            "tableVI_measured_polymul_t4_v45",
            us4,
            f"wide digit-split datapath; t6/t4 time ratio={us/us4:.2f} "
            f"(paper: t=6 wins on ABP/power)",
        )
    )
    # beyond-paper (§Perf P4): fused cascade HBM-traffic model.  Unfused:
    # NTT(a) out, NTT(b) out, product in x2/out, iNTT in = 6 HBM crossings
    # of (rows, n) int64 per channel beyond inputs/outputs; fused kernel
    # keeps everything VMEM-resident: only a/b in + p out cross HBM.
    row_bytes = 8 * n
    unfused = 8 * row_bytes  # 2 in + 2 ntt-out + prod(w+r via 2 reads) + intt in/out
    fused = 3 * row_bytes  # a in, b in, p out
    out.append(
        (
            "perfP4_fused_cascade_traffic",
            0.0,
            f"unfused={unfused/1024:.0f}KiB/row-channel fused={fused/1024:.0f}KiB "
            f"reduction={unfused/fused:.1f}x (plus the paper's zero-permutation property)",
        )
    )
    return out


# --------------------------------------------------------------------------
# CI smoke (the `bench-smoke` job): small preset, interpret mode
# --------------------------------------------------------------------------


def diff_against_baseline(rec: dict, baseline: dict) -> list[str]:
    """Regression diff of the structural columns (op counts + modeled
    HBM bytes; wall-clock is machine-dependent and NOT gated).  A metric
    may improve or hold; growing past the committed baseline fails."""
    fails = []
    for bk, r in rec["backends"].items():
        base = baseline.get("backends", {}).get(bk)
        if not base:
            continue
        for key in ("hbm_bytes", "kernel_launches"):
            if r[key] > base[key]:
                fails.append(
                    f"baseline regression [{bk}].{key}: {r[key]} > "
                    f"committed {base[key]}"
                )
    for scope in ("cost_model", "cost_model_n256"):
        for schedule, c in rec.get(scope, {}).items():
            base = baseline.get(scope, {}).get(schedule)
            if not base:
                continue
            for key in (
                "sublane_stages", "reduction_ops_fwd", "reduction_ops_inv",
            ):
                if c[key] > base[key]:
                    fails.append(
                        f"baseline regression [{scope}.{schedule}].{key}: "
                        f"{c[key]} > committed {base[key]}"
                    )
    for name, c in rec.get("big_n", {}).items():
        base = baseline.get("big_n", {}).get(name)
        if not base:
            continue
        for key in ("hbm_bytes", "kernel_launches", "tile_bytes", "depth"):
            if c[key] > base[key]:
                fails.append(
                    f"baseline regression [big_n.{name}].{key}: "
                    f"{c[key]} > committed {base[key]}"
                )
    return fails


def run_ci_smoke(out_path: str, n: int = 64, t: int = 3, v: int = 30,
                 batch: int = 2, baseline_path: str | None = None,
                 compiled: bool = False) -> dict:
    """Benchmark the small preset across all four backends and BOTH
    stage schedules, write the result JSON, and enforce:

    * fusion — the fused-e2e path moves fewer HBM bytes than the
      three-kernel path, traces to exactly 1 pallas_call, and every
      (backend, schedule) pair is bit-exact vs the bigint oracle;
    * the launch counts and reduction-op counts the models claim match
      the traced computations;
    * lane alignment — the four-step schedule has 0 sub-128-lane stages,
      here and at the n=256 operating preset (structural, no execution);
    * lazy reduction — modeled reduction ops per transform are >= 2x
      below the strict butterfly count whenever the lazy window is on;
    * optionally, no op-count/HBM-byte regression vs a committed
      baseline JSON (``BENCH_seed.json``).

    With ``compiled=True`` every (backend, schedule) row additionally
    records ``compiled_us_per_poly`` + ``compile_s`` from the AOT chain
    (``jax.jit(...).lower(...).compile()`` — a real XLA:CPU compile
    today; see ``repro.tune.sweep.measure_plan``) beside the interpret
    ``us_per_poly``.  Wall-clock columns stay un-gated either way.
    """
    p = params_mod.make_params(n=n, t=t, v=v)
    rng = random.Random(7)
    a = [rng.randrange(p.q) for _ in range(p.n)]
    b = [rng.randrange(p.q) for _ in range(p.n)]
    oracle = pm.oracle_multiply(a, b, p)
    rng_np = np.random.default_rng(7)
    za = jnp.asarray(
        rng_np.integers(0, 1 << v, size=(batch, n, p.plan.seg_count))
    )
    zb = jnp.asarray(
        rng_np.integers(0, 1 << v, size=(batch, n, p.plan.seg_count))
    )
    rec = {
        "preset": {"n": n, "t": t, "v": v, "batch": batch},
        "mode": "compiled" if jax.default_backend() == "tpu" else "interpret",
        "compiled_mode": compiled,
        "backends": {},
    }
    if compiled:
        from repro.tune import sweep as sweep_mod
    for bk in ops_mod.BACKENDS:
        model = ops_mod.hbm_traffic_model(p, rows=batch, backend=bk)
        r = {
            "hbm_bytes": model["hbm_bytes"],
            "kernel_launches": model["kernel_launches"],
            # structural ground truth: pallas_call eqns in the traced
            # computation; must equal the model's claim or the gate fails
            "traced_pallas_calls": ops_mod.count_pallas_launches(
                p, backend=bk, rows=batch
            ),
            "intermediate_bytes": model["intermediate_bytes"],
            "schedules": {},
        }
        for schedule in CONCRETE_SCHEDULES:
            pl = _plan(p, backend=bk, schedule=schedule)
            us = _time_plan(pl, za, zb, iters=1)
            exact = repro.polymul_ints(pl, a, b) == oracle
            rs = {
                "us_per_poly": us,
                "bit_exact_vs_oracle": exact,
            }
            if compiled:
                m = sweep_mod.measure_plan(pl, za, zb, iters=3, warmup=1)
                rs["compiled_us_per_poly"] = (
                    m["us_per_poly"] if m["mode"] == "compiled" else None
                )
                rs["compile_s"] = m["compile_s"]
            r["schedules"][schedule] = rs
        rec["backends"][bk] = r
    rec["cost_model"] = _cost_model_record(p)
    # the lane-alignment claim is about the operating point (n >= 256
    # where the tile reaches the full 128-lane width): record it
    # structurally — models + traced kernels, no interpret-mode execution
    rec["cost_model_n256"] = _cost_model_record(
        params_mod.make_params(n=256, t=6, v=30)
    )
    # big-n point (PR 7): the n=4096 four-step operating size through the
    # fused-e2e Pallas path, bit-exact vs the host-NTT bigint oracle, with
    # the frozen ScheduleSpec's VMEM tile recorded so tiling regressions
    # show up in the baseline diff (interpret mode: one execution, t=2
    # keeps the smoke under a few seconds)
    p4k = params_mod.make_params(n=4096, t=2, v=30)
    pl4k = _plan(p4k, backend="pallas_fused_e2e", schedule="four_step")
    spec4k = pl4k.config.schedule
    rng4k = random.Random(11)
    a4 = [rng4k.randrange(p4k.q) for _ in range(p4k.n)]
    b4 = [rng4k.randrange(p4k.q) for _ in range(p4k.n)]
    t0 = time.perf_counter()
    got4k = repro.polymul_ints(pl4k, a4, b4)
    us4k = (time.perf_counter() - t0) * 1e6
    m4k = ops_mod.hbm_traffic_model(p4k, rows=1, backend="pallas_fused_e2e")
    rec["big_n"] = {
        "n4096_fused_e2e_four_step": {
            "schedule": str(spec4k),
            "depth": spec4k.depth,
            "row_blk": spec4k.row_blk,
            "tile_bytes": spec4k.tile_bytes,
            "vmem_budget": spec4k.vmem_budget,
            "hbm_bytes": m4k["hbm_bytes"],
            "kernel_launches": m4k["kernel_launches"],
            "us_per_poly": us4k,
            "bit_exact_vs_oracle": got4k == pm.oracle_multiply(a4, b4, p4k),
        }
    }
    fused = rec["backends"]["pallas_fused_e2e"]
    three = rec["backends"]["pallas"]
    rec["fused_e2e_hbm_reduction_vs_pallas"] = (
        three["hbm_bytes"] / fused["hbm_bytes"]
    )
    failures = []
    if fused["hbm_bytes"] >= three["hbm_bytes"]:
        failures.append(
            f"fused-e2e moves {fused['hbm_bytes']} HBM bytes but the "
            f"three-kernel path moves {three['hbm_bytes']}: fusion regressed"
        )
    for bk, r in rec["backends"].items():
        if r["traced_pallas_calls"] != r["kernel_launches"]:
            failures.append(
                f"backend {bk}: traffic model claims "
                f"{r['kernel_launches']} kernel launches but the traced "
                f"computation contains {r['traced_pallas_calls']} "
                f"pallas_calls — the model no longer matches the dispatch"
            )
        for schedule, rs in r["schedules"].items():
            if not rs["bit_exact_vs_oracle"]:
                failures.append(
                    f"backend {bk} / schedule {schedule} is not bit-exact "
                    "vs the bigint oracle"
                )
    if fused["traced_pallas_calls"] != 1:
        failures.append(
            f"fused-e2e path traces to {fused['traced_pallas_calls']} "
            "pallas_calls, expected exactly 1: the e2e fusion was undone"
        )
    for scope in ("cost_model", "cost_model_n256"):
        cm = rec[scope]
        if cm["four_step"]["sublane_stages"] != 0:
            failures.append(
                f"{scope}: four_step schedule has "
                f"{cm['four_step']['sublane_stages']} sub-128-lane stages, "
                "expected 0 — the lane-aligned schedule regressed"
            )
        for schedule, c in cm.items():
            if c["traced_selects_fwd"] != c["reduction_ops_fwd"]:
                failures.append(
                    f"{scope}.{schedule}: model claims "
                    f"{c['reduction_ops_fwd']} reduction ops but the traced "
                    f"kernel contains {c['traced_selects_fwd']} selects"
                )
            if (
                c["lazy_window"] is not None
                and 2 * c["reduction_ops_fwd"] > c["strict_reduction_ops"]
            ):
                failures.append(
                    f"{scope}.{schedule}: lazy reduction saves < 2x "
                    f"({c['reduction_ops_fwd']} vs strict "
                    f"{c['strict_reduction_ops']})"
                )
    for name, c in rec["big_n"].items():
        if not c["bit_exact_vs_oracle"]:
            failures.append(
                f"big_n {name} is not bit-exact vs the bigint oracle"
            )
        if c["tile_bytes"] > c["vmem_budget"]:
            failures.append(
                f"big_n {name}: frozen schedule tile ({c['tile_bytes']} B) "
                f"exceeds the VMEM budget ({c['vmem_budget']} B)"
            )
    if baseline_path:
        with open(baseline_path) as f:
            failures += diff_against_baseline(rec, json.load(f))
    rec["failures"] = failures
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ci-smoke", action="store_true",
                    help="small-preset smoke for the bench-smoke CI job")
    ap.add_argument("--out", default="BENCH_ci.json",
                    help="JSON output path for --ci-smoke")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline JSON (BENCH_seed.json) to "
                         "diff op counts / HBM bytes against")
    ap.add_argument("--compiled", action="store_true",
                    help="with --ci-smoke: also record AOT compiled "
                         "wall-clock (compiled_us_per_poly) per "
                         "backend/schedule beside the interpret numbers")
    ap.add_argument("--row-blk", type=int, default=None,
                    help="kernel tile rows per grid step "
                         "(None = per-kernel default)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.ci_smoke:
        rec = run_ci_smoke(
            args.out, baseline_path=args.baseline, compiled=args.compiled
        )
        for msg in rec["failures"]:
            print(f"[FAIL] {msg}", file=sys.stderr)
        return 1 if rec["failures"] else 0
    print("name,us_per_call,derived")
    for name, us, derived in run(row_blk=args.row_blk):
        print(f'{name},{us:.1f},"{derived}"')
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
