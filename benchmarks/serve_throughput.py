"""Serving throughput: the batched PolymulEngine vs the unbatched
per-request loop.

The paper positions the feed-forward PaReNTT datapath for "low latency
and high sample rate"; this benchmark measures the sample-rate half on
the serving layer: R requests stream through (a) a sequential loop of
jitted single-request ``repro.polymul`` calls and (b) the
shape-bucketed batching engine at a fixed slot count.  Reported:
requests/s for both, the batched/loop speedup, and the engine's
p50/p99 submit-to-result latency plus padding/dispatch accounting.
``--deadline-ms`` and ``--fault-rate`` turn the same driver into a
degraded-mode benchmark: goodput (requests resolved with a value —
deadline met, retries survived) is reported alongside raw req/s, with
the shed/retry/failure counters that explain the gap.

``--ci-smoke`` is the ``serve-smoke`` CI gate: it runs the small
preset at batch 8, verifies the engine's mixed-preset stream bit-exact
against the eager plan executor, MERGES a ``"serve"`` record into the
BENCH_ci.json artifact written by ``benchmarks/polymul_e2e.py``, and
exits non-zero if batched throughput falls below the unbatched loop
(the existence proof of the batching win — off-TPU both sides run the
same jnp datapath, so dispatch amortization is all that is measured).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

import repro
from repro import api, compile_cache
from repro.serve.crypto_engine import PolymulEngine


def _requests(pl, count: int, rng) -> list:
    shape = (pl.n, pl.config.seg_count)
    return [
        (
            rng.integers(0, 1 << pl.v, size=shape),
            rng.integers(0, 1 << pl.v, size=shape),
        )
        for _ in range(count)
    ]


def _time_loop(pl, reqs, repeats: int) -> float:
    """Best-of-N wall seconds for the sequential per-request loop
    through the shared jitted executor (the unbatched baseline)."""
    za0, zb0 = jnp.asarray(reqs[0][0]), jnp.asarray(reqs[0][1])
    jax.block_until_ready(api.execute(pl, za0, zb0))  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for za, zb in reqs:
            jax.block_until_ready(
                api.execute(pl, jnp.asarray(za), jnp.asarray(zb))
            )
        best = min(best, time.perf_counter() - t0)
    return best


def _time_engine(pl, reqs, batch: int, repeats: int, *,
                 deadline_s=None, fault_rate: float = 0.0, seed: int = 7,
                 span_log=None):
    """(best wall seconds, latency ms array over successful futures,
    stats, traces, goodput count) for the batching engine serving the
    same request list — optionally under per-request deadlines and a
    Bernoulli dispatch-fault rate (the engine's retry/shed machinery
    then shows up in the stats and the goodput gap).  ``span_log``
    turns on request tracing (the overhead under test in
    :func:`tracing_overhead`)."""
    eng = PolymulEngine(batch_slots=batch, backoff_base_s=1e-4,
                        span_log=span_log)
    shape = (pl.n, pl.config.seg_count)
    eng.submit(pl, np.zeros(shape, np.int64), np.zeros(shape, np.int64))
    eng.run_until_idle()  # compile the padded-batch executable
    if fault_rate > 0.0:
        from repro.serve.faults import FaultInjector, FaultRule

        FaultInjector(
            [FaultRule("raise", rate=fault_rate)], seed=seed
        ).install(eng)
    best, lat, stats, good = float("inf"), None, {}, 0
    for _ in range(repeats):
        eng.reset_stats()
        t0 = time.perf_counter()
        futs = [
            eng.submit(pl, za, zb, deadline=deadline_s)
            for za, zb in reqs
        ]
        eng.run_until_idle()
        wall = time.perf_counter() - t0
        ok = [f for f in futs if f.exception() is None]
        if wall < best:
            best = wall
            lat = np.array([f.latency_s for f in ok]) * 1e3
            stats = dict(eng.stats)
            good = len(ok)
    return best, lat, stats, eng.trace_count, good


def bench(n: int, t: int, v: int, *, batch: int, requests: int,
          repeats: int, seed: int = 7, deadline_ms: float = 0.0,
          fault_rate: float = 0.0) -> dict:
    rng = np.random.default_rng(seed)
    pl = repro.plan(n=n, t=t, v=v)
    reqs = _requests(pl, requests, rng)
    loop_s = _time_loop(pl, reqs, repeats)
    eng_s, lat, stats, traces, good = _time_engine(
        pl, reqs, batch, repeats, seed=seed,
        deadline_s=deadline_ms / 1e3 if deadline_ms > 0 else None,
        fault_rate=fault_rate,
    )
    rec = {
        "preset": {"n": n, "t": t, "v": v},
        "batch_slots": batch,
        "requests": requests,
        "seed": seed,
        "loop_rps": requests / loop_s,
        "batched_rps": requests / eng_s,
        "batched_vs_loop_speedup": loop_s / eng_s,
        # goodput: requests that resolved with a value (deadline met,
        # retries survived) per second — equals batched_rps when no
        # deadline/fault knobs are set
        "goodput_rps": good / eng_s,
        "latency_p50_ms": (
            float(np.percentile(lat, 50)) if lat.size else float("nan")
        ),
        "latency_p99_ms": (
            float(np.percentile(lat, 99)) if lat.size else float("nan")
        ),
        "dispatches": stats["dispatches"],
        "padded_slots": stats["padded_slots"],
        "jit_traces": traces,
    }
    if deadline_ms > 0 or fault_rate > 0:
        rec["deadline_ms"] = deadline_ms
        rec["fault_rate"] = fault_rate
        rec["shed"] = stats["shed"]
        rec["failed"] = stats["failed"]
        rec["retried"] = stats["retried"]
        rec["dispatch_failures"] = stats["dispatch_failures"]
    return rec


def mixed_stream_check(requests: int = 12, seed: int = 3) -> dict:
    """Serve BOTH paper presets interleaved through one engine and
    verify every result bit-exact against the eager plan executor
    (itself oracle-gated by the tier-1 suite); also assert one jit
    trace per distinct config."""
    rng = np.random.default_rng(seed)
    eng = PolymulEngine(batch_slots=4)
    plans = [eng.plan(n=64, t=3, v=30), eng.plan(n=32, t=4, v=45)]
    reqs = []
    for i in range(requests):
        pl = plans[i % 2]
        za, zb = _requests(pl, 1, rng)[0]
        reqs.append((pl, za, zb))
    futs = [eng.submit(pl, za, zb) for pl, za, zb in reqs]
    eng.run_until_idle()
    exact = all(
        np.array_equal(
            f.result(),
            np.asarray(repro.polymul(pl, jnp.asarray(za), jnp.asarray(zb))),
        )
        for f, (pl, za, zb) in zip(futs, reqs)
    )
    return {
        "requests": requests,
        "configs": len({api.plan_key(pl) for pl, _, _ in reqs}),
        "bit_exact": bool(exact),
        "jit_traces": eng.trace_count,
    }


def tracing_overhead(n: int, t: int, v: int, *, batch: int,
                     requests: int, repeats: int, seed: int = 7,
                     span_log_path: str | None = None,
                     max_overhead: float = 0.05) -> dict:
    """Closed-loop throughput with request tracing ON vs OFF through the
    same engine configuration — the ``obs-smoke`` CI gate that keeps the
    span log an always-on-able tool rather than a debug mode.

    Both sides are best-of-``repeats`` so scheduler noise has to be
    reproducibly in the tracing path to fail the gate.  ``overhead`` is
    ``1 - traced_rps / plain_rps`` (negative means tracing measured
    faster, i.e. pure noise); the gate fails when it exceeds
    ``max_overhead``.
    """
    from repro import obs

    rng = np.random.default_rng(seed)
    pl = repro.plan(n=n, t=t, v=v)
    reqs = _requests(pl, requests, rng)
    plain_s, _, _, _, _ = _time_engine(pl, reqs, batch, repeats, seed=seed)
    span_log = obs.SpanLog(span_log_path)
    traced_s, _, _, _, _ = _time_engine(pl, reqs, batch, repeats,
                                        seed=seed, span_log=span_log)
    span_log.flush()
    cons = obs.conservation(span_log.records)
    overhead = 1.0 - (plain_s / traced_s)
    failures = list(cons["violations"])
    if overhead > max_overhead:
        failures.append(
            f"tracing overhead {overhead:.1%} exceeds the "
            f"{max_overhead:.0%} budget: {requests / traced_s:.1f} "
            f"traced vs {requests / plain_s:.1f} plain req/s"
        )
    return {
        "preset": {"n": n, "t": t, "v": v},
        "batch_slots": batch,
        "requests": requests,
        "repeats": repeats,
        "seed": seed,
        "plain_rps": requests / plain_s,
        "traced_rps": requests / traced_s,
        "overhead": overhead,
        "max_overhead": max_overhead,
        "spans": cons["spans"],
        "span_violations": cons["violations"],
        "failures": failures,
    }


def run_ci_smoke(out_path: str, *, batch: int = 8, requests: int = 64,
                 repeats: int = 3) -> dict:
    rec = bench(64, 3, 30, batch=batch, requests=requests, repeats=repeats)
    rec["mixed_stream"] = mixed_stream_check()
    failures = []
    if rec["batched_vs_loop_speedup"] < 1.0:
        failures.append(
            f"batched engine is SLOWER than the unbatched loop at batch "
            f"{batch}: {rec['batched_rps']:.1f} vs {rec['loop_rps']:.1f} "
            f"req/s — the batching win regressed"
        )
    if not rec["mixed_stream"]["bit_exact"]:
        failures.append("mixed-preset stream is not bit-exact vs polymul")
    if rec["mixed_stream"]["jit_traces"] != rec["mixed_stream"]["configs"]:
        failures.append(
            f"mixed stream traced {rec['mixed_stream']['jit_traces']} "
            f"times for {rec['mixed_stream']['configs']} configs — the "
            f"plan-bucket cache regressed"
        )
    rec["failures"] = failures
    # merge into the bench-smoke artifact (polymul_e2e writes it first
    # in CI; standalone runs create a serve-only record)
    doc = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    doc["serve"] = rec
    doc["failures"] = doc.get("failures", []) + failures
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(rec, indent=1))
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ci-smoke", action="store_true",
                    help="small-preset gate for the serve-smoke CI step")
    ap.add_argument("--out", default="BENCH_ci.json",
                    help="JSON artifact to merge the 'serve' record into")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--t", type=int, default=6)
    ap.add_argument("--v", type=int, default=30)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline; goodput counts only "
                         "deadline-met requests (0 = no deadline)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="Bernoulli transient-raise rate per dispatch "
                         "via the fault injector (0 = no faults)")
    ap.add_argument("--seed", type=int, default=7,
                    help="seeds request payloads and the fault schedule; "
                         "stamped into output records")
    ap.add_argument("--tracing-overhead", action="store_true",
                    help="measure tracing-on vs tracing-off throughput "
                         "and gate the overhead (the obs-smoke CI step); "
                         "merges a 'tracing_overhead' record into --out")
    ap.add_argument("--span-log", default=None, metavar="FILE",
                    help="JSONL span log path for --tracing-overhead")
    ap.add_argument("--max-overhead", type=float, default=0.05,
                    help="tracing overhead budget as a fraction "
                         "(--tracing-overhead)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.tracing_overhead:
        rec = tracing_overhead(
            args.n, args.t, args.v, batch=args.batch,
            requests=args.requests, repeats=args.repeats, seed=args.seed,
            span_log_path=args.span_log, max_overhead=args.max_overhead,
        )
        print(json.dumps(rec, indent=1))
        doc = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                doc = json.load(f)
        doc["tracing_overhead"] = rec
        doc["failures"] = doc.get("failures", []) + rec["failures"]
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
        for msg in rec["failures"]:
            print(f"[FAIL] {msg}", file=sys.stderr)
        return 1 if rec["failures"] else 0
    if args.ci_smoke:
        rec = run_ci_smoke(args.out, batch=args.batch,
                           requests=args.requests, repeats=args.repeats)
        for msg in rec["failures"]:
            print(f"[FAIL] {msg}", file=sys.stderr)
        return 1 if rec["failures"] else 0
    rec = bench(args.n, args.t, args.v, batch=args.batch,
                requests=args.requests, repeats=args.repeats, seed=args.seed,
                deadline_ms=args.deadline_ms, fault_rate=args.fault_rate)
    print(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
