#!/usr/bin/env python3
"""Find the knee of a configuration once, by one sweep in one process:
the highest open-loop rate at which the backlog does not grow through
the window.  Not part of the benchmark's runs; its table is recorded in
``PERF.md`` and the open cells' rates are set from it.

    python3 bench/sweep.py --config <config> --seed <n> --seconds <s>

It first runs the closed loop of ``traffic/closed32.json`` and takes
its products/s as R, then offers Poisson traffic (one product per
arrival) at fractions of R, rising.  A rate holds when at least 99% of
the requests due in the window complete inside it and the p99 latency
of the second half of the window is within 1.2x the first half's.  In a
window where the whole process stalled (the generator ran over 50 ms
late) the halves' p99 measure the stall, not the backlog, so such a
rate is judged on completion alone.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import loadgen, run  # noqa: E402

FRACTIONS = (0.5, 0.6, 0.7, 0.75, 0.8, 0.85, 0.9, 0.95, 1.0, 1.05, 1.1)


def p99(ms) -> float:
    return float(np.percentile(ms, 99)) if len(ms) else float("nan")


def measure(served, traffic, seconds, seed) -> dict:
    w = loadgen.drive(traffic, served.submit, seconds, np.random.default_rng(seed),
                      lambda req, value: None)
    served.offset += len(w.requests)
    half = w.t0 + seconds / 2
    done = [r for r in w.requests if r.t_done is not None]
    first = [(r.t_done - r.t_due) * 1e3 for r in done if r.t_due < half]
    second = [(r.t_done - r.t_due) * 1e3 for r in done if r.t_due >= half]
    lat = w.latencies_ms()
    return {
        "offered": len(w.requests) / seconds,
        "products_per_s": w.completed_in_window() / seconds,
        "completed_share": w.completed_in_window() / max(len(w.requests), 1),
        "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
        "p99_ms": p99(lat),
        "p99_ratio": p99(second) / p99(first),
        "lateness_max_ms": float(w.lateness_ms().max()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    if run.chip_devices(1) is None:
        print("sweep: no TPU", file=sys.stderr)
        return 2
    cfg = json.loads((run.ROOT / "bench" / "configs" / f"{args.config}.json").read_text())
    closed = json.loads((run.ROOT / "bench" / "traffic" / "closed32.json").read_text())

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    served = run.serve(cfg, args.seed, log=log)
    rows = []
    try:
        base = measure(served, closed, args.seconds, args.seed)
        log(f"closed32: {base}")
        for f in FRACTIONS:
            rate = f * base["products_per_s"]
            row = {"fraction": f, "rate_per_s": rate,
                   **measure(served, {"kind": "open", "rate_per_s": rate, "burst": 1},
                             args.seconds, args.seed + len(rows) + 1)}
            stalled = row["lateness_max_ms"] > 50
            row["holds"] = (row["completed_share"] >= 0.99
                            and (stalled or row["p99_ratio"] <= 1.2))
            log(json.dumps(row))
            rows.append(row)
    finally:
        served.eng.stop(drain=True, timeout=120)
    knee = None  # the last rate that holds below the first that does not
    for r in rows:
        if not r["holds"]:
            break
        knee = r["rate_per_s"]
    print(json.dumps({"config": args.config, "closed32": base, "rows": rows,
                      "knee_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
