"""Crypto serving launcher: synthetic mixed-preset polymul traffic
through the batching :class:`repro.serve.crypto_engine.PolymulEngine`.

    PYTHONPATH=src python -m repro.launch.serve_crypto --requests 32 --slots 8

The traffic generator interleaves heterogeneous presets (default: the
paper's two operating points at its ring size n=4096, t=6 x v=30 and
t=4 x v=45) and draws Poisson arrivals at ``--rate`` requests/s (0 =
closed loop: everything arrives at t=0).  Requests are bucketed by plan
config and served in padded micro-batches; the report shows throughput,
latency percentiles and the bucket/trace accounting.  Each preset is
compiled before traffic starts (``PolymulEngine.warmup``).  Compiling
one n=4096 preset for a TPU takes about two minutes, so a quick try on
the CPU passes small presets, e.g. ``--presets 64:3:30,64:4:45``.

The exit code is non-zero when any request failed (a compile failure,
or a dispatch that ran out of retries) or when a breaker opened, so a
run that served its traffic on a fallback backend does not pass.

Mesh mode: ``--mesh 2x2`` shards dispatches over a (data, model) host
mesh — run under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(or on real multi-device hardware).  int64-width presets only.

Robustness knobs (PR 8): ``--deadline-ms`` sheds late requests with a
typed error, ``--max-pending`` bounds the queue (backpressure), and
``--async-dispatch`` serves from the engine's background dispatcher
thread; the summary reports goodput and the shed/retry/breaker
counters next to throughput.

Observability (PR 10): ``--span-log FILE`` traces every request into a
JSONL span log (inspect with ``repro.launch.obs_report``), ``--json``
emits the summary as a machine-readable record with the ``--seed``
stamped in, so a run is reproducible from its own output.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax

from repro import api, compile_cache, obs
from repro.serve.crypto_engine import PolymulEngine


def parse_preset(spec: str) -> dict:
    """'n:t:v' (e.g. '64:3:30') -> plan kwargs."""
    try:
        n, t, v = (int(x) for x in spec.split(":"))
    except ValueError as e:
        raise SystemExit(f"bad --presets entry {spec!r}: want n:t:v") from e
    return {"n": n, "t": t, "v": v}


def build_mesh(spec: str):
    """'DxM' -> Mesh over the first D*M host devices as (data, model)."""
    from jax.sharding import Mesh

    d, m = (int(x) for x in spec.lower().split("x"))
    devs = jax.devices()
    if len(devs) < d * m:
        raise SystemExit(
            f"--mesh {spec} needs {d * m} devices but only {len(devs)} "
            f"are visible; set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={d * m} for a host mesh"
        )
    return Mesh(np.array(devs[: d * m]).reshape(d, m), ("data", "model"))


def make_traffic(plans, requests: int, rate: float, rng) -> list:
    """[(arrival_s, plan, za, zb)] — presets interleaved round-robin,
    exponential inter-arrival gaps at ``rate`` req/s (0 = all at t=0)."""
    out, now = [], 0.0
    for i in range(requests):
        pl = plans[i % len(plans)]
        if rate > 0:
            now += float(rng.exponential(1.0 / rate))
        shape = (pl.n, pl.config.seg_count)
        out.append(
            (
                now,
                pl,
                rng.integers(0, 1 << pl.v, size=shape),
                rng.integers(0, 1 << pl.v, size=shape),
            )
        )
    return out


def drive(eng: PolymulEngine, traffic, *, deadline_s=None) -> list:
    """Open-loop event pump: submit each request at its arrival time,
    stepping the engine whenever work is pending (with the background
    dispatcher running, submission is all this loop does).  Returns
    futures."""
    futs = []
    i = 0
    t0 = time.perf_counter()
    while i < len(traffic) or eng.pending():
        now = time.perf_counter() - t0
        while i < len(traffic) and traffic[i][0] <= now:
            _, pl, za, zb = traffic[i]
            futs.append(eng.submit(pl, za, zb, deadline=deadline_s))
            i += 1
        if eng.running:
            time.sleep(1e-3)
        elif eng.pending():
            eng.step()
        elif i < len(traffic):
            time.sleep(min(traffic[i][0] - now, 1e-3))
    if eng.running:
        eng.run_until_idle()
    return futs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8,
                    help="fixed batch slots per dispatch")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate in requests/s (0 = closed loop)")
    ap.add_argument("--presets", default="4096:6:30,4096:4:45",
                    help="comma-separated n:t:v presets, interleaved")
    ap.add_argument("--mesh", default="",
                    help="'DxM' (data x model) host mesh, e.g. 2x2")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--donate", action="store_true",
                    help="donate operand buffers to XLA per dispatch")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline; late requests are shed "
                         "with DeadlineExceededError (0 = none)")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="bound the submission queue (0 = unbounded); "
                         "submit then blocks for space")
    ap.add_argument("--async-dispatch", action="store_true",
                    help="serve from the background dispatcher thread "
                         "instead of stepping inline")
    ap.add_argument("--span-log", default=None, metavar="FILE",
                    help="trace every request into this JSONL span log "
                         "(inspect with repro.launch.obs_report)")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as a JSON record (seed "
                         "stamped in) instead of text")
    args = ap.parse_args(argv)

    compile_cache.enable()
    mesh = build_mesh(args.mesh) if args.mesh else None
    span_log = obs.SpanLog(args.span_log) if args.span_log else None
    eng = PolymulEngine(batch_slots=args.slots, mesh=mesh,
                        donate=args.donate,
                        max_pending=args.max_pending or None,
                        span_log=span_log)
    plans = [eng.plan(**parse_preset(s)) for s in args.presets.split(",")]
    rng = np.random.default_rng(args.seed)

    # compile every config before traffic, so the timed run measures
    # serving, and a config that cannot compile stops the run here
    for pl in plans:
        eng.warmup(pl)

    if args.async_dispatch:
        eng.start()
    traffic = make_traffic(plans, args.requests, args.rate, rng)
    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms > 0 else None
    t0 = time.perf_counter()
    futs = drive(eng, traffic, deadline_s=deadline_s)
    wall = time.perf_counter() - t0
    if args.async_dispatch:
        eng.stop()

    snap = eng.snapshot()
    ok = [f for f in futs if f.exception() is None]
    rc = 1 if snap["failed"] or snap["breaker_opened"] else 0
    served = snap["served"]
    if span_log is not None:
        span_log.flush()
    if args.json:
        lat = np.array([f.latency_s for f in ok]) * 1e3
        record = {
            "seed": args.seed,
            "requests": len(futs),
            "rate_rps": args.rate,
            "presets": args.presets,
            "wall_s": wall,
            "served_rps": served / wall,
            "goodput_rps": len(ok) / wall,
            "latency_p50_ms": (
                float(np.percentile(lat, 50)) if lat.size else None
            ),
            "latency_p99_ms": (
                float(np.percentile(lat, 99)) if lat.size else None
            ),
            "jit_traces": eng.trace_count,
            "span_log": args.span_log,
            "snapshot": snap,
        }
        print(json.dumps(record, indent=1))
        return rc
    print(f"served {served}/{len(futs)} requests in {wall:.3f}s "
          f"({served / wall:.1f} req/s, goodput {len(ok) / wall:.1f} "
          f"req/s) [seed={args.seed}]")
    if ok:
        lat = np.array([f.latency_s for f in ok]) * 1e3
        print(f"latency p50={np.percentile(lat, 50):.2f}ms "
              f"p99={np.percentile(lat, 99):.2f}ms")
    print(f"dispatches={snap['dispatches']} "
          f"padded_slots={snap['padded_slots']} "
          f"jit_traces={eng.trace_count} "
          f"buckets={len({api.plan_key(p) for p in plans})}")
    print(f"shed={snap['shed']} retried={snap['retried']} "
          f"failed={snap['failed']} rejected={snap['rejected']} "
          f"dispatch_failures={snap['dispatch_failures']} "
          f"breaker_opened={snap['breaker_opened']} "
          f"breaker_recovered={snap['breaker_recovered']}")
    if mesh is not None:
        print(f"mesh axes={dict(mesh.shape)}")
    if args.span_log:
        print(f"span log: {args.span_log}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
