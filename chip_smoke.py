#!/usr/bin/env python3
"""Smoke run of the served polymul path on a TPU: the quickest proof
that the system still starts on the chip.

    python3 chip_smoke.py                # one chip, both paper presets
    python3 chip_smoke.py --four-chips   # the (data=2, model=2) mesh only

One chip: a ``PolymulEngine`` with 8 batch slots plans the paper's two
operating points at n=4096 with ``backend="auto"`` (t=6 x v=30, the
int64 width, and t=4 x v=45, the wide width), compiles each once,
serves 64 seeded random products per preset, and checks one full batch
per preset bit-exact against the host bigint oracle
(``repro.core.polymul.oracle_multiply``).  The engine's counters must
show no failure, retry or breaker, and every bucket must have served on
the backend its plan named.

``--four-chips``: an engine in mesh mode over a (data=2, model=2) mesh
of four devices at n=4096/t=6/v=30, so each ``model`` shard holds 3 RNS
channels.  The plan's tables must sit one channel slice per shard; every
product must equal a one-device engine's on ``devices[0]``, and one
batch must equal the oracle.

The script exits non-zero, and prints no result line, when JAX finds no
TPU or when any check fails.  Its last line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Throughput and latency lines are a smoke reading, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SLOTS = 8
PRODUCTS = 64
PRESETS = ((4096, 6, 30), (4096, 4, 45))
MESH_PRESET = (4096, 6, 30)


def say(msg: str) -> None:
    print(msg, flush=True)


def random_operands(pl, count: int, rng: random.Random):
    """``count`` seeded products: coefficient lists < q and their
    ``(n, S)`` segment arrays on the host."""
    from repro.core import bigint

    cfg = pl.config
    out = []
    for _ in range(count):
        a = [rng.randrange(pl.q) for _ in range(cfg.n)]
        b = [rng.randrange(pl.q) for _ in range(cfg.n)]
        out.append((
            a, b,
            bigint.ints_to_limbs(a, cfg.v, cfg.seg_count),
            bigint.ints_to_limbs(b, cfg.v, cfg.seg_count),
        ))
    return out


def check_oracle(pl, ops, results) -> int:
    """Bit-exact check of ``results`` against the host oracle; returns
    the number of products checked."""
    from repro import api
    from repro.core import polymul

    for i, ((a, b, _, _), limbs) in enumerate(zip(ops, results)):
        if api.from_limbs(pl, limbs) != polymul.oracle_multiply(a, b, pl.params):
            raise AssertionError(f"{_name(pl)}: product {i} differs from the oracle")
    return len(results)


def _name(pl) -> str:
    c = pl.config
    return f"n={c.n} t={c.t} v={c.v}"


def serve(eng, pl, ops):
    """Submit every product, drain the engine, return the result arrays
    and the wall seconds."""
    t0 = time.perf_counter()
    futs = [eng.submit(pl, za, zb) for _, _, za, zb in ops]
    eng.run_until_idle()
    out = [f.result() for f in futs]
    return out, time.perf_counter() - t0


def check_counters(eng, plans) -> None:
    """No failure, retry or breaker, and each bucket served on the
    backend its plan named."""
    snap = eng.snapshot()
    bad = {k: snap[k] for k in ("failed", "retried", "dispatch_failures",
                                "breaker_opened", "shed") if snap[k]}
    if bad:
        raise AssertionError(f"engine counters not clean: {bad}")
    from repro.serve.crypto_engine import _bucket_key_str

    for pl in plans:
        key = _bucket_key_str(pl.config)
        got = snap["bucket_backends"].get(key)
        if got != pl.config.backend:
            raise AssertionError(
                f"{key}: served on {got!r}, plan named {pl.config.backend!r}"
            )
    say(f"counters: failed={snap['failed']} retried={snap['retried']} "
        f"dispatch_failures={snap['dispatch_failures']} "
        f"breaker_opened={snap['breaker_opened']} "
        f"backends={snap['bucket_backends']}")


def one_chip(seed: int) -> None:
    from repro.serve.crypto_engine import PolymulEngine

    rng = random.Random(seed)
    eng = PolymulEngine(batch_slots=SLOTS)
    plans = []
    for n, t, v in PRESETS:
        pl = eng.plan(n=n, t=t, v=v, backend="auto")
        plans.append(pl)
        secs = eng.warmup(pl)
        say(f"setup {_name(pl)}: backend={pl.config.backend} "
            f"width={pl.config.width} "
            f"schedule={pl.config.schedule.canonical} compile_s={secs:.1f}")
    for pl in plans:
        ops = random_operands(pl, PRODUCTS, rng)
        eng.reset_stats()  # counters and latencies of this preset alone
        results, wall = serve(eng, pl, ops)
        snap = eng.snapshot()
        L = pl.config.L
        if any(r.shape != (pl.config.n, L) for r in results):
            raise AssertionError(f"{_name(pl)}: result shape is not (n, L)")
        checked = check_oracle(pl, ops[:SLOTS], results[:SLOTS])
        say(f"served {_name(pl)}: products={snap['served']} "
            f"oracle_checked={checked} bit_exact=True")
        say(f"smoke reading (not a benchmark) {_name(pl)}: "
            f"products_per_s={snap['served'] / wall:.1f} "
            f"p50_ms={snap['latency_p50_ms']:.1f} "
            f"p99_ms={snap['latency_p99_ms']:.1f}")
        if snap["served"] < PRODUCTS:
            raise AssertionError(f"{_name(pl)}: served {snap['served']}")
        check_counters(eng, [pl])
    if eng.trace_count != len(plans):
        raise AssertionError(
            f"{eng.trace_count} compilations for {len(plans)} configs"
        )


def four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.serve.crypto_engine import PolymulEngine
    from repro.sharding import partition

    devs = jax.devices()
    if len(devs) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("data", "model"))
    n, t, v = MESH_PRESET
    eng = PolymulEngine(batch_slots=SLOTS, mesh=mesh)
    ref = PolymulEngine(batch_slots=SLOTS)
    pl = eng.plan(n=n, t=t, v=v, backend="auto")
    ref_pl = ref.plan(n=n, t=t, v=v, backend="auto")

    # the channel tables: one (t/2)-channel slice per model shard, on
    # four distinct devices, not all on devices[0]
    per_shard = t // mesh.shape["model"]
    specs = partition.plan_leaf_specs(mesh, pl)
    for name, leaf in pl.consts.items():
        if len(specs[name]) and specs[name][0] == "model":
            shards = leaf.addressable_shards
            if ({s.data.shape[0] for s in shards} != {per_shard}
                    or len({s.device for s in shards}) != 4):
                raise AssertionError(
                    f"table {name} is not split {per_shard} channels per "
                    f"shard over 4 devices: "
                    f"{[(s.device.id, s.data.shape) for s in shards]}"
                )
    for name, leaf in ref_pl.consts.items():
        if leaf.devices() != {devs[0]}:
            raise AssertionError(f"reference table {name} is not on devices[0]")
    say(f"placement: every channel table split {per_shard} channels per "
        f"shard over devices {sorted(d.id for d in devs[:4])}")

    for label, e, p in (("mesh", eng, pl), ("one-device", ref, ref_pl)):
        secs = e.warmup(p)
        say(f"setup {label} {_name(p)}: backend={p.config.backend} "
            f"compile_s={secs:.1f}")
    ops = random_operands(pl, PRODUCTS, random.Random(seed))
    got, wall = serve(eng, pl, ops)
    want, _ = serve(ref, ref_pl, ops)
    same = sum(bool((g == w).all()) for g, w in zip(got, want))
    if same != len(ops):
        raise AssertionError(f"mesh differs from one device: {same}/{len(ops)}")
    checked = check_oracle(pl, ops[:SLOTS], got[:SLOTS])
    snap = eng.snapshot()
    say(f"served mesh {_name(pl)}: products={snap['served']} "
        f"equal_to_one_device={same} oracle_checked={checked}")
    say(f"smoke reading (not a benchmark) mesh {_name(pl)}: "
        f"products_per_s={snap['served'] / wall:.1f} "
        f"p50_ms={snap['latency_p50_ms']:.1f} "
        f"p99_ms={snap['latency_p99_ms']:.1f}")
    check_counters(eng, [pl])
    check_counters(ref, [ref_pl])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=2, model=2) mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {dev}); nothing served",
              file=sys.stderr)
        return 2
    say(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")

    from repro import compile_cache

    say(f"compile cache: {compile_cache.enable()}")
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
