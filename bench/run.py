#!/usr/bin/env python3
"""One run of one benchmark cell: served PaReNTT products through
``PolymulEngine`` on the chip, timed from the client side.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration is ``bench/configs/<config>.json`` and its traffic
``bench/traffic/<traffic>.json``.  One process:

1. finds the chips the cell asks for, and exits 2 with no result line
   when JAX finds fewer, or no TPU;
2. turns on the persistent compile cache, plans only the cell's
   configuration with ``backend="auto"`` and compiles it;
3. draws the operand pool from ``--seed`` on the host and warms the
   served path, with the engine's dispatcher thread running;
4. runs the traffic for ``--seconds`` (``setup_s`` ends at the first
   timed request), waits for every request due in the window, and
   compares a sample of them, drawn from the seed, with the plain
   reference (``reference.py``);
5. prints the checks on standard error and, as the last line of
   standard output, one JSON object: ``correct``, ``attempted``,
   ``failed``, ``metrics``, ``device`` and, last, ``checks``.

``--trace 0`` reports the cell's end-to-end metrics.  ``--trace 1``
records the engine's request spans and a profiler trace of a short
stretch inside the window, and reports the per-layer metrics, each read
by ``bench/metrics/<metric>.py``, with the device's busy and traced
seconds and a breakdown.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # setup_s counts from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import loadgen, reference  # noqa: E402

SAMPLE = 48  # products compared with the reference in every run
POOL = 192  # operand polynomials per side: POOL**2 distinct pairs
TRACE_LEAD = 0.25  # the traced stretch starts this share into the window
TRACE_MAX_S = 2.0  # and lasts at most this long
WARM_BATCHES = 2  # full batches served before the window


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list[str]
    per_layer: list[str]
    units: dict[str, str]


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its
    configuration and traffic files and the metrics it reports."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    config = json.loads((root / "bench" / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    loadgen.validate(traffic)

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(
        name=name, config=config, traffic=traffic, chips=int(w["chips"]),
        end_to_end=[m["name"] for m in manifest["end_to_end"] if applies(m)],
        per_layer=[m["name"] for m in manifest["per_layer"] if applies(m)],
        units={m["name"]: m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]},
    )


def load_reader(root: Path, metric: str):
    """``bench/metrics/<metric>.py``'s ``read`` function."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def chip_devices(chips: int):
    """The first ``chips`` TPU devices, or None when JAX has fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        return None
    return devs[:chips]


def device_info(devs) -> dict:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "device_kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": max(peaks)}


def pair(k: int) -> tuple[int, int]:
    """Operand indices of product ``k``: a bijection onto the POOL**2
    pairs for k < POOL**2, with both sides changing at every step."""
    i = k % POOL
    return i, (i + k // POOL) % POOL


def make_pool(cfg: dict, q: int, rng: np.random.Generator) -> np.ndarray:
    """``(2, POOL, n, S)`` int64 segments of coefficients below q."""
    n, v, S = cfg["n"], cfg["v"], cfg["seg_count"]
    z = rng.integers(0, 1 << v, size=(2, POOL, n, S), dtype=np.int64)
    # a top segment below q's keeps every coefficient below q
    z[..., -1] = rng.integers(0, q >> (v * (S - 1)), size=(2, POOL, n), dtype=np.int64)
    return z


class Sample:
    """A uniform sample of SAMPLE counted requests and their results,
    drawn from the seed as they complete (reservoir sampling)."""

    def __init__(self, rng: np.random.Generator):
        self.rng = rng
        self.seen = 0
        self.items: list[tuple[int, np.ndarray]] = []

    def __call__(self, req, value) -> None:
        if len(self.items) < SAMPLE:
            self.items.append((req.k, value))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < SAMPLE:
                self.items[j] = (req.k, value)
        self.seen += 1


def check_sample(cfg: dict, pool: np.ndarray, items, offset: int) -> int:
    """Coefficients of the sampled results that differ from the
    reference's (a wrongly shaped result counts all n).  Window request
    ``k`` is product ``k + offset``."""
    q = math.prod(cfg["q_primes"])
    n, v, w = cfg["n"], cfg["v"], cfg["limb_bits"]
    ints: dict[tuple[int, int], list[int]] = {}

    def operand(side, i):
        if (side, i) not in ints:
            ints[side, i] = reference.digits_to_ints(pool[side, i], v)
        return ints[side, i]

    wrong = 0
    for k, value in items:
        value = np.asarray(value)
        if value.shape != (n, cfg["limb_count"]):
            wrong += n
            continue
        i, j = pair(k + offset)
        want = reference.negacyclic_mod(operand(0, i), operand(1, j), q)
        got = reference.digits_to_ints(value, w)
        wrong += sum(g != x for g, x in zip(got, want))
    return wrong


def _check_plan(cfg: dict, pl) -> None:
    """The program's plan has to be the configuration's ring."""
    c = pl.config
    want = (cfg["n"], cfg["t"], cfg["v"], math.prod(cfg["q_primes"]),
            cfg["seg_count"], cfg["limb_bits"], cfg["limb_count"])
    got = (c.n, c.t, c.v, pl.q, c.seg_count, c.w, c.L)
    if want != got:
        raise SystemExit(
            f"the plan is not the configuration's ring: (n, t, v, q, S, w, L) "
            f"= {got}, configuration {cfg['name']} states {want}"
        )


class _GcPauses:
    """Collections of the cyclic garbage collector and their pauses."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.pauses: list[float] = []
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.count[info["generation"]] += 1
            self.pauses.append(time.perf_counter() - self._t)

    def summary(self) -> str:
        p = self.pauses or [0.0]
        return (f"collections by generation {self.count}, "
                f"pause max_ms={max(p) * 1e3:.3f} total_ms={sum(p) * 1e3:.3f}")


class _Profiler:
    """Traces [start, start + length] of the window in its own thread."""

    def __init__(self, outdir: str, start_in: float, length: float):
        self.outdir, self.start_in, self.length = outdir, start_in, length
        self.error: BaseException | None = None
        self.thread = threading.Thread(target=self._run, name="bench-profiler",
                                       daemon=True)

    def _run(self):
        import jax

        try:
            time.sleep(self.start_in)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.outdir, profiler_options=opts)
            time.sleep(self.length)
            jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 - re-raised by join()
            self.error = e

    def join(self):
        self.thread.join()
        if self.error is not None:
            raise self.error


@dataclasses.dataclass
class Served:
    """A started engine with the configuration's plan compiled and warm,
    and the operand pool: ``submit(k)`` sends window product ``k``,
    which is pool product ``k + offset``."""
    eng: object
    pl: object
    pool: np.ndarray
    offset: int

    def submit(self, k):
        i, j = pair(k + self.offset)
        return self.eng.submit(self.pl, self.pool[0, i], self.pool[1, j])


def serve(cfg: dict, seed: int, *, spans=None, wrap_executor=None,
          annotate: bool = False, name: str = "bench", log=print) -> Served:
    """Plan and compile ``cfg`` with ``backend="auto"``, draw the pool
    from ``seed``, start the engine's dispatcher thread and serve
    WARM_BATCHES full batches through it.  ``wrap_executor`` replaces
    the engine's executor with ``wrap_executor(executor, cfg)``: the
    control and the fault tests put their paths in the program's place
    through it.  ``annotate`` wraps each dispatch in an
    ``engine.dispatch`` profiler annotation."""
    import jax

    from repro import compile_cache
    from repro.serve.crypto_engine import PolymulEngine

    log(f"compile cache: {compile_cache.enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    eng = PolymulEngine(name=name, span_log=spans)
    pl = eng.plan(n=cfg["n"], t=cfg["t"], v=cfg["v"], backend="auto")
    _check_plan(cfg, pl)
    compile_s = eng.warmup(pl)
    log(f"plan {cfg['name']}: backend={pl.config.backend} width={pl.config.width} "
        f"schedule={pl.config.schedule.canonical} batch_slots={eng.batch_slots} "
        f"compile_s={compile_s:.3f}")
    if wrap_executor is not None:
        eng.executor = wrap_executor(eng.executor, cfg)
    if annotate:
        inner = eng.executor

        def dispatch(p, za, zb):
            with jax.profiler.TraceAnnotation("engine.dispatch"):
                return inner(p, za, zb)

        eng.executor = dispatch
    offset = WARM_BATCHES * eng.batch_slots
    served = Served(eng, pl, make_pool(cfg, pl.q, np.random.default_rng(seed)), offset)
    eng.start()
    try:
        for f in [served.submit(k - offset) for k in range(offset)]:
            f.result(timeout=600)
    except BaseException:
        eng.stop(drain=False, timeout=60)
        raise
    return served


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devs, *,
             root: Path = ROOT, wrap_executor=None, log=print) -> dict:
    """One run of ``cell``; returns the result object.  ``wrap_executor``
    is :func:`serve`'s."""
    import jax

    from repro.obs.tracing import SpanLog

    cfg = cell.config
    spans = SpanLog() if trace else None
    compiles: list[float] = []  # when XLA compiled something: none in the window

    def on_event(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    pauses = _GcPauses()
    served = serve(cfg, seed, spans=spans, wrap_executor=wrap_executor,
                   annotate=trace, name=f"bench-{cell.name}", log=log)
    eng, pool, offset = served.eng, served.pool, served.offset
    try:
        before = eng.stats
        sample = Sample(np.random.default_rng([seed, 1]))
        annotate = (lambda: jax.profiler.TraceAnnotation("client.submit")) if trace else None
        prof = None
        if trace:
            outdir = tempfile.mkdtemp(prefix="bench-trace-")
            prof = _Profiler(outdir, TRACE_LEAD * seconds,
                             min(TRACE_MAX_S, TRACE_LEAD * seconds))
            prof.thread.start()
        # The objects set-up made stay for the process's life; a server
        # freezes them so that collections scan only the traffic's own.
        gc.collect()
        gc.freeze()
        gc.callbacks.append(pauses)
        setup_s = time.perf_counter() - T_START
        window = loadgen.drive(cell.traffic, served.submit, seconds,
                               np.random.default_rng([seed, 2]), sample,
                               annotate=annotate)
        if prof is not None:
            prof.join()
    finally:
        eng.stop(drain=True, timeout=120)
        jax.monitoring.unregister_event_duration_listener(on_event)
        if pauses in gc.callbacks:
            gc.callbacks.remove(pauses)
        gc.unfreeze()
    after = eng.stats
    counters = {k: after[k] - before[k] for k in after}
    device = device_info(devs)

    attempted = len(window.requests)
    failed = sum(1 for r in window.requests if r.t_done is None)
    lat = window.latencies_ms()
    late = window.lateness_ms()
    log(f"window: {window.seconds:.3f} s, attempted={attempted} failed={failed} "
        f"completed_in_window={window.completed_in_window()} "
        f"dispatches={counters['dispatches']} served={counters['served']}")
    if len(lat):
        log(f"client latency: p99_ms={float(np.percentile(lat, 99)):.4f} "
            f"max_ms={float(lat.max()):.4f}")
    log(f"generator lateness: p50_ms={float(np.percentile(late, 50)):.4f} "
        f"max_ms={float(late.max()):.4f}")
    log(f"compiles in the window: "
        f"{sum(window.t0 <= t <= window.t_end for t in compiles)}")
    log(f"gc in the window: {pauses.summary()}")
    errors = sorted({r.error for r in window.requests if r.error})
    if errors:
        log(f"request errors: {errors[:3]}")

    metrics: dict = {}
    result = {"correct": False, "attempted": attempted, "failed": failed}
    if not trace:
        values = {
            "products_per_s": window.completed_in_window() / window.seconds,
            "latency_p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "setup_s": setup_s,
        }
        for name in cell.end_to_end:
            if values[name] is not None:
                metrics[name] = {"value": values[name], "unit": cell.units[name]}
    else:
        from bench import trace as trace_mod

        # the executable's HLO names each traced op's scope; the engine
        # keeps its compiled executable per plan
        hlo = served.eng._executable(served.pl).as_text()
        reduced = trace_mod.reduce_dir(outdir, trace_mod.scopes_from_hlo(hlo))
        shutil.rmtree(outdir, ignore_errors=True)
        log(f"trace: window_s={reduced.window_s:.6f} busy_s={reduced.busy_s:.6f} "
            f"modules={reduced.modules} scope_s={reduced.scope_s} "
            f"unattributed_share={reduced.unattributed_share():.4f}")
        records = {
            "trace": reduced,
            "spans": [s for s in spans.spans()
                      if window.t0 <= s["t_start"] <= window.t_end],
            "counters": counters,
            "latencies_ms": lat,
            "batch_slots": eng.batch_slots,
            "config": cfg,
            "device_kind": device["kind"],
        }
        for name in cell.per_layer:
            value = load_reader(root, name)(records)
            if value is not None:
                metrics[name] = {"value": value, "unit": cell.units[name]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["metrics"] = metrics
    result["device"] = device

    t_check = time.perf_counter()
    wrong = check_sample(cfg, pool, sample.items, offset)
    checks = {
        "wrong_coefficients": {"value": wrong, "limit": 0, "rule": "<="},
        "failed_requests": {"value": failed, "limit": 0, "rule": "<="},
        "products_checked": {"value": len(sample.items),
                             "limit": min(SAMPLE, attempted), "rule": ">="},
    }
    log(f"reference check: {len(sample.items)} products in "
        f"{time.perf_counter() - t_check:.2f} s")
    result["correct"] = bool(wrong == 0 and failed == 0 and attempted > 0
                             and len(sample.items) >= min(SAMPLE, attempted))
    result["checks"] = checks
    return result


def main(argv=None, *, wrap_executor=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(ROOT, args.workload)
    devs = chip_devices(cell.chips)
    if devs is None:
        import jax

        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
              f"{[d.platform for d in jax.devices()]}; nothing run", file=sys.stderr)
        return 2

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      wrap_executor=wrap_executor, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
