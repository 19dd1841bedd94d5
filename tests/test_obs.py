"""Observability layer tests (DESIGN.md §12): metrics registry
semantics, the histogram-quantile accuracy contract vs the nearest-rank
order statistics (property-tested on latency- and queue-wait-shaped
series), span lifecycle + conservation through the serving engine, the
dispatch spans (``engine.step`` and its phases) and their clock, the
frozen snapshot schema, and Prometheus/JSONL exporter round-trips.

Uses hypothesis when installed; otherwise the fallback shim turns each
``@given`` property into an individual skip (the seeded versions of the
same bounds still run — the contract is never untested).
"""
import json
import threading

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

from repro import obs
from repro.obs.metrics import MetricsRegistry
from repro.serve.crypto_engine import (
    SNAPSHOT_KEYS,
    SNAPSHOT_SCHEMA_VERSION,
    PolymulEngine,
)


# ---------------------------------------------------------------------------
# metrics registry semantics
# ---------------------------------------------------------------------------


def test_counter_inc_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("repro_test_total", "help", ("engine",))
    c.labels(engine="a").inc()
    c.labels(engine="a").inc(2)
    c.labels(engine="b").inc(5)
    assert c.labels(engine="a").value == 3
    assert c.labels(engine="b").value == 5


def test_counter_rejects_negative():
    reg = MetricsRegistry()
    c = reg.counter("repro_test_total", "help")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("repro_depth", "help")
    g.set(4.0)
    g.inc()
    g.dec(2.0)
    assert g.value == 3.0


def test_reregistration_idempotent_and_conflicts_raise():
    reg = MetricsRegistry()
    c1 = reg.counter("repro_shared_total", "help", ("engine",))
    c2 = reg.counter("repro_shared_total", "help", ("engine",))
    assert c1 is c2  # two engines share one family
    with pytest.raises(ValueError):
        reg.counter("repro_shared_total", "help", ("other",))
    with pytest.raises(ValueError):
        reg.gauge("repro_shared_total", "help")


def test_invalid_metric_names_rejected():
    reg = MetricsRegistry()
    for bad in ("", "1abc", "a-b", "a.b"):
        with pytest.raises(ValueError):
            reg.counter(bad, "help")


def test_histogram_empty_and_bad_quantile():
    reg = MetricsRegistry()
    h = reg.histogram("repro_lat_seconds", "help")
    assert h.quantile(0.5) is None
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_counter_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("repro_race_total", "help")

    def bump():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=bump) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


def test_default_registry_reset_zeroes_values():
    c = obs.registry().counter("repro_tmp_total", "help")
    c.inc(7)
    obs.reset_default_registry()
    # families persist (dashboards keep their series); values zero
    assert obs.registry().get("repro_tmp_total") is c
    assert c.value == 0


# ---------------------------------------------------------------------------
# histogram-quantile accuracy contract vs numpy.percentile
# ---------------------------------------------------------------------------


def _assert_quantile_bound(values, q):
    """The documented contract: lower/G - lo <= estimate <= higher*G + lo
    where lower/higher are the nearest-rank order statistics around the
    requested rank, G = HIST_GROWTH and lo = the first bucket bound."""
    reg = MetricsRegistry()
    h = reg.histogram("repro_q_seconds", "help")
    for v in values:
        h.observe(v)
    est = h.quantile(q)
    a = np.asarray(values)
    lower = float(np.percentile(a, q * 100, method="lower"))
    higher = float(np.percentile(a, q * 100, method="higher"))
    lo = h.buckets[0]
    g = obs.HIST_GROWTH
    assert lower / g - lo <= est <= higher * g + lo, (
        f"quantile({q}) = {est} outside [{lower / g - lo}, "
        f"{higher * g + lo}] (order statistics {lower}, {higher}, "
        f"n={len(values)})"
    )


@pytest.mark.parametrize("q", [0.5, 0.9, 0.99])
def test_quantile_bound_latency_shaped(q):
    # lognormal ~ serving latency: long right tail
    rng = np.random.default_rng(11)
    values = rng.lognormal(mean=-6.0, sigma=1.5, size=500).tolist()
    _assert_quantile_bound(values, q)


@pytest.mark.parametrize("q", [0.5, 0.99])
def test_quantile_bound_queue_wait_shaped(q):
    # exponential with a point mass near zero ~ queue waits: most
    # requests dispatch immediately, stragglers wait out a batch window
    rng = np.random.default_rng(12)
    values = np.concatenate([
        rng.exponential(scale=2e-3, size=300),
        np.full(200, 1e-7),  # below first bucket bound: absolute floor
    ]).tolist()
    _assert_quantile_bound(values, q)


@settings(max_examples=50, deadline=None)
@given(
    st.lists(
        st.floats(min_value=1e-8, max_value=60.0,
                  allow_nan=False, allow_infinity=False),
        min_size=1, max_size=200,
    ),
    st.sampled_from([0.0, 0.5, 0.9, 0.99, 1.0]),
)
def test_quantile_bound_property(values, q):
    _assert_quantile_bound(values, q)


def test_quantile_exact_on_degenerate_series():
    reg = MetricsRegistry()
    h = reg.histogram("repro_one_seconds", "help")
    h.observe(1e-3)
    est = h.quantile(0.5)
    assert est is not None
    assert 1e-3 / obs.HIST_GROWTH <= est <= 1e-3 * obs.HIST_GROWTH


# ---------------------------------------------------------------------------
# tracing: span lifecycle, conservation, JSONL round-trip
# ---------------------------------------------------------------------------


def test_span_finish_twice_raises_and_late_events_noop():
    log = obs.SpanLog(None)
    sp = log.start_span("request", bucket="b")
    sp.event("admit")
    sp.finish("resolved")
    sp.event("late")  # must not reopen or mutate
    assert sp.events[-1]["name"] == "admit"
    with pytest.raises(RuntimeError):
        sp.finish("failed")


def test_trace_ids_unique_across_threads():
    log = obs.SpanLog(None)
    ids = []
    lock = threading.Lock()

    def mint():
        local = [log.start_span("request").trace_id for _ in range(200)]
        with lock:
            ids.extend(local)

    threads = [threading.Thread(target=mint) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(set(ids)) == len(ids) == 800


def test_conservation_flags_violations():
    log = obs.SpanLog(None)
    ok = log.start_span("request")
    ok.finish("resolved")
    bad = log.start_span("request")
    bad.status = "pending"  # forged non-terminal record
    bad.t_end = bad.t_start
    log._emit(bad.to_record())
    rej = log.start_span("request")
    rej.finish("rejected")  # never admitted: no terminal obligation
    cons = obs.conservation(log.records)
    assert cons["spans"] == 3
    assert cons["admitted"] == 2
    assert cons["by_status"]["rejected"] == 1
    assert any("non-terminal" in v for v in cons["violations"])


def test_span_log_jsonl_round_trip(tmp_path):
    path = tmp_path / "spans.jsonl"
    with obs.SpanLog(path) as log:
        sp = log.start_span("request", bucket="b", seq=1)
        sp.event("dispatch", backend="jnp")
        sp.finish("resolved", latency_s=0.25)
        log.event("breaker_open", level=1)
    records = obs.read_jsonl(path)
    assert records == log.records
    span = next(r for r in records if r["kind"] == "span")
    assert span["status"] == "resolved"
    assert span["attrs"]["latency_s"] == 0.25
    assert span["events"][0]["name"] == "dispatch"
    assert span["t_unix"] > 1e9  # wall anchor, not perf_counter scale


def test_read_jsonl_rejects_malformed(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"kind": "span"}\nnot json\n')
    with pytest.raises(ValueError, match="not valid JSON"):
        obs.read_jsonl(path)
    path.write_text('{"kind": "mystery"}\n')
    with pytest.raises(ValueError, match="not a span/event"):
        obs.read_jsonl(path)


# ---------------------------------------------------------------------------
# engine integration: spans + counters + frozen snapshot schema
# ---------------------------------------------------------------------------


def _drive_engine(span_log=None, registry=None, requests=6):
    eng = PolymulEngine(batch_slots=4, span_log=span_log, registry=registry)
    pl = eng.plan(n=64, t=3, v=30)
    rng = np.random.default_rng(5)
    shape = (pl.n, pl.config.seg_count)
    futs = [
        eng.submit(
            pl,
            rng.integers(0, 1 << pl.v, size=shape),
            rng.integers(0, 1 << pl.v, size=shape),
        )
        for _ in range(requests)
    ]
    eng.run_until_idle()
    return eng, futs


def test_engine_spans_conserve_and_match_counters():
    log = obs.SpanLog(None)
    eng, futs = _drive_engine(span_log=log)
    assert all(f.exception() is None for f in futs)
    cons = obs.conservation(log.records)
    assert cons["violations"] == []
    snap = eng.snapshot()
    assert cons["admitted"] == snap["submitted"] == len(futs)
    assert cons["by_status"].get("resolved", 0) == snap["served"]
    # every future carries its span's trace id
    ids = {f.trace_id for f in futs}
    assert len(ids) == len(futs)
    span_ids = {r["trace_id"] for r in log.spans() if r["name"] == "request"}
    assert ids == span_ids


def test_engine_doa_request_sheds_with_span():
    log = obs.SpanLog(None)
    eng = PolymulEngine(batch_slots=4, span_log=log)
    pl = eng.plan(n=64, t=3, v=30)
    shape = (pl.n, pl.config.seg_count)
    z = np.zeros(shape, np.int64)
    fut = eng.submit(pl, z, z, deadline=0.0)  # dead on arrival
    eng.run_until_idle()
    assert fut.exception() is not None
    spans = log.spans("shed")
    assert len(spans) == 1
    assert spans[0]["attrs"]["reason"] == "doa"
    assert obs.conservation(log.records)["violations"] == []


def test_engine_backpressure_rejection_span():
    log = obs.SpanLog(None)
    eng = PolymulEngine(batch_slots=4, max_pending=1, span_log=log)
    pl = eng.plan(n=64, t=3, v=30)
    shape = (pl.n, pl.config.seg_count)
    z = np.zeros(shape, np.int64)
    eng.submit(pl, z, z)
    assert eng.try_submit(pl, z, z) is None  # queue full
    eng.run_until_idle()
    cons = obs.conservation(log.records)
    assert cons["by_status"].get("rejected", 0) == 1
    assert cons["violations"] == []
    assert eng.stats["rejected"] == 1


def test_engine_counters_in_private_registry():
    reg = MetricsRegistry()
    eng, _ = _drive_engine(registry=reg)
    fam = reg.get("repro_engine_served_total")
    assert fam is not None
    assert fam.labels(engine=eng.name).value == 6
    # latency histogram populated — quantile available for export
    lat = reg.get("repro_engine_latency_seconds")
    assert lat.labels(engine=eng.name).count == 6


def test_snapshot_schema_frozen():
    """Regression pin: the snapshot wire contract.  Adding a key means
    bumping SNAPSHOT_SCHEMA_VERSION and updating SNAPSHOT_KEYS — this
    test failing on an unintended change is the point."""
    eng, _ = _drive_engine()
    snap = eng.snapshot()
    assert snap["schema_version"] == SNAPSHOT_SCHEMA_VERSION == 1
    assert set(snap.keys()) == set(SNAPSHOT_KEYS)
    # stable value types (JSON-serializable wire record)
    json.dumps(snap)


def test_reset_stats_zeroes_metrics():
    reg = MetricsRegistry()
    eng, _ = _drive_engine(registry=reg)
    eng.reset_stats()
    assert eng.stats["served"] == 0
    assert reg.get("repro_engine_latency_seconds").labels(
        engine=eng.name
    ).count == 0


# ---------------------------------------------------------------------------
# exporters: Prometheus text format + JSON
# ---------------------------------------------------------------------------


def test_prometheus_round_trip_through_strict_parser():
    reg = MetricsRegistry()
    _drive_engine(registry=reg)
    reg.gauge("repro_demo_ratio", "a gauge", ("stage",)).labels(
        stage="compose"
    ).set(0.25)
    text = obs.to_prometheus(reg)
    families = obs.parse_prometheus(text)
    assert "repro_engine_served_total" in families
    assert "repro_engine_latency_seconds" in families
    hist = families["repro_engine_latency_seconds"]
    assert hist["type"] == "histogram"


def test_prometheus_parser_rejects_malformed():
    for bad in (
        "# TYPE repro_x_total counter\nrepro_x_total notanumber\n",
        "# TYPE repro_x_total counter\nrepro_x_total{le=1.0} 1\n",  # unquoted
        "# TYPE repro_h histogram\nrepro_h 1\n",  # bare sample for histogram
        # histogram without the mandatory +Inf bucket
        "# TYPE repro_h histogram\n"
        'repro_h_bucket{le="1.0"} 1\nrepro_h_sum 1\nrepro_h_count 1\n',
        # bucket counts not cumulative
        "# TYPE repro_h histogram\n"
        'repro_h_bucket{le="1.0"} 5\nrepro_h_bucket{le="+Inf"} 3\n'
        "repro_h_sum 1\nrepro_h_count 5\n",
        # buckets without _sum/_count
        "# TYPE repro_h histogram\n"
        'repro_h_bucket{le="+Inf"} 3\n',
    ):
        with pytest.raises(ValueError):
            obs.parse_prometheus(bad)


def test_json_export_carries_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("repro_j_seconds", "help")
    for v in (0.001, 0.002, 0.004):
        h.observe(v)
    doc = obs.to_json(reg)
    assert doc["schema"] == "repro.obs/v1"
    fam = next(f for f in doc["families"] if f["name"] == "repro_j_seconds")
    series = fam["series"][0]
    assert series["count"] == 3
    assert series["p50"] is not None and series["p99"] is not None


# ---------------------------------------------------------------------------
# dispatch spans: engine.step and its phases, on the profiler's clock
# ---------------------------------------------------------------------------

PHASES = ["engine.pack", "engine.upload", "engine.launch",
          "engine.device_wait", "engine.readback", "engine.resolve"]


def _steps(log):
    """{step record: [its child records in start order]}, steps in order."""
    spans = log.spans()
    steps = [s for s in spans if s["name"] == "engine.step"]
    return [(st, sorted((c for c in spans if c["parent_id"] == st["trace_id"]),
                        key=lambda c: c["t_start"]))
            for st in sorted(steps, key=lambda s: s["t_start"])]


def _assert_phases_nested_in_order(step, children, names=PHASES):
    assert [c["name"] for c in children] == names
    assert all(c["status"] == "ok" for c in children)
    assert all(c["attrs"]["dispatch_index"] == step["attrs"]["dispatch_index"]
               for c in children)
    t = step["t_start"]
    for c in children:  # inside the step, one after another
        assert t <= c["t_start"] <= c["t_end"]
        t = c["t_end"]
    assert t <= step["t_end"]


def test_each_dispatch_is_a_step_with_six_phases_in_order():
    log = obs.SpanLog(None)
    eng, futs = _drive_engine(span_log=log)  # 6 requests, 4 slots
    steps = _steps(log)
    assert [st["attrs"]["dispatch_index"] for st, _ in steps] == [0, 1]
    assert [(st["attrs"]["batch"], st["attrs"]["pad"]) for st, _ in steps] == [
        (4, 0), (2, 2)]
    for st, children in steps:
        assert st["status"] == "ok" and st["parent_id"] is None
        assert st["attrs"]["bucket"] == "n64_t3_v30_jnp"
        assert st["attrs"]["probing"] is False
        _assert_phases_nested_in_order(st, children)
        pack, upload, readback = children[0], children[1], children[4]
        n, S = 64, eng.plan(n=64, t=3, v=30).config.seg_count
        L = futs[0].result().shape[1]
        assert pack["attrs"]["bytes"] == upload["attrs"]["bytes"] == 2 * 4 * n * S * 8
        assert readback["attrs"]["bytes"] == 4 * n * L * 4  # (slots, n, L) int32
        # no step or phase span carries a dispatch event
        assert st["events"] == [] and all(c["events"] == [] for c in children)


def _width_dispatches(reg, eng):
    fam = reg.get("repro_engine_width_dispatches_total")
    return {width: child.value for (engine, width), child in fam.children()
            if engine == eng.name}


def test_dispatches_by_width_sum_to_dispatches_and_steps_name_the_width():
    log, reg = obs.SpanLog(None), MetricsRegistry()
    eng = PolymulEngine(batch_slots=4, span_log=log, registry=reg)
    rng = np.random.default_rng(15)
    plans = {"int64": eng.plan(n=64, t=3, v=30), "wide": eng.plan(n=32, t=4, v=45)}
    for pl, count in ((plans["int64"], 6), (plans["wide"], 3)):
        shape = (pl.n, pl.config.seg_count)
        for _ in range(count):
            eng.submit(pl, rng.integers(0, 1 << pl.v, size=shape),
                       rng.integers(0, 1 << pl.v, size=shape))
    eng.run_until_idle()
    by_width = _width_dispatches(reg, eng)
    assert by_width == {"int64": 2, "wide": 1}  # 4 + 2 padded, 3 padded
    assert sum(by_width.values()) == eng.stats["dispatches"] == 3
    steps = _steps(log)
    assert len(steps) == 3
    for st, children in steps:
        width = st["attrs"]["width"]
        cfg = plans[width].config
        assert st["attrs"]["bucket"] == f"n{cfg.n}_t{cfg.t}_v{cfg.v}_{cfg.backend}"
        _assert_phases_nested_in_order(st, children)
    assert sorted(st["attrs"]["width"] for st, _ in steps) == ["int64", "int64", "wide"]
    eng.reset_stats()
    assert _width_dispatches(reg, eng) == {"int64": 0, "wide": 0}


@pytest.mark.parametrize("ntv", [(64, 3, 30), (32, 4, 45)], ids=["int64", "wide"])
def test_both_device_widths_carry_the_stage_scopes(ntv):
    """The compiled polymul names its pipeline stages in its op metadata
    on both device widths, which is how a device trace attributes them."""
    import re

    import jax

    from repro import api

    n, t, v = ntv
    pl = api.plan(n=n, t=t, v=v)
    z = np.zeros((2, n, pl.config.seg_count), np.int64)
    hlo = jax.jit(api.polymul).lower(pl, z, z).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    scopes = {m for name in op_names for m in re.findall(r"parentt\.(\w+)", name)}
    assert {"polymul", "decompose", "cascade", "compose"} <= scopes
    assert "fused_e2e" not in scopes
    # every stage nests under the one root
    assert all("parentt.polymul/" in name for name in op_names if "parentt." in name)


def test_request_dispatch_events_point_at_their_step():
    log = obs.SpanLog(None)
    eng, futs = _drive_engine(span_log=log)
    requests = {r["trace_id"]: r for r in log.spans() if r["name"] == "request"}
    for st, _ in _steps(log):
        assert st["attrs"]["requests"]
        for tid in st["attrs"]["requests"]:
            ev = next(e for e in requests[tid]["events"] if e["name"] == "dispatch")
            assert ev["attrs"]["step_id"] == st["trace_id"]
            assert ev["attrs"]["dispatch_index"] == st["attrs"]["dispatch_index"]
    listed = [t for st, _ in _steps(log) for t in st["attrs"]["requests"]]
    assert sorted(listed) == sorted(f.trace_id for f in futs)


@pytest.mark.parametrize("where, phase, children", [
    ("executor hook", "engine.step", ["engine.pack"]),
    ("upload", "engine.upload", ["engine.pack", "engine.upload"]),
])
def test_failed_dispatch_closes_its_step_failed_naming_the_phase(
        monkeypatch, where, phase, children):
    import jax

    from repro.serve.faults import FaultInjector, FaultRule

    log = obs.SpanLog(None)
    eng = PolymulEngine(batch_slots=4, span_log=log, backoff_base_s=0.0)
    pl = eng.plan(n=64, t=3, v=30)
    eng.warmup(pl)
    if where == "executor hook":
        FaultInjector([FaultRule("raise", max_count=1)]).install(eng)
        error = "InjectedFault"
    else:
        real = jax.device_put
        calls = []

        def device_put(*a, **k):
            calls.append(1)
            if len(calls) == 1:
                raise OSError("transfer failed")
            return real(*a, **k)

        monkeypatch.setattr(jax, "device_put", device_put)
        error = "OSError"
    z = np.ones((pl.n, pl.config.seg_count), np.int64)
    fut = eng.submit(pl, z, z)
    eng.run_until_idle()
    assert fut.exception() is None  # retried, then served
    (failed, kids), (ok, ok_kids) = _steps(log)
    assert failed["status"] == "failed" and ok["status"] == "ok"
    assert failed["attrs"]["error"] == error
    assert failed["attrs"]["phase"] == phase
    assert [c["name"] for c in kids] == children
    if phase != "engine.step":
        assert kids[-1]["status"] == "failed"
        assert kids[-1]["attrs"]["error"] == error
    _assert_phases_nested_in_order(ok, ok_kids)


@pytest.mark.parametrize("wrapper, children", [
    ("pass-through", PHASES),
    ("test double", ["engine.pack", "engine.resolve"]),
])
def test_wrapped_executor_keeps_the_step_and_its_host_phases(wrapper, children):
    log = obs.SpanLog(None)
    eng = PolymulEngine(batch_slots=4, span_log=log)
    pl = eng.plan(n=64, t=3, v=30)
    inner = eng.executor
    if wrapper == "pass-through":
        eng.executor = lambda p, za, zb: inner(p, za, zb)
    else:
        eng.executor = lambda p, za, zb: np.zeros(
            (za.shape[0], p.n, p.config.L), np.int64)
    z = np.ones((pl.n, pl.config.seg_count), np.int64)
    eng.submit(pl, z, z)
    eng.run_until_idle()
    ((step, kids),) = _steps(log)
    assert step["status"] == "ok"
    _assert_phases_nested_in_order(step, kids, children)


@pytest.mark.parametrize("traced", [False, True])
def test_trace_annotations_only_with_a_span_log(monkeypatch, traced):
    import jax

    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    log = obs.SpanLog(None) if traced else None
    eng, futs = _drive_engine(span_log=log)
    assert all(f.exception() is None for f in futs)
    if traced:
        assert made == (["engine.step"] + PHASES) * 2
        assert len(_steps(log)) == 2
    else:
        assert made == []
        assert eng._tls.step is None


def test_lifecycle_readers_ignore_step_spans():
    from repro.launch.obs_report import lifecycle_report

    log = obs.SpanLog(None)
    _drive_engine(span_log=log)
    records = log.records
    requests_only = [r for r in records if r["name"] == "request"]
    assert len(requests_only) < len(records)
    assert obs.conservation(records) == obs.conservation(requests_only)
    full, bare = lifecycle_report(records), lifecycle_report(requests_only)
    assert full.pop("records") > bare.pop("records")
    assert full == bare


def test_mesh_mode_dispatches_are_traced(host_mesh_4):
    """Mesh mode places the operands with the executable's shardings;
    its results are checked in test_serve_crypto's mesh tests."""
    log = obs.SpanLog(None)
    eng = PolymulEngine(batch_slots=4, mesh=host_mesh_4, span_log=log)
    pl = eng.plan(n=64, t=6, v=30)
    z = np.ones((pl.n, pl.config.seg_count), np.int64)
    futs = [eng.submit(pl, z, z) for _ in range(6)]
    eng.run_until_idle()
    assert all(f.exception() is None for f in futs)
    steps = _steps(log)
    assert len(steps) == 2
    for st, kids in steps:
        _assert_phases_nested_in_order(st, kids)


def test_span_log_clock_maps_whole_ns_apart_exactly():
    """perf_counter readings are whole ns; however the anchor and the
    readings fall, two of them d ns apart map exactly d ns apart."""
    rng = np.random.default_rng(16)
    for _ in range(20):
        log = obs.SpanLog(None)
        for n_ns in rng.integers(10**9, 2**21 * 10**9, size=200, dtype=np.int64):
            t = int(n_ns) / 1e9
            for d in (1, 999_999_999, 2_500_000_000):
                assert log.to_trace_ns((int(n_ns) + d) / 1e9) - log.to_trace_ns(t) == d
            assert log.to_trace_ns(t + 2.5) - log.to_trace_ns(t) == 2_500_000_000


@pytest.mark.parametrize("reference", ["wall clock", "profiler trace"])
def test_span_log_clock_maps_onto_the_profiler_clock(tmp_path, reference):
    import glob
    import time

    import jax
    from jax.profiler import ProfileData

    log = obs.SpanLog(None)
    if reference == "wall clock":
        t = time.perf_counter()
        now_ns = time.time_ns()
        assert abs(log.to_trace_ns(t) - now_ns) < 5e6
        # one offset: differences of perf_counter times map exactly
        assert log.to_trace_ns(t + 2.5) - log.to_trace_ns(t) == 2_500_000_000
        assert log.to_unix(t) * 1e9 == pytest.approx(log.to_trace_ns(t), abs=1e3)
        return
    jax.profiler.start_trace(str(tmp_path))
    try:
        starts = []
        for i in range(3):
            with log.traced("engine.step") as step:
                step.annotate(dispatch_index=i)
                with step.child("engine.pack") as pack:
                    starts.append(pack.t_start)
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    env = next(p for p in pd.planes if p.name == "Task Environment")
    t0 = dict(env.stats)["profile_start_time"]
    marks = {dict(ev.stats)["dispatch_index"]: t0 + int(ev.start_ns)
             for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events if ev.name == "engine.pack"}
    assert sorted(marks) == [0, 1, 2]
    # the annotation opens right after the span starts: within 1 ms here
    for i, t in enumerate(starts):
        assert 0 <= marks[i] - log.to_trace_ns(t) < 1_000_000
