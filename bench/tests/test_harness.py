"""The harness end to end on the CPU at n=64, with the look for a chip
skipped: cells, traffic and metrics found by name from files alone, a
sound run correct, and the control and each fault a cell can have
called wrong."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import control, run, trace

REPO = Path(run.__file__).resolve().parents[1]

TINY_READER = '''
def read(records):
    return float(records["counters"]["dispatches"])
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the benchmark's files plus a tiny configuration,
    a traffic mix and a per-layer metric dropped in as new files."""
    from repro import api

    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    pl = api.plan(n=64, t=6, v=30, backend="auto")
    cfg = json.loads((REPO / "bench/configs/paper-n4096-t6v30.json").read_text())
    cfg.update(name="tiny-n64-t6v30", n=64, q_primes=[p.q for p in pl.params.primes])
    (root / "bench/configs/tiny-n64-t6v30.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-bursts.json").write_text(
        json.dumps({"kind": "open", "rate_per_s": 300, "burst": 5}))
    (root / "bench/metrics/engine.dispatches.py").write_text(TINY_READER)
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["workloads"] += [
        {"name": "tiny.closed32", "config": "tiny-n64-t6v30", "traffic": "closed32",
         "chips": 1, "why": "test"},
        {"name": "tiny.bursts", "config": "tiny-n64-t6v30", "traffic": "tiny-bursts",
         "chips": 1, "why": "test"},
    ]
    m["per_layer"].append({"name": "engine.dispatches", "unit": "dispatches",
                           "better": "lower", "source": "program_counter",
                           "layer": "serve crypto_engine (PolymulEngine)",
                           "moves": "products_per_s", "workloads": ["tiny.bursts"]})
    for metric in m["per_layer"]:
        if (metric["name"].startswith(("engine.", "client."))
                and metric["name"] != "engine.dispatches"):
            metric["workloads"].append("tiny.bursts")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def _run(root, name, seed=2**33 + 7, trace_on=False, fault=None):
    cell = run.load_cell(root, name)
    return run.run_cell(cell, seed, 1.0, trace_on, jax.devices()[:1], root=root,
                        wrap_executor=control.FAULTS[fault] if fault else None,
                        log=lambda msg: None)


def test_new_files_are_found_by_name(root):
    cell = run.load_cell(root, "tiny.bursts")
    assert cell.config["n"] == 64
    assert cell.traffic == {"kind": "open", "rate_per_s": 300, "burst": 5}
    assert "engine.dispatches" in cell.per_layer
    # the tails are declared for named cells only; these hold for all
    assert {"products_per_s", "latency_p50_ms", "setup_s"} <= set(cell.end_to_end)
    read = run.load_reader(root, "engine.dispatches")
    assert read({"counters": {"dispatches": 3}}) == 3.0


def test_sound_run_is_correct(root):
    res = _run(root, "tiny.closed32")
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 48
    assert set(res["metrics"]) == set(run.load_cell(root, "tiny.closed32").end_to_end)
    assert {"products_per_s", "latency_p50_ms", "setup_s"} <= set(res["metrics"])
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["wrong_coefficients"] == {"value": 0, "limit": 0, "rule": "<="}
    assert res["checks"]["products_checked"]["value"] == run.SAMPLE


def test_traced_run_reads_the_per_layer_metrics(root, monkeypatch):
    fake = trace.Reduced(window_s=0.25, busy_s=0.2, modules=10,
                         scope_s={"cascade": 0.1}, ops=[("cascade:fusion", 0.1)],
                         gaps=[("engine.dispatch", 0.05, 10)])
    monkeypatch.setattr(trace, "reduce_dir", lambda outdir, scopes: fake)
    res = _run(root, "tiny.bursts", trace_on=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"engine.queue_wait_ms_p50", "client.latency_p99_ms",
                                   "engine.slot_occupancy_pct", "engine.dispatches"}
    assert res["metrics"]["client.latency_p99_ms"]["value"] > 0
    assert 0 < res["metrics"]["engine.slot_occupancy_pct"]["value"] <= 100
    assert res["metrics"]["engine.queue_wait_ms_p50"]["unit"] == "ms"
    assert res["device"]["busy_s"] == 0.2 and res["device"]["window_s"] == 0.25
    assert res["breakdown"]["idle_gaps"] == [["engine.dispatch x10", 0.05]]


@pytest.mark.parametrize("fault", ["control", "alter", "half"])
@pytest.mark.parametrize("cell", ["tiny.closed32", "tiny.bursts"])
def test_broken_paths_are_not_correct(root, fault, cell):
    res = _run(root, cell, seed=11, fault=fault)
    assert res["correct"] is False
    assert res["checks"]["wrong_coefficients"]["value"] > 0


def test_no_chip_no_result():
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench/run.py"), "--workload", "paper-n4096.closed32",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "TPU" in proc.stderr
