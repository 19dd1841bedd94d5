"""The wide-word configuration (t=4 x v=45) through the harness on the
CPU: a tiny cell of it served correct, the float64 control called
wrong, the configuration file agreeing with the program's plan, and its
byte counts equal to those of the t=6 x v=30 point."""
import json
import shutil
from pathlib import Path

import jax
import pytest

from bench import control, run, work

REPO = Path(run.__file__).resolve().parents[1]
CONFIG = REPO / "bench/configs/paper-wide-n4096-t4v45.json"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the benchmark's files plus the wide configuration
    cut to n=64, served under the ``closed32`` traffic."""
    from repro import api

    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    pl = api.plan(n=64, t=4, v=45, backend="auto")
    cfg = json.loads(CONFIG.read_text())
    cfg.update(name="tiny-wide-n64-t4v45", n=64,
               q_primes=[p.q for p in pl.params.primes])
    (root / "bench/configs/tiny-wide-n64-t4v45.json").write_text(json.dumps(cfg))
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["workloads"].append({"name": "tiny-wide.closed32", "config": "tiny-wide-n64-t4v45",
                           "traffic": "closed32", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def _run(root, fault=None):
    cell = run.load_cell(root, "tiny-wide.closed32")
    return run.run_cell(cell, 2**33 + 15, 1.0, False, jax.devices()[:1], root=root,
                        wrap_executor=control.FAULTS[fault] if fault else None,
                        log=lambda msg: None)


def test_tiny_wide_cell_is_correct(root):
    res = _run(root)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["wrong_coefficients"]["value"] == 0
    assert res["checks"]["products_checked"]["value"] == run.SAMPLE


def test_float64_control_is_read_wrong_at_v45(root):
    res = _run(root, fault="control")
    assert res["correct"] is False
    assert res["checks"]["wrong_coefficients"]["value"] > 0


def test_config_file_is_the_programs_wide_plan():
    from repro import api

    cfg = json.loads(CONFIG.read_text())
    pl = api.plan(n=cfg["n"], t=cfg["t"], v=cfg["v"], backend="auto")
    run._check_plan(cfg, pl)  # raises SystemExit on any mismatch
    assert (pl.config.width, pl.config.schedule.kind) == ("wide", "radix2")
    assert cfg["reduced"] == []


def test_wide_point_moves_the_bytes_of_the_t6v30_point():
    wide = json.loads(CONFIG.read_text())
    narrow = json.loads((REPO / "bench/configs/paper-n4096-t6v30.json").read_text())
    want = {"cascade": 2_555_904, "crt": 4_849_664}
    assert work.config_bytes(wide, 8) == work.config_bytes(narrow, 8) == want
