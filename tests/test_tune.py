"""The profile-driven autotuner (DESIGN.md §11): the persistent tuning
table (schema, atomic writes, batch-fallback lookup, staleness), the
plan-time resolution order (explicit knob > tuning table > static
default), the compiled-mode sweep harness (dedupe + taxonomy pruning +
never-slower winner), and the HLO cost-model cross-check."""
import json
import os

import numpy as np
import pytest

import repro
from repro.errors import UnknownKnobError
from repro.tune import (
    TABLE_SCHEMA,
    TABLE_VERSION,
    TUNABLE_KNOBS,
    TuningTable,
    TuningTableError,
    parse_workload_key,
    workload_key,
)
from repro.tune import costcheck


def _table(tmp_path, winner, *, n=64, t=3, v=30, batch=2, kind="cpu", **extra):
    tab = TuningTable()
    tab.put(n=n, t=t, v=v, batch=batch, winner=winner, kind=kind, **extra)
    path = tmp_path / "TUNING.json"
    tab.save(path)
    return str(path), tab


class TestTable:
    def test_round_trip(self, tmp_path):
        winner = {"backend": "jnp", "schedule": "radix2",
                  "row_blk": None, "channel_grid": None}
        path, tab = _table(tmp_path, winner, winner_us=10.0, default_us=12.0)
        got = TuningTable.load(path)
        assert got.entries == tab.entries
        assert got.to_dict()["schema"] == TABLE_SCHEMA
        assert got.to_dict()["version"] == TABLE_VERSION

    def test_workload_key_round_trip(self):
        assert workload_key(256, 6, 30, 2) == "n256_t6_v30_b2"
        assert parse_workload_key("n256_t6_v30_b2") == {
            "n": 256, "t": 6, "v": 30, "batch": 2,
        }
        with pytest.raises(TuningTableError, match="bad workload key"):
            parse_workload_key("n256_t6")

    def test_rejects_bad_schema_and_version(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"schema": "nope", "version": TABLE_VERSION}))
        with pytest.raises(TuningTableError, match="schema"):
            TuningTable.load(p)
        p.write_text(json.dumps({"schema": TABLE_SCHEMA, "version": 99}))
        with pytest.raises(TuningTableError, match="version"):
            TuningTable.load(p)
        p.write_text("{not json")
        with pytest.raises(TuningTableError, match="malformed"):
            TuningTable.load(p)

    def test_rejects_unresolved_and_non_tunable_winners(self):
        tab = TuningTable()
        with pytest.raises(TuningTableError, match="non-tunable"):
            tab.put(n=64, t=3, v=30, batch=2, winner={"use_sau": False})
        with pytest.raises(TuningTableError, match="resolved backend"):
            tab.put(n=64, t=3, v=30, batch=2, winner={"backend": "auto"})
        with pytest.raises(TuningTableError, match="resolved schedule"):
            tab.put(n=64, t=3, v=30, batch=2, winner={"schedule": "auto"})
        with pytest.raises(TuningTableError, match="row_blk"):
            tab.put(n=64, t=3, v=30, batch=2, winner={"row_blk": True})
        with pytest.raises(TuningTableError, match="channel_grid"):
            tab.put(n=64, t=3, v=30, batch=2, winner={"channel_grid": 1})

    def test_atomic_save_leaves_no_temp_files(self, tmp_path):
        winner = {"backend": "jnp", "schedule": "radix2",
                  "row_blk": None, "channel_grid": None}
        path, tab = _table(tmp_path, winner)
        tab.save(path)  # overwrite in place
        assert sorted(os.listdir(tmp_path)) == ["TUNING.json"]
        assert TuningTable.load(path).entries == tab.entries

    def test_lookup_batch_fallback(self):
        tab = TuningTable()
        tab.put(n=64, t=3, v=30, batch=8, kind="cpu",
                winner={"backend": "pallas"})
        tab.put(n=64, t=3, v=30, batch=2, kind="cpu",
                winner={"backend": "jnp"})
        # exact batch hits its entry; batch=None (the plan-time call,
        # plans are batch-agnostic) resolves the smallest batch
        assert tab.lookup(n=64, t=3, v=30, batch=8, kind="cpu")["backend"] == "pallas"
        assert tab.lookup(n=64, t=3, v=30, kind="cpu")["backend"] == "jnp"
        assert tab.lookup(n=64, t=3, v=30, kind="tpu") is None
        assert tab.lookup(n=128, t=3, v=30, kind="cpu") is None

    def test_prune_stale(self):
        tab = TuningTable()
        tab.put(n=64, t=3, v=30, batch=2, kind="cpu",
                winner={"backend": "jnp"}, measured_at=1000.0)
        tab.put(n=256, t=6, v=30, batch=2, kind="cpu",
                winner={"backend": "jnp"}, measured_at=5000.0)
        removed = tab.prune_stale(max_age_s=3000.0, now=6000.0)
        assert removed == [("cpu", "n64_t3_v30_b2")]
        assert list(tab.entries["cpu"]) == ["n256_t6_v30_b2"]
        # no timestamp counts as stale; emptied kinds are dropped
        tab2 = TuningTable(entries={"cpu": {"n64_t3_v30_b2": {
            "winner": {"backend": "jnp"}}}})
        assert tab2.prune_stale(max_age_s=1.0, now=10.0) == [
            ("cpu", "n64_t3_v30_b2")
        ]
        assert tab2.entries == {}


class TestPlanResolution:
    """plan(tuning=...): explicit knob > tuning table > static default."""

    WINNER = {"backend": "pallas_fused", "schedule": "radix2",
              "row_blk": 2, "channel_grid": None}

    def _path(self, tmp_path):
        import jax

        path, _ = _table(tmp_path, self.WINNER, n=64, t=3, v=30, batch=2,
                         kind=str(jax.default_backend()))
        return path

    def test_table_fills_default_knobs(self, tmp_path):
        path = self._path(tmp_path)
        cfg = repro.plan_key(repro.plan(n=64, t=3, v=30, tuning=path))
        assert cfg.backend == "pallas_fused"
        assert cfg.schedule.canonical == "radix2"
        assert cfg.row_blk == 2

    def test_explicit_knob_beats_table(self, tmp_path):
        path = self._path(tmp_path)
        cfg = repro.plan_key(repro.plan(n=64, t=3, v=30, backend="jnp",
                                        tuning=path))
        assert cfg.backend == "jnp"
        # untouched knobs still come from the table
        assert cfg.schedule.canonical == "radix2"
        cfg2 = repro.plan_key(repro.plan(n=64, t=3, v=30, schedule="four_step",
                                         row_blk=4, tuning=path))
        assert cfg2.schedule.canonical == "four_step"
        assert cfg2.row_blk == 4

    def test_off_and_default_match(self, tmp_path):
        assert repro.plan_key(repro.plan(n=64, t=3, v=30)) == repro.plan_key(
            repro.plan(n=64, t=3, v=30, tuning="off")
        )
        assert repro.plan_key(repro.plan(n=64, t=3, v=30, tuning=None)) == (
            repro.plan_key(repro.plan(n=64, t=3, v=30))
        )

    def test_plan_key_drift_restricted_to_tuned_knobs(self, tmp_path):
        import dataclasses

        path = self._path(tmp_path)
        tcfg = repro.plan_key(repro.plan(n=64, t=3, v=30, tuning=path))
        ucfg = repro.plan_key(repro.plan(n=64, t=3, v=30))
        drift = {
            f.name for f in dataclasses.fields(tcfg)
            if getattr(tcfg, f.name) != getattr(ucfg, f.name)
        }
        assert drift <= set(TUNABLE_KNOBS)

    def test_table_instance_and_missing_path(self, tmp_path):
        tab = TuningTable()
        tab.put(n=64, t=3, v=30, batch=2,
                winner={"backend": "pallas", "schedule": "radix2"})
        cfg = repro.plan_key(repro.plan(n=64, t=3, v=30, tuning=tab))
        assert cfg.backend == "pallas"
        with pytest.raises(TuningTableError, match="no tuning table"):
            repro.plan(n=64, t=3, v=30, tuning=str(tmp_path / "absent.json"))
        with pytest.raises(UnknownKnobError):
            repro.plan(n=64, t=3, v=30, tuning=42)

    def test_tuning_auto_never_raises(self):
        # degrades to static defaults when the seed is absent; resolves
        # the committed seed when present — either way a valid plan
        pl = repro.plan(n=64, t=3, v=30, tuning="auto")
        assert repro.plan_key(pl).n == 64

    def test_other_device_kind_is_ignored(self, tmp_path):
        path, _ = _table(tmp_path, self.WINNER, kind="tpu")
        assert repro.plan_key(repro.plan(n=64, t=3, v=30, tuning=path)) == (
            repro.plan_key(repro.plan(n=64, t=3, v=30))
        )

    def test_tuned_plans_are_retrace_free(self, tmp_path):
        import jax
        import jax.numpy as jnp

        path = self._path(tmp_path)
        traces = 0

        def fn(pl, za, zb):
            nonlocal traces
            traces += 1
            return repro.polymul(pl, za, zb)

        jfn = jax.jit(fn)
        rng = np.random.default_rng(0)
        shape = (2, 64, repro.plan(n=64, t=3, v=30).config.seg_count)
        za = jnp.asarray(rng.integers(0, 1 << 30, size=shape))
        zb = jnp.asarray(rng.integers(0, 1 << 30, size=shape))
        a = jfn(repro.plan(n=64, t=3, v=30, tuning=path), za, zb)
        b = jfn(repro.plan(n=64, t=3, v=30, tuning=path), za, zb)
        assert traces == 1
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_verifier_passes_on_tuned_config(self, tmp_path):
        from repro.analysis.verify import verify_plan

        path = self._path(tmp_path)
        report = verify_plan(repro.plan(n=64, t=3, v=30, tuning=path))
        assert report.ok, [f for f in report.findings]


class TestSweep:
    def test_micro_sweep_prunes_dedupes_and_never_loses(self):
        from repro.tune import sweep as sweep_mod

        wl = sweep_mod.Workload(n=64, t=3, v=30, batch=2)
        cands = (
            sweep_mod.DEFAULT_CANDIDATE,
            sweep_mod.Candidate(backend="jnp", schedule="radix2"),
            # four_step:h is unservable at n=64 — exercises the
            # plan-error-taxonomy pruning path
            sweep_mod.Candidate(backend="jnp", schedule="four_step:h"),
        )
        rep = sweep_mod.sweep_workload(wl, cands, iters=1, warmup=1)
        by_status = {}
        for c in rep["candidates"]:
            by_status.setdefault(c["status"], []).append(c)
        assert len(by_status.get("pruned", [])) == 1
        pruned = by_status["pruned"][0]
        assert pruned["error"]  # taxonomy type recorded
        assert rep["entry"]["winner_us"] <= rep["entry"]["default_us"]
        assert rep["entry"]["rank_correlation"] is None or (
            -1.0 <= rep["entry"]["rank_correlation"] <= 1.0
        )
        # winner knobs are resolved + table-valid
        tab = TuningTable()
        tab.put(**rep["entry"])

    def test_candidate_that_does_not_compile_is_failed_not_timed(
        self, monkeypatch
    ):
        """A compile failure is recorded as the candidate's result; no
        other path is timed in its place.  A default that does not
        compile fails the sweep."""
        from repro.errors import PlanError
        from repro.tune import sweep as sweep_mod

        real = sweep_mod.measure_plan

        def compiles_jnp_only(pl, za, zb, **kw):
            if repro.plan_key(pl).backend != "jnp":
                raise ValueError("the compiler refused the kernel")
            return real(pl, za, zb, **kw)

        monkeypatch.setattr(sweep_mod, "measure_plan", compiles_jnp_only)
        wl = sweep_mod.Workload(n=64, t=3, v=30, batch=2)
        cands = (
            sweep_mod.DEFAULT_CANDIDATE,
            sweep_mod.Candidate(backend="pallas", schedule="radix2"),
        )
        rep = sweep_mod.sweep_workload(wl, cands, iters=1, warmup=0)
        failed = [c for c in rep["candidates"] if c["status"] == "failed"]
        assert [c["config"]["backend"] for c in failed] == ["pallas"]
        assert failed[0]["error"] == "ValueError"
        assert "us_per_poly" not in failed[0]
        assert rep["entry"]["winner"]["backend"] == "jnp"

        def refuses_all(pl, za, zb, **kw):
            raise ValueError("the compiler refused the kernel")

        monkeypatch.setattr(sweep_mod, "measure_plan", refuses_all)
        with pytest.raises(PlanError, match="default candidate failed"):
            sweep_mod.sweep_workload(wl, cands, iters=1, warmup=0)

    def test_measured_winner_resolves_through_plan(self, tmp_path):
        from repro.tune import sweep as sweep_mod

        wl = sweep_mod.Workload(n=64, t=3, v=30, batch=2)
        tab, report = sweep_mod.sweep([wl], quick=True, iters=1, warmup=0)
        path = tmp_path / "T.json"
        tab.save(path)
        pl = repro.plan(n=64, t=3, v=30, tuning=str(path))
        winner = report["workloads"][0]["entry"]["winner"]
        cfg = repro.plan_key(pl)
        if winner["backend"] is not None:
            assert cfg.backend == winner["backend"]
        if winner["schedule"] is not None:
            assert cfg.schedule.canonical == winner["schedule"]


class TestCostCheck:
    def test_spearman(self):
        assert costcheck.spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
        assert costcheck.spearman([1, 2, 3], [30, 20, 10]) == pytest.approx(-1.0)
        assert costcheck.spearman([1.0, 1.0, 1.0], [1, 2, 3]) is None
        assert costcheck.spearman([1.0], [2.0]) is None
        with pytest.raises(ValueError, match="length"):
            costcheck.spearman([1.0], [1.0, 2.0])

    def test_ranks_average_ties(self):
        assert costcheck._ranks([10.0, 10.0, 30.0]) == [1.5, 1.5, 3.0]

    def test_cross_check_flags_bad_disagreement(self):
        cands = [
            {"name": "a", "measured_us": 1.0, "model_us": 400.0},
            {"name": "b", "measured_us": 2.0, "model_us": 300.0},
            {"name": "c", "measured_us": 3.0, "model_us": 200.0},
            {"name": "d", "measured_us": 4.0, "model_us": 100.0},
            {"name": "e", "measured_us": 5.0, "model_us": None},  # eager
        ]
        out = costcheck.cross_check(cands)
        assert out["modeled"] == 4 and out["unmodeled"] == 1
        assert out["rank_correlation"] == pytest.approx(-1.0)
        flagged = {f["name"] for f in out["flagged"]}
        assert "a" in flagged and "d" in flagged

    def test_predicted_cost_units(self):
        from test_hlo_analyzer import SYNTHETIC_CUSTOM_CALL

        got = costcheck.predicted_cost(SYNTHETIC_CUSTOM_CALL, kind="cpu")
        assert got["custom_call_count"] == 2
        assert got["custom_call_bytes"] > 0
        assert got["model_us"] > 0.0
