"""Backend-dispatch layer: the one switch that selects the datapath for
the whole stack (jnp / pallas / pallas_fused), its shape contracts, and
the regression gate that keeps the fused Pallas cascade bit-exact
against the Python bigint oracles."""
import os
import random
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

import repro
from repro.core import params as params_mod
from repro.core import polymul as pm
from repro.kernels import ops

PRESETS = [(3, 30, 64), (6, 30, 256)]


def _rand_ints(p, seed):
    rng = random.Random(seed)
    a = [rng.randrange(p.q) for _ in range(p.n)]
    b = [rng.randrange(p.q) for _ in range(p.n)]
    return a, b


class TestFusedBitExact:
    """The paper's headline datapath must match the exact oracles."""

    @pytest.mark.parametrize("t,v,n", PRESETS)
    def test_pallas_fused_vs_oracles(self, t, v, n):
        p = params_mod.make_params(n=n, t=t, v=v)
        a, b = _rand_ints(p, seed=n)
        pl = repro.plan(n=n, t=t, v=v, backend="pallas_fused")
        got = repro.polymul_ints(pl, a, b)
        assert got == pm.oracle_multiply(a, b, p)
        assert got == pm.schoolbook_negacyclic(a, b, p.q)

    @pytest.mark.parametrize("t,v,n", PRESETS)
    def test_pallas_fused_e2e_vs_oracles(self, t, v, n):
        """The single-kernel decompose -> cascade -> compose pipeline
        (residues never touch HBM) must be bit-exact too."""
        p = params_mod.make_params(n=n, t=t, v=v)
        a, b = _rand_ints(p, seed=13 * n)
        pl = repro.plan(n=n, t=t, v=v, backend="pallas_fused_e2e")
        got = repro.polymul_ints(pl, a, b)
        assert got == pm.oracle_multiply(a, b, p)
        assert got == pm.schoolbook_negacyclic(a, b, p.q)

    @pytest.mark.parametrize("schedule", ["radix2", "four_step"])
    @pytest.mark.parametrize("backend", ops.BACKENDS)
    def test_every_backend_and_schedule_vs_oracle(self, backend, schedule):
        """Every (backend, schedule) pair through the public plan at the
        small preset is bit-exact vs the bigint oracle."""
        p = params_mod.make_params(n=64, t=3, v=30)
        a, b = _rand_ints(p, seed=7)
        pl = repro.plan(n=64, t=3, v=30, backend=backend, schedule=schedule)
        assert repro.polymul_ints(pl, a, b) == pm.oracle_multiply(a, b, p)

    @pytest.mark.parametrize("t,v,n", PRESETS)
    def test_backends_agree(self, t, v, n):
        p = params_mod.make_params(n=n, t=t, v=v)
        a, b = _rand_ints(p, seed=7 * n)
        outs = {
            bk: repro.polymul_ints(repro.plan(n=n, t=t, v=v, backend=bk), a, b)
            for bk in ops.BACKENDS
        }
        for bk, got in outs.items():
            assert got == outs["jnp"], f"backend {bk} disagrees with jnp"


class TestDispatch:
    def test_params_carry_backend(self):
        p = params_mod.make_params(n=64, t=3, v=30, backend="pallas_fused")
        assert p.backend == "pallas_fused"
        from repro import api

        assert api.plan_from_params(p).config.backend == "pallas_fused"
        # backend variants share one table/plan object (single H2D upload)
        pj = params_mod.make_params(n=64, t=3, v=30)
        assert p.tables is pj.tables and p.plan is pj.plan

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown backend"):
            repro.plan(n=64, t=3, v=30, backend="cuda")
        with pytest.raises(ValueError, match="unknown backend"):
            params_mod.make_params(n=64, t=3, v=30, backend="nope")
        err = pytest.raises(
            repro.UnknownKnobError, repro.plan, n=64, t=3, v=30, backend="cuda"
        ).value
        assert err.knob == "backend" and err.value == "cuda"
        assert "jnp" in err.alternatives

    def test_v45_pallas_backend_unservable(self):
        # The wide width has no Pallas datapath: the plan-time error
        # carries the knob and the servable alternatives.
        err = pytest.raises(
            repro.UnservableConfigError,
            repro.plan, n=64, t=4, v=45, backend="pallas_fused",
        ).value
        assert err.knob == "backend" and err.value == "pallas_fused"
        assert err.alternatives == ("auto", "jnp")

    def test_residue_shape_mismatch_fails_loudly(self):
        p = params_mod.make_params(n=64, t=3, v=30)
        good = jnp.zeros((3, 2, 64), dtype=jnp.int64)
        bad_t = jnp.zeros((4, 2, 64), dtype=jnp.int64)
        bad_n = jnp.zeros((3, 2, 32), dtype=jnp.int64)
        with pytest.raises(ValueError, match="expected residues"):
            ops.negacyclic_mul(bad_t, bad_t, p)
        with pytest.raises(ValueError, match="expected residues"):
            ops.ntt_forward(bad_n, p)
        with pytest.raises(ValueError, match="shapes differ"):
            ops.negacyclic_mul(good, jnp.zeros((3, 3, 64), dtype=jnp.int64), p)

    def test_segment_shape_mismatch_fails_loudly(self):
        p = params_mod.make_params(n=64, t=3, v=30)
        with pytest.raises(ValueError, match="segments"):
            ops.rns_decompose(jnp.zeros((5, p.plan.seg_count + 1), dtype=jnp.int64), p)
        with pytest.raises(ValueError, match="rns_compose"):
            ops.rns_compose(jnp.zeros((p.t + 1, 5), dtype=jnp.int64), p)

    def test_e2e_stage_calls_degrade(self):
        """Under pallas_fused_e2e the stage entry points have no
        single-kernel equivalent: they must run (degrading to the
        per-stage kernels) and stay exact, so BFV residue-domain call
        sites keep working with the backend threaded through params."""
        p = params_mod.make_params(n=64, t=3, v=30, backend="pallas_fused_e2e")
        pj = params_mod.make_params(n=64, t=3, v=30)
        rng = np.random.default_rng(5)
        z = jnp.asarray(rng.integers(0, 1 << 30, size=(64, p.plan.seg_count)))
        res = ops.rns_decompose(z, p)
        assert np.array_equal(
            np.asarray(res), np.asarray(ops.rns_decompose(z, pj))
        )
        spec = ops.ntt_forward(res.reshape(p.t, 1, p.n), p)
        back = ops.ntt_inverse(spec, p)
        assert np.array_equal(
            np.asarray(back), np.asarray(res.reshape(p.t, 1, p.n))
        )
        limbs = ops.rns_compose(res, p)
        assert np.array_equal(
            np.asarray(limbs), np.asarray(ops.rns_compose(res, pj))
        )

    def test_hbm_traffic_model_ordering(self):
        """Each fusion level strictly reduces modeled HBM bytes and
        kernel launches."""
        p = params_mod.make_params(n=64, t=3, v=30)
        models = {
            bk: ops.hbm_traffic_model(p, rows=4, backend=bk)
            for bk in ops.BACKENDS
        }
        assert (
            models["pallas_fused_e2e"]["hbm_bytes"]
            < models["pallas_fused"]["hbm_bytes"]
            < models["pallas"]["hbm_bytes"]
        )
        assert models["pallas_fused_e2e"]["kernel_launches"] == 1
        assert models["pallas_fused_e2e"]["intermediate_bytes"] == 0
        assert models["jnp"]["kernel_launches"] == 0
        # segments in / limbs out is the irreducible floor
        m = models["pallas_fused_e2e"]
        assert m["hbm_bytes"] == m["segment_bytes_in"] + m["limb_bytes_out"]

    def test_traffic_model_matches_traced_launch_counts(self):
        """The model's kernel_launches must equal the number of
        pallas_call equations in the actual traced computation — the
        structural tie that keeps the traffic model honest if a
        backend is ever de-fused."""
        p = params_mod.make_params(n=64, t=3, v=30)
        for bk in ops.BACKENDS:
            counted = ops.count_pallas_launches(p, backend=bk, rows=2)
            claimed = ops.hbm_traffic_model(p, rows=2, backend=bk)[
                "kernel_launches"
            ]
            assert counted == claimed, (
                f"backend {bk}: traced {counted} pallas_calls, "
                f"model claims {claimed}"
            )
        assert ops.count_pallas_launches(p, backend="pallas_fused_e2e") == 1

    @pytest.mark.parametrize("backend,hbm_bytes,launches,intermediate", [
        ("jnp", 47104, 0, 36864),
        ("pallas", 59392, 10, 36864),
        ("pallas_fused", 40960, 8, 18432),
        ("pallas_fused_e2e", 10240, 1, 0),
    ])
    def test_small_preset_traffic_pinned(
        self, backend, hbm_bytes, launches, intermediate
    ):
        """The modelled HBM bytes and the traced launches of the small
        preset (n=64, t=3, v=30, two rows), pinned as literals: a change
        that moves one of them updates the literal on purpose."""
        p = params_mod.make_params(n=64, t=3, v=30)
        m = ops.hbm_traffic_model(p, rows=2, backend=backend)
        assert (
            m["hbm_bytes"], m["kernel_launches"], m["intermediate_bytes"]
        ) == (hbm_bytes, launches, intermediate)
        assert ops.count_pallas_launches(p, backend=backend, rows=2) == launches

    @pytest.mark.parametrize("backend", ["pallas", "pallas_fused", "pallas_fused_e2e"])
    def test_arbitrary_leading_batch_dims(self, backend):
        """(t, B1, B2, n) residues work on the kernel backends (which fold
        to (t, rows, n) tiles internally) and match jnp exactly."""
        p = params_mod.make_params(n=64, t=3, v=30)
        rng = np.random.default_rng(3)
        shape = (2, 3, 64)
        a = jnp.asarray(
            np.stack([rng.integers(0, int(q), size=shape) for q in p.plan.qs])
        )
        b = jnp.asarray(
            np.stack([rng.integers(0, int(q), size=shape) for q in p.plan.qs])
        )
        got = ops.negacyclic_mul(a, b, p, backend=backend)
        want = ops.negacyclic_mul(a, b, p, backend="jnp")
        assert got.shape == a.shape
        assert np.array_equal(np.asarray(got), np.asarray(want))


class TestCollection:
    @pytest.mark.slow  # subprocess full-suite collection (~30 s); the CI
    # fast lane runs the same check as a dedicated workflow step
    def test_collect_only_is_clean(self):
        """Collection errors can never silently return: `pytest
        --collect-only` over the whole suite must exit 0."""
        repo = Path(__file__).resolve().parent.parent
        env = dict(os.environ)
        src = str(repo / "src")
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "--collect-only", "-q", "tests"],
            cwd=repo,
            env=env,
            capture_output=True,
            text=True,
            timeout=300,
        )
        # pytest exits 2 on collection errors, 0 when everything collects
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
