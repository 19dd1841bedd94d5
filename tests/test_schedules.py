"""Lane-aligned four-step NTT schedule + Harvey lazy-reduction
butterflies (DESIGN.md §6): bit-exactness of the four_step schedule vs
the radix-2 oracle and the bigint oracle across every backend and entry
point, the lane-alignment / reduction-op cost model vs the traced
kernels, and the lazy-reduction bound bookkeeping."""
import random

import numpy as np
import pytest

import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

import repro
from repro.core import modmath
from repro.core import ntt as ntt_mod
from repro.core import params as params_mod
from repro.core import polymul as pm
from repro.kernels import ops

PRESETS = [(3, 30, 64), (6, 30, 256)]
KERNEL_BACKENDS = ["pallas", "pallas_fused", "pallas_fused_e2e"]


def _rand_res(p, rows, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        np.stack([rng.integers(0, int(q), size=(rows, p.n)) for q in p.plan.qs])
    )


class TestFourStepOracle:
    """The four-step flow graph must be bit-identical to the radix-2
    reference it was re-grouped from — pure-jnp, no kernels."""

    @pytest.mark.parametrize("n", [8, 64, 128, 256, 512])
    def test_fwd_inv_match_radix2(self, n):
        q = 12289 if n <= 256 else 998244353
        tab = ntt_mod.make_tables(q, n)
        idx = ntt_mod.four_step_row_indices(*ntt_mod.four_step_split(n))
        row_fwd = jnp.asarray(np.asarray(tab.fwd)[idx])
        row_inv = jnp.asarray(np.asarray(tab.inv)[idx])
        rng = np.random.default_rng(n)
        a = jnp.asarray(rng.integers(0, q, size=(3, n)))
        f_ref = ntt_mod.ntt_raw(
            a, jnp.asarray(tab.fwd), q, tab.mul_eps, tab.mul_shifts
        )
        f_fs = ntt_mod.ntt_raw_four_step(
            a, jnp.asarray(tab.fwd), row_fwd, q, tab.mul_eps, tab.mul_shifts
        )
        assert np.array_equal(np.asarray(f_fs), np.asarray(f_ref))
        i_ref = ntt_mod.intt_raw(
            f_ref, jnp.asarray(tab.inv), q, tab.half, tab.mul_eps, tab.mul_shifts
        )
        i_fs = ntt_mod.intt_raw_four_step(
            f_fs, jnp.asarray(tab.inv), row_inv, q, tab.half,
            tab.mul_eps, tab.mul_shifts,
        )
        assert np.array_equal(np.asarray(i_fs), np.asarray(i_ref))
        assert np.array_equal(np.asarray(i_fs), np.asarray(a))

    def test_split_and_bad_n(self):
        assert ntt_mod.four_step_split(256) == (2, 128)
        assert ntt_mod.four_step_split(4096) == (32, 128)
        assert ntt_mod.four_step_split(64) == (2, 32)
        with pytest.raises(ValueError, match="power-of-two"):
            ntt_mod.four_step_split(2)
        with pytest.raises(ValueError, match="power-of-two"):
            ntt_mod.four_step_split(96)


class TestScheduleBitExact:
    """four_step == radix2 == bigint oracle for every dispatch entry
    point, on both presets, across every backend (acceptance gate)."""

    @pytest.mark.parametrize("t,v,n", PRESETS)
    @pytest.mark.parametrize("backend", ["jnp"] + KERNEL_BACKENDS)
    def test_stage_entry_points(self, t, v, n, backend):
        p = params_mod.make_params(n=n, t=t, v=v)
        a = _rand_res(p, 2, seed=n)
        b = _rand_res(p, 2, seed=n + 1)
        for fn, args in (
            (ops.ntt_forward, (a,)),
            (ops.ntt_inverse, (a,)),
            (ops.negacyclic_mul, (a, b)),
        ):
            want = fn(*args, p, backend=backend, schedule="radix2")
            got = fn(*args, p, backend=backend, schedule="four_step")
            assert np.array_equal(np.asarray(got), np.asarray(want)), (
                fn.__name__, backend)

    @pytest.mark.parametrize("t,v,n", PRESETS)
    @pytest.mark.parametrize("schedule", ["radix2", "four_step", "auto"])
    def test_e2e_vs_bigint_oracle(self, t, v, n, schedule):
        pl = repro.plan(
            n=n, t=t, v=v, backend="pallas_fused_e2e", schedule=schedule
        )
        rng = random.Random(17 * n)
        a = [rng.randrange(pl.q) for _ in range(n)]
        b = [rng.randrange(pl.q) for _ in range(n)]
        got = repro.polymul_ints(pl, a, b)
        assert got == pm.oracle_multiply(a, b, pl.params)

    def test_auto_resolution(self):
        spec = ops.resolve_schedule(params_mod.make_params(n=64, t=3, v=30))
        assert spec.kind == "radix2" and spec.splits == ()
        spec = ops.resolve_schedule(params_mod.make_params(n=256, t=6, v=30))
        assert spec.kind == "four_step" and spec.splits == ((2, 128),)
        p = params_mod.make_params(n=64, t=3, v=30, schedule="four_step")
        assert ops.resolve_schedule(p).kind == "four_step"
        assert ops.resolve_schedule(p, "radix2").kind == "radix2"
        with pytest.raises(ValueError, match="unknown schedule"):
            params_mod.make_params(n=64, t=3, v=30, schedule="fft")

    @settings(max_examples=20, deadline=None)
    @given(data=st.data())
    def test_property_schedule_backend_rows(self, data):
        """Randomized (schedule, backend, rows): the cascade is
        bit-identical across every datapath combination."""
        schedule = data.draw(st.sampled_from(["radix2", "four_step", "auto"]))
        backend = data.draw(st.sampled_from(["jnp"] + KERNEL_BACKENDS))
        rows = data.draw(st.integers(min_value=1, max_value=9))
        seed = data.draw(st.integers(min_value=0, max_value=2**31))
        p = params_mod.make_params(n=64, t=3, v=30)
        a = _rand_res(p, rows, seed)
        b = _rand_res(p, rows, seed + 1)
        got = ops.negacyclic_mul(a, b, p, backend=backend, schedule=schedule)
        want = ops.negacyclic_mul(a, b, p, backend="jnp", schedule="radix2")
        assert np.array_equal(np.asarray(got), np.asarray(want))


class TestRowPadding:
    """rows not divisible by row_blk (e.g. rows=3, row_blk=8): the
    padding path must stay bit-exact on every kernel backend — easy to
    break when the grid changes."""

    @pytest.mark.parametrize("backend", KERNEL_BACKENDS)
    @pytest.mark.parametrize("rows,row_blk", [(3, 8), (5, 4), (1, 8)])
    def test_residue_entry_points(self, backend, rows, row_blk):
        p = params_mod.make_params(n=64, t=3, v=30, row_blk=row_blk)
        pj = params_mod.make_params(n=64, t=3, v=30)
        a = _rand_res(p, rows, seed=rows)
        b = _rand_res(p, rows, seed=rows + 100)
        got = ops.negacyclic_mul(a, b, p, backend=backend)
        want = ops.negacyclic_mul(a, b, pj, backend="jnp")
        assert got.shape == a.shape
        assert np.array_equal(np.asarray(got), np.asarray(want))
        gf = ops.ntt_forward(a, p, backend=backend)
        assert np.array_equal(
            np.asarray(gf), np.asarray(ops.ntt_forward(a, pj, backend="jnp"))
        )

    @pytest.mark.parametrize("rows,row_blk", [(3, 8), (7, 4)])
    @pytest.mark.parametrize("schedule", ["radix2", "four_step"])
    def test_e2e_padding(self, rows, row_blk, schedule):
        p = params_mod.make_params(
            n=64, t=3, v=30, backend="pallas_fused_e2e",
            schedule=schedule, row_blk=row_blk,
        )
        pj = params_mod.make_params(n=64, t=3, v=30)
        rng = np.random.default_rng(rows)
        za = jnp.asarray(
            rng.integers(0, 1 << 30, size=(rows, p.n, p.plan.seg_count))
        )
        zb = jnp.asarray(
            rng.integers(0, 1 << 30, size=(rows, p.n, p.plan.seg_count))
        )
        got = ops.fused_polymul_e2e(za, zb, p)
        want = ops.fused_polymul_e2e(za, zb, pj, backend="jnp")
        assert np.array_equal(np.asarray(got), np.asarray(want))


class TestE2eKernelVariants:
    """Both e2e kernel variants — the channel-tiled grid (default for
    t >= 2) and the unrolled-channel fallback (channel_grid=False, not
    reachable through ops dispatch) — must stay bit-exact on both
    schedules."""

    @pytest.mark.parametrize("schedule", ["radix2", "four_step"])
    @pytest.mark.parametrize("channel_grid", [False, True])
    def test_variants_match_jnp(self, schedule, channel_grid):
        from repro.kernels import ntt as ntt_kernels

        p = params_mod.make_params(n=64, t=3, v=30)
        ct = p.tables
        rng = np.random.default_rng(11)
        za = jnp.asarray(
            rng.integers(0, 1 << 30, size=(3, p.n, p.plan.seg_count))
        )
        zb = jnp.asarray(
            rng.integers(0, 1 << 30, size=(3, p.n, p.plan.seg_count))
        )
        lazy = (ct.lazy_window, ct.shoup_beta)
        fwd, fsh, frow, frsh = ops._sched_tables(ct, schedule, lazy, "fwd")
        inv, ish, irow, irsh = ops._sched_tables(ct, schedule, lazy, "inv")
        got = ntt_kernels.fused_e2e_polymul_pallas(
            za, zb, fwd, inv, p.plan.qi_star_limbs_d, p.plan.q_limbs_d,
            fsh, ish, frow, irow, frsh, irsh,
            plan=p.plan, schedule=schedule, lazy=lazy,
            channel_grid=channel_grid, interpret=True,
        )
        want = ops.fused_polymul_e2e(za, zb, p, backend="jnp")
        assert np.array_equal(np.asarray(got), np.asarray(want))


class TestCostModel:
    """The lane-alignment / reduction-op claims, cross-checked against
    the traced kernels."""

    @pytest.mark.parametrize("t,v,n", PRESETS)
    @pytest.mark.parametrize("schedule", ["radix2", "four_step"])
    @pytest.mark.parametrize("direction", ["fwd", "inv"])
    def test_model_matches_traced_selects(self, t, v, n, schedule, direction):
        p = params_mod.make_params(n=n, t=t, v=v)
        m = ops.transform_cost_model(p, schedule=schedule, direction=direction)
        c = ops.count_reduction_selects(p, schedule=schedule, direction=direction)
        assert m["reduction_ops"] == c

    def test_four_step_has_no_sublane_stages(self):
        for n in (64, 256, 4096):
            strides = ntt_mod.stage_lane_strides(n, "four_step")
            assert all(s == 0 for s in strides)
        p = params_mod.make_params(n=256, t=6, v=30)
        m = ops.transform_cost_model(p, schedule="four_step")
        assert m["sublane_stages"] == 0
        assert ops.transform_cost_model(p, schedule="radix2")["sublane_stages"] == 7

    @pytest.mark.parametrize("t,v,n,schedule,sublane,reduction_ops,strict", [
        (3, 30, 64, "radix2", 6, 13, 30),
        (3, 30, 64, "four_step", 0, 13, 30),
        (6, 30, 256, "radix2", 7, 17, 40),
        (6, 30, 256, "four_step", 0, 17, 40),
    ])
    def test_cost_model_pinned(
        self, t, v, n, schedule, sublane, reduction_ops, strict
    ):
        """Sub-128-lane stages and reduction ops per transform, pinned as
        literals in both directions (the traced selects are tied to the
        model above): lazy reduction stays >= 2x below strict."""
        p = params_mod.make_params(n=n, t=t, v=v)
        for direction in ("fwd", "inv"):
            m = ops.transform_cost_model(
                p, schedule=schedule, direction=direction
            )
            assert (
                m["sublane_stages"], m["lazy_window"], m["reduction_ops"],
                m["strict_reduction_ops"],
            ) == (sublane, 2, reduction_ops, strict)
        assert 2 * reduction_ops <= strict

    def test_lazy_saves_2x_reduction_ops(self):
        p = params_mod.make_params(n=256, t=6, v=30)
        for schedule in ("radix2", "four_step"):
            for direction in ("fwd", "inv"):
                m = ops.transform_cost_model(
                    p, schedule=schedule, direction=direction
                )
                assert m["lazy_window"] is not None
                assert 2 * m["reduction_ops"] <= m["strict_reduction_ops"]


class TestLazyBounds:
    """Harvey lazy-reduction bookkeeping: window selection, the Shoup
    product bounds, and the per-stage invariant the tables record."""

    def test_window_selection(self):
        assert modmath.lazy_params([12289]) == (4, 16)  # 14-bit: wide window
        q30 = int(params_mod.make_params(n=64, t=3, v=30).plan.qs[0])
        assert modmath.lazy_params([q30]) == (2, 32)
        assert modmath.lazy_params([(1 << 31) + 11]) == (None, None)
        assert modmath.lazy_params([12289, 40961 * 4 + 1]) == (None, None)  # mixed

    def test_envelope_validation(self):
        with pytest.raises(ValueError, match="window"):
            modmath.validate_lazy_envelope(12289, 3, 16)
        with pytest.raises(ValueError, match="Shoup operand"):
            modmath.validate_lazy_envelope(12289, 4, 10)

    @pytest.mark.parametrize("q", [12289, None])
    def test_shoup_mul_window(self, q):
        if q is None:
            q = int(params_mod.make_params(n=64, t=3, v=30).plan.qs[0])
        window, beta = modmath.lazy_params([q])
        rng = np.random.default_rng(q & 0xFFFF)
        w = rng.integers(0, q, size=64)
        ws = modmath.shoup_constants(w, q, beta)
        v = jnp.asarray(rng.integers(0, window * q, size=64))
        out = np.asarray(
            modmath.shoup_mul(v, jnp.asarray(w), jnp.asarray(ws), q, beta)
        )
        assert (out >= 0).all() and (out < 2 * q).all()
        assert (out % q == np.asarray(v) * w % q).all()
        canon = np.asarray(modmath.canonicalize(jnp.asarray(out), q, window))
        assert (canon == np.asarray(v) * w % q).all()

    def test_tables_record_bounds(self):
        ct = params_mod.make_params(n=64, t=3, v=30).tables
        assert ct.lazy_window == 2 and ct.shoup_beta == 32
        assert ct.fwd_shoup.shape == ct.fwd.shape
        assert ct.fs_row_fwd.shape == (ct.t, 32, 2)
        bounds = ct.stage_bounds()
        assert len(bounds) == 6  # log2(64) stages
        assert all(b == (2, 4) for b in bounds)
        assert ct.stage_bounds(inverse=True)[0] == (2, 4)
