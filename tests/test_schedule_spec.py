"""PR 7 gates: the resolved ``ScheduleSpec`` API and the hierarchical
four-step schedules behind it.

* plan() freezes a concrete spec — no ``"auto"`` survives, the tile
  model always fits the VMEM budget, equal specs share one jit trace;
* the error taxonomy: vocabulary mistakes raise ``UnknownKnobError``,
  valid-but-unservable combos raise ``UnservableConfigError``, both
  carrying knob/value/alternatives;
* big-n acceptance: n=4096 (depth 1) and n=8192 (depth 2 hierarchical)
  bit-exact vs the bigint oracle through ``repro.polymul`` on the
  fused-e2e Pallas path;
* the fast host-NTT oracle itself cross-checked vs the schoolbook.

Property tests use hypothesis when installed; otherwise the fallback
shim turns each into an individual skip (tests/_hypothesis_fallback.py).
"""
import random

import numpy as np
import pytest

import jax

import repro
from repro.core import ntt as ntt_mod
from repro.core import params as params_mod
from repro.core import polymul as pm
from repro.core import primes as primes_mod
from repro.core import schedule as sched
from repro.kernels import ops

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover
    from _hypothesis_fallback import given, settings, st


# --------------------------------------------------------------------------
# Spec resolution: structure + round trip
# --------------------------------------------------------------------------


class TestSpecResolution:
    @pytest.mark.parametrize("n,depth", [
        (64, 1), (256, 1), (1024, 1), (4096, 1),
        (8192, 2), (16384, 2), (32768, 2), (65536, 3),
    ])
    def test_chain_shape(self, n, depth):
        """The four-step chain is a function of n alone: level 0 tiles
        n itself, every deeper level re-splits the previous column, and
        the final column transform fits the direct kernel bound."""
        spec = sched.concrete_spec(n, "four_step")
        assert spec.depth == depth
        c0, r0 = spec.splits[0]
        assert c0 * r0 == n
        for (c_prev, _), (c, r) in zip(spec.splits, spec.splits[1:]):
            assert c * r == c_prev
        assert spec.splits[-1][0] <= ntt_mod.MAX_FS_COL

    def test_no_auto_survives_plan(self):
        for n in (64, 256, 8192):
            pl = repro.plan(n=n, t=2, v=30)
            spec = pl.config.schedule
            assert isinstance(spec, repro.ScheduleSpec)
            assert spec.kind in ("radix2", "four_step")
            assert spec.canonical in sched.SCHEDULE_STRINGS
            assert spec.canonical != "auto"

    def test_plan_round_trips_through_frozen_spec(self):
        """A frozen spec fed back as the schedule knob reproduces the
        identical config (and therefore the identical plan_key)."""
        a = repro.plan(n=256, t=3, v=30, backend="pallas_fused_e2e")
        b = repro.plan(
            n=256, t=3, v=30, backend="pallas_fused_e2e",
            schedule=a.config.schedule,
        )
        assert a.config == b.config
        assert repro.plan_key(a) == repro.plan_key(b)

    def test_canonical_string_round_trips(self):
        for n, schedule in [(64, "radix2"), (256, "four_step"),
                            (8192, "four_step:h")]:
            spec = sched.concrete_spec(n, schedule)
            again = sched.concrete_spec(n, spec.canonical)
            assert again.kind == spec.kind
            assert again.splits == spec.splits

    def test_tiling_hint_accepted_when_canonical(self):
        pl = repro.plan(
            n=256, t=3, v=30, backend="pallas_fused_e2e",
            schedule="four_step", tiling=((2, 128),),
        )
        assert pl.config.schedule.splits == ((2, 128),)

    @settings(max_examples=60, deadline=None)
    @given(
        logn=st.integers(min_value=6, max_value=16),
        seg_count=st.integers(min_value=1, max_value=16),
        limb_count=st.integers(min_value=1, max_value=12),
        lazy=st.booleans(),
        schedule=st.sampled_from(("auto", "radix2", "four_step")),
    )
    def test_resolved_spec_fits_budget_property(
        self, logn, seg_count, limb_count, lazy, schedule
    ):
        """Property: whatever plan-shaped knobs come in, the resolved
        spec either fits the VMEM budget (tile_bytes consistent with a
        recomputation of the tile model) or resolution raises the
        structured unservable error — never a silent over-budget spec."""
        n = 1 << logn
        try:
            spec = sched.resolve_spec(
                n, schedule, seg_count=seg_count, limb_count=limb_count,
                lazy=lazy,
            )
        except repro.UnservableConfigError as e:
            assert e.knob is not None
            return
        assert spec.kind in ("radix2", "four_step")
        assert spec.row_blk >= 1
        assert spec.tile_bytes <= spec.vmem_budget
        assert spec.tile_bytes == sched.tile_bytes_model(
            spec.kind, n, spec.splits, spec.row_blk, seg_count,
            limb_count, lazy,
        )

    def test_default_row_blk_halves_until_fit(self):
        """Deterministic pin of the property above: at n=65536 with a
        wide operand the default row block must shrink below
        DEFAULT_E2E_ROW_BLK to fit, and the result still fits."""
        spec = sched.resolve_spec(
            65536, "four_step", seg_count=4, limb_count=8, lazy=True
        )
        assert spec.row_blk < sched.DEFAULT_E2E_ROW_BLK
        assert spec.tile_bytes <= spec.vmem_budget


# --------------------------------------------------------------------------
# Error taxonomy
# --------------------------------------------------------------------------


class TestErrorTaxonomy:
    def test_unknown_schedule_string(self):
        with pytest.raises(repro.UnknownKnobError) as ei:
            repro.plan(n=64, t=3, v=30, schedule="radix4")
        assert ei.value.knob == "schedule"
        assert ei.value.value == "radix4"
        assert "four_step" in ei.value.alternatives

    def test_hier_unservable_below_8192(self):
        with pytest.raises(repro.UnservableConfigError) as ei:
            repro.plan(n=4096, t=2, v=30, schedule="four_step:h")
        assert ei.value.knob == "schedule"
        assert "four_step" in ei.value.alternatives

    def test_errors_are_valueerrors(self):
        """Back-compat: every taxonomy member still satisfies the
        pre-PR-7 ``pytest.raises(ValueError)`` call sites."""
        assert issubclass(repro.PlanError, ValueError)
        assert issubclass(repro.UnknownKnobError, repro.PlanError)
        assert issubclass(repro.UnservableConfigError, repro.PlanError)

    def test_mismatched_tiling_hint_unservable(self):
        with pytest.raises(repro.UnservableConfigError) as ei:
            repro.plan(
                n=256, t=3, v=30, backend="pallas_fused_e2e",
                schedule="four_step", tiling=((4, 64),),
            )
        assert ei.value.knob == "tiling"
        assert ei.value.alternatives == (((2, 128),),)

    def test_non_power_of_two_row_blk(self):
        with pytest.raises(repro.UnknownKnobError) as ei:
            repro.plan(n=64, t=3, v=30, row_blk=3)
        assert ei.value.knob == "row_blk"
        assert ei.value.value == 3

    def test_oversized_row_blk_unservable_big_n(self):
        """Valid row_blk vocabulary, unservable combination: at n=65536
        a wide explicit row block blows the VMEM tile budget, and the
        error names smaller row blocks that do fit."""
        with pytest.raises(repro.UnservableConfigError) as ei:
            sched.resolve_spec(
                65536, "four_step", row_blk=8, seg_count=4,
                limb_count=8, lazy=True,
            )
        err = ei.value
        assert err.knob == "row_blk"
        assert err.value == 8
        assert err.alternatives  # at least one servable fallback
        for rb in err.alternatives:
            assert sched.tile_bytes_model(
                "four_step", 65536, sched.concrete_spec(65536, "four_step").splits,
                rb, 4, 8, True,
            ) <= sched.VMEM_BUDGET_BYTES

    def test_no_servable_row_blk_names_n(self):
        with pytest.raises(repro.UnservableConfigError) as ei:
            sched.resolve_spec(
                65536, "four_step", seg_count=512, limb_count=512,
                lazy=True,
            )
        assert ei.value.knob == "n"
        assert ei.value.value == 65536


# --------------------------------------------------------------------------
# Retrace probe: spec identity == jit identity
# --------------------------------------------------------------------------


class TestSpecRetrace:
    def test_string_and_spec_routes_share_one_trace(self):
        """plan(schedule="four_step") and plan(schedule=<frozen spec>)
        produce equal configs, hence one compilation."""
        traces = []

        def f(pl, za, zb):
            traces.append(1)
            return repro.polymul(pl, za, zb)

        fj = jax.jit(f)
        a = repro.plan(n=256, t=2, v=30, schedule="four_step")
        b = repro.plan(n=256, t=2, v=30, schedule=a.config.schedule)
        c = repro.plan(n=256, t=2, v=30)  # auto -> same four_step spec
        rng = np.random.default_rng(3)
        import jax.numpy as jnp
        za = jnp.asarray(
            rng.integers(0, 1 << 30, size=(256, a.config.seg_count))
        )
        zb = jnp.asarray(
            rng.integers(0, 1 << 30, size=(256, a.config.seg_count))
        )
        fj(a, za, zb)
        fj(b, za, zb)
        fj(c, za, zb)
        assert len(traces) == 1
        fj(repro.plan(n=256, t=2, v=30, schedule="radix2"), za, zb)
        assert len(traces) == 2


# --------------------------------------------------------------------------
# Oracles + big-n acceptance
# --------------------------------------------------------------------------


class TestHostNttOracle:
    @pytest.mark.parametrize("n", [8, 64, 256])
    def test_matches_schoolbook(self, n):
        q = primes_mod.default_prime_set(n, 1, 30)[0].q
        rng = random.Random(n)
        a = [rng.randrange(q) for _ in range(n)]
        b = [rng.randrange(q) for _ in range(n)]
        assert pm.ntt_negacyclic_host(a, b, q) == pm.schoolbook_negacyclic(
            a, b, q
        )


class TestBigNAcceptance:
    """The PR's acceptance gate: hierarchical sizes bit-exact through
    the PUBLIC plan/polymul API on the fused-e2e Pallas backend
    (interpret mode off-TPU), against the bigint oracle."""

    @pytest.mark.parametrize("n,schedule,depth", [
        (4096, "four_step", 1),
        (8192, "four_step:h", 2),
    ])
    def test_fused_e2e_bit_exact_vs_oracle(self, n, schedule, depth):
        pl = repro.plan(
            n=n, t=2, v=30, backend="pallas_fused_e2e", schedule=schedule
        )
        assert pl.config.schedule.depth == depth
        p = pl.params
        rng = random.Random(n)
        a = [rng.randrange(p.q) for _ in range(n)]
        b = [rng.randrange(p.q) for _ in range(n)]
        assert repro.polymul_ints(pl, a, b) == pm.oracle_multiply(a, b, p)

    def test_n4096_fused_e2e_spec_pinned(self):
        """The n=4096 fused-e2e point's frozen spec and modelled traffic,
        pinned as literals: its tile fits the VMEM budget."""
        pl = repro.plan(
            n=4096, t=2, v=30, backend="pallas_fused_e2e",
            schedule="four_step",
        )
        spec = pl.config.schedule
        assert str(spec) == "four_step[32.128]"
        assert (spec.depth, spec.row_blk, spec.tile_bytes) == (1, 4, 1179648)
        assert spec.tile_bytes <= spec.vmem_budget == 16 << 20
        m = ops.hbm_traffic_model(
            pl.params, rows=1, backend="pallas_fused_e2e"
        )
        assert (m["hbm_bytes"], m["kernel_launches"]) == (229376, 1)
