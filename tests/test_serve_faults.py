"""The fault-injection harness and the engine's exactly-once contract
under arbitrary seeded fault schedules.

The property test proper runs under ``hypothesis`` when installed; a
seeded parametrized sweep covers the same invariant unconditionally, so
the contract is exercised in every environment (the shim in
``_hypothesis_fallback`` turns ``@given`` tests into skips when the
package is absent)."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover - exercised on bare CI images
    from _hypothesis_fallback import given, settings, st

import repro
from repro import api
from repro.errors import EngineError
from repro.serve.crypto_engine import PolymulEngine
from repro.serve.faults import (
    FaultInjector,
    FaultRule,
    InjectedFault,
    spot_check,
)


def _mk(pl, rng):
    shape = (pl.n, pl.config.seg_count)
    return (
        rng.integers(0, 1 << pl.v, size=shape),
        rng.integers(0, 1 << pl.v, size=shape),
    )


class TestInjector:
    def test_rule_validation(self):
        with pytest.raises(ValueError, match="raise/delay/corrupt"):
            FaultRule("explode")
        with pytest.raises(ValueError, match="rate"):
            FaultRule("raise", rate=1.5)

    def test_schedule_is_deterministic(self):
        """Same (rules, seed, call sequence) -> identical fault log."""
        pl = repro.plan(n=64, t=3, v=30)

        def run(seed):
            inj = FaultInjector(
                [
                    FaultRule("raise", rate=0.3, max_count=3),
                    FaultRule("corrupt", rate=0.3, at=(5,)),
                ],
                seed=seed,
            )
            fn = inj.wrap(lambda p, a, b: np.zeros((1,), np.int64))
            for _ in range(20):
                try:
                    fn(pl, None, None)
                except InjectedFault:
                    pass
            return list(inj.log)

        assert run(7) == run(7)
        assert run(7) != run(8)  # the seed actually matters
        assert any(i == 5 and k == "corrupt" for i, k, _ in run(7))

    def test_raise_beats_corrupt_and_quiesce(self):
        pl = repro.plan(n=64, t=3, v=30)
        inj = FaultInjector(
            [FaultRule("raise", at=(0,), rate=0.0),
             FaultRule("corrupt", at=(0, 1), rate=0.0)],
            seed=0,
        )
        fn = inj.wrap(lambda p, a, b: np.zeros((2,), np.int64))
        with pytest.raises(InjectedFault):
            fn(pl, None, None)  # call 0: raise wins, corrupt never fires
        assert inj.indices("corrupt") == set()
        out = fn(pl, None, None)  # call 1: corrupt fires
        assert inj.indices("corrupt") == {1}
        assert np.array_equal(out, np.ones((2,), np.int64))
        inj.quiesce()
        fn(pl, None, None)
        assert len(inj.log) == 2  # nothing fires after quiesce

    def test_corruption_detected_by_spot_check(self):
        rng = np.random.default_rng(0)
        eng = PolymulEngine(batch_slots=2)
        pl = eng.plan(n=64, t=3, v=30)
        inj = FaultInjector([FaultRule("corrupt", at=(0,), rate=0.0)],
                            seed=0).install(eng)
        za, zb = _mk(pl, rng)
        fut = eng.submit(pl, za, zb)
        eng.run_until_idle()
        assert fut.exception() is None  # corruption is engine-invisible
        assert fut.dispatch_index in inj.indices("corrupt")
        assert not spot_check(pl, za, zb, fut.result())
        # a clean re-serve passes both detection arms
        za2, zb2 = _mk(pl, rng)
        fut2 = eng.submit(pl, za2, zb2)
        eng.run_until_idle()
        assert spot_check(pl, za2, zb2, fut2.result())
        assert spot_check(pl, za2, zb2, fut2.result(), use_oracle=True)


def _exactly_once_under_schedule(seed: int, threaded: bool = False) -> None:
    """THE property: under an arbitrary seeded schedule of raises,
    delays, and corruptions, every submitted request resolves exactly
    once — a value or a typed EngineError, no losses, no duplicates —
    and every un-corrupted result is bit-exact vs api.polymul.
    ``threaded`` serves through the background dispatcher, whose two
    workers may enter executor calls out of dispatch-index order."""
    rng = np.random.default_rng(seed)
    eng = PolymulEngine(
        batch_slots=4, max_retries=8, breaker_threshold=2,
        breaker_cooldown_s=0.02, backoff_base_s=1e-4,
    )
    plans = [eng.plan(n=64, t=3, v=30), eng.plan(n=32, t=4, v=45)]
    inj = FaultInjector(
        [
            FaultRule("raise", rate=float(rng.uniform(0.05, 0.3)),
                      max_count=int(rng.integers(1, 6))),
            FaultRule("delay", rate=0.1, delay_s=0.001, max_count=4),
            FaultRule("corrupt", rate=float(rng.uniform(0.05, 0.3)),
                      max_count=int(rng.integers(1, 5))),
        ],
        seed=seed,
    ).install(eng)
    if threaded:
        eng.start()
    entries = []
    for i in range(24):
        pl = plans[i % 2]
        za, zb = _mk(pl, rng)
        entries.append((pl, za, zb, eng.submit(pl, za, zb)))
    eng.run_until_idle()
    eng.stop()

    assert eng.pending() == 0
    s = eng.stats
    assert s["served"] + s["shed"] + s["failed"] == s["submitted"] == 24
    corrupt_idx = inj.indices("corrupt")
    for pl, za, zb, fut in entries:
        assert fut.done(), "future lost (never resolved)"
        if fut.state == "FAILED":
            assert isinstance(fut.exception(), EngineError)
            continue
        want = np.asarray(api.polymul(pl, za[None], zb[None]))[0]
        if fut.dispatch_index in corrupt_idx:
            assert not np.array_equal(fut.result(), want)
        else:
            assert np.array_equal(fut.result(), want)
    # exactly-once: the lifecycle refuses a second transition
    fut = entries[0][3]
    with pytest.raises(RuntimeError, match="resolved twice"):
        fut._fail(RuntimeError("dup"))


class TestExactlyOnceProperty:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_exactly_once_seeded(self, seed):
        _exactly_once_under_schedule(seed)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_exactly_once_seeded_threaded(self, seed):
        _exactly_once_under_schedule(seed, threaded=True)

    @given(seed=st.integers(min_value=0, max_value=2**20))
    @settings(max_examples=8, deadline=None)
    def test_exactly_once_property(self, seed):
        _exactly_once_under_schedule(seed)


@pytest.mark.slow
def test_soak_smoke_end_to_end():
    """An importable mini-run of the CI soak driver: all gates green on
    a reduced request count (the full 500+-request soak is the
    serve-soak CI step)."""
    from repro.launch.serve_soak import run_soak

    record = run_soak(requests=120, seed=0)
    assert record["failures"] == []
    assert record["breaker_opened"] >= 1
    assert record["breaker_recovered"] >= 1
    assert record["faults"]["corrupted"] >= 1
