"""The crypto serving subsystem: shape-bucketed continuous batching
(PolymulEngine), the mesh-sharded cascade (`model` x `data` shard_map
with plan tables resident per-shard), and the crypto partition rules.

Mesh tests run on REAL 4-device host meshes — conftest.py forces
``--xla_force_host_platform_device_count=4`` before jax initializes.
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import repro
from repro import api, obs
from repro.core import polymul as pm
from repro.obs.metrics import MetricsRegistry
from repro.serve.crypto_engine import (
    PolymulEngine,
    negacyclic_mul_sharded,
    polymul_sharded,
)
from repro.sharding import partition


def _rand_segments(pl, rng, batch=None):
    shape = (pl.n, pl.config.seg_count)
    if batch is not None:
        shape = (batch,) + shape
    return (
        rng.integers(0, 1 << pl.v, size=shape),
        rng.integers(0, 1 << pl.v, size=shape),
    )


def _rand_residues(pl, rng, batch):
    return jnp.asarray(
        np.stack(
            [
                rng.integers(0, int(q), size=(batch, pl.n))
                for q in pl.params.plan.qs
            ]
        )
    )


class TestEngineBatching:
    def test_mixed_preset_stream_bit_exact_vs_oracle(self):
        """Both paper presets interleaved through ONE engine: every
        result bit-exact vs the bigint oracle, and exactly one jit
        trace per distinct PlanConfig (the acceptance criterion)."""
        eng = PolymulEngine(batch_slots=4)
        plans = [eng.plan(n=64, t=3, v=30), eng.plan(n=32, t=4, v=45)]
        import random

        r = random.Random(0)
        reqs = []
        for i in range(10):
            pl = plans[i % 2]
            a = [r.randrange(pl.q) for _ in range(pl.n)]
            b = [r.randrange(pl.q) for _ in range(pl.n)]
            za = np.asarray(api.to_segments(pl, a))
            zb = np.asarray(api.to_segments(pl, b))
            reqs.append((pl, a, b, eng.submit(pl, za, zb)))
        eng.run_until_idle()
        for pl, a, b, fut in reqs:
            got = api.from_limbs(pl, fut.result())
            assert got == pm.oracle_multiply(a, b, pl.params)
        assert eng.trace_count == 2  # one compile per distinct config
        assert sorted(
            set(eng.traced_configs), key=lambda c: c.v
        ) == sorted({api.plan_key(p) for p in plans}, key=lambda c: c.v)

    def test_padding_and_slot_reuse_invariants(self):
        """9 requests through 4 slots -> 3 dispatches (4+4+1), 3 padded
        slots total, still ONE trace: the padded batch shape is stable
        across dispatches."""
        rng = np.random.default_rng(1)
        eng = PolymulEngine(batch_slots=4)
        pl = eng.plan(n=64, t=3, v=30)
        futs = []
        want = []
        for _ in range(9):
            za, zb = _rand_segments(pl, rng)
            futs.append(eng.submit(pl, za, zb))
            want.append(
                np.asarray(repro.polymul(pl, jnp.asarray(za), jnp.asarray(zb)))
            )
        assert eng.pending() == 9
        assert eng.step() == 4
        assert eng.pending() == 5
        eng.run_until_idle()
        assert eng.stats["dispatches"] == 3
        assert eng.stats["padded_slots"] == 3
        assert eng.stats["served"] == 9
        assert eng.trace_count == 1
        for fut, w in zip(futs, want):
            assert np.array_equal(fut.result(), w)
            assert fut.latency_s >= 0

    def test_plan_cache_hits(self):
        eng = PolymulEngine()
        a = eng.plan(n=64, t=3, v=30)
        b = eng.plan(n=64, t=3, v=30)
        assert a is b  # cached by plan_key
        c = eng.plan(n=64, t=3, v=30, backend="pallas_fused")
        assert c is not a

    def test_future_unserved_raises(self):
        rng = np.random.default_rng(2)
        eng = PolymulEngine(batch_slots=2)
        pl = eng.plan(n=64, t=3, v=30)
        fut = eng.submit(pl, *_rand_segments(pl, rng))
        assert not fut.done()
        with pytest.raises(RuntimeError, match="not served"):
            fut.result()
        eng.run_until_idle()
        assert fut.done()

    def test_submit_shape_validation(self):
        eng = PolymulEngine()
        pl = eng.plan(n=64, t=3, v=30)
        bad = np.zeros((32, pl.config.seg_count), np.int64)
        ok = np.zeros((64, pl.config.seg_count), np.int64)
        with pytest.raises(ValueError, match="expected za segments"):
            eng.submit(pl, bad, ok)

    def test_oracle_width_requests_served_eagerly(self):
        """v > 46 buckets run the host oracle: no tracing, no padding,
        results still exact (vs the schoolbook)."""
        import random

        r = random.Random(3)
        eng = PolymulEngine(batch_slots=4)
        pl = eng.plan(n=32, t=2, v=50)
        a = [r.randrange(pl.q) for _ in range(pl.n)]
        b = [r.randrange(pl.q) for _ in range(pl.n)]
        fut = eng.submit(
            pl,
            np.asarray(api.to_segments(pl, a)),
            np.asarray(api.to_segments(pl, b)),
        )
        eng.run_until_idle()
        assert api.from_limbs(pl, fut.result()) == pm.schoolbook_negacyclic(
            a, b, pl.q
        )
        assert eng.trace_count == 0
        assert eng.stats["padded_slots"] == 0

    def test_execute_hook_and_plan_key(self):
        rng = np.random.default_rng(4)
        pl = repro.plan(n=64, t=3, v=30)
        assert api.plan_key(pl) == pl.config
        za, zb = _rand_segments(pl, rng, batch=2)
        want = np.asarray(repro.polymul(pl, jnp.asarray(za), jnp.asarray(zb)))
        got = api.execute(pl, jnp.asarray(za), jnp.asarray(zb))
        assert np.array_equal(np.asarray(got), want)
        # donating twin: operands are consumed, result identical
        got_d = api.execute(
            pl, jnp.asarray(za), jnp.asarray(zb), donate=True
        )
        assert np.array_equal(np.asarray(got_d), want)


def _packed_readbacks(eng) -> float:
    return eng._registry.get("repro_engine_packed_readbacks_total").labels(
        engine=eng.name
    ).value


def _schoolbook_mod_q(pl, za, zb):
    """Plain Python negacyclic product of two segment polynomials, mod
    q, as base-2^w limbs: the check owes nothing to the program."""
    v, w, n, q = pl.v, pl.config.w, pl.n, pl.q

    def value(segs):
        return sum(int(d) << (v * j) for j, d in enumerate(segs))

    a = [value(r) for r in za]
    b = [value(r) for r in zb]
    c = [0] * n
    for i in range(n):
        for j in range(n):
            if i + j < n:
                c[i + j] += a[i] * b[j]
            else:
                c[i + j - n] -= a[i] * b[j]
    mask = (1 << w) - 1
    return np.array([[((x % q) >> (w * k)) & mask for k in range(pl.config.L)]
                     for x in c], np.int64)


# (n, t, v) of one small plan per compiled width
_WIDTH_PLANS = [
    pytest.param((64, 3, 30), id="int64"),
    pytest.param((64, 4, 45), id="wide"),
]


class TestInt32Readback:
    """The batch executable hands its result to the host as int32, and
    the executor widens it back to the ``(batch_slots, n, L)`` int64
    contract on the host."""

    @pytest.mark.parametrize("traced", [False, True],
                             ids=["untraced", "traced"])
    @pytest.mark.parametrize("ntv", _WIDTH_PLANS)
    def test_result_crosses_as_int32_and_is_widened(self, ntv, traced):
        n, t, v = ntv
        eng = PolymulEngine(
            batch_slots=4, registry=MetricsRegistry(),
            span_log=obs.SpanLog(None) if traced else None,
        )
        pl = eng.plan(n=n, t=t, v=v)
        L = pl.config.L
        rng = np.random.default_rng(14)
        za, zb = _rand_segments(pl, rng, batch=4)
        dev = eng._executable(pl)(pl, za, zb)
        assert dev.shape == (4, n, L) and dev.dtype == jnp.int32
        seen = []
        inner = eng.executor

        def spy(p, a, b):
            out = inner(p, a, b)
            seen.append((a, b, out))
            return out

        eng.executor = spy
        operands = [_rand_segments(pl, rng) for _ in range(6)]
        futs = [eng.submit(pl, a, b) for a, b in operands]
        eng.run_until_idle()
        assert len(seen) == eng.stats["dispatches"] == 2  # 4 + 2 padded
        for a, b, out in seen:
            assert out.shape == (4, n, L) and out.dtype == np.int64
            assert out.flags.c_contiguous
            want = np.asarray(api.polymul(pl, jnp.asarray(a), jnp.asarray(b)))
            assert np.array_equal(out, want)
        assert np.array_equal(futs[5].result(), seen[1][2][1])
        # a full batch and a partial one, each product exact mod q
        for fut, (a, b) in zip(futs, operands):
            assert np.array_equal(fut.result(), _schoolbook_mod_q(pl, a, b))
        assert _packed_readbacks(eng) == eng.stats["dispatches"]

    def test_traced_readback_bytes_are_the_int32_result(self):
        log = obs.SpanLog(None)
        eng = PolymulEngine(batch_slots=4, registry=MetricsRegistry(),
                            span_log=log)
        pl = eng.plan(n=64, t=3, v=30)
        eng.submit(pl, *_rand_segments(pl, np.random.default_rng(15)))
        eng.run_until_idle()
        (readback,) = [s for s in log.spans() if s["name"] == "engine.readback"]
        assert readback["attrs"]["bytes"] == 4 * 64 * pl.config.L * 4  # int32
        assert _packed_readbacks(eng) == 1


class TestCryptoPartitionRules:
    def test_polymul_specs_layout(self, host_mesh_4):
        pl = repro.plan(n=64, t=6, v=30)
        specs = partition.polymul_specs(host_mesh_4, pl)
        assert specs["segments"] == P(("data",), None, None)
        assert specs["residues"] == P("model", ("data",), None)
        assert specs["limbs"] == P(("data",), None, None)

    def test_polymul_specs_nondivisible_channel_fallback(self, host_mesh_4):
        pl = repro.plan(n=64, t=3, v=30)  # 3 % 2 != 0 -> replicate channels
        specs = partition.polymul_specs(host_mesh_4, pl)
        assert specs["residues"] == P(None, ("data",), None)

    def test_plan_leaf_specs_channel_major(self, host_mesh_4):
        pl = repro.plan(n=64, t=6, v=30)
        specs = partition.plan_leaf_specs(host_mesh_4, pl)
        for name, leaf in pl.consts.items():
            if name == "rns_q_limbs":
                assert specs[name] == P(*([None] * leaf.ndim)), name
            else:
                assert specs[name][0] == "model", name
                assert len(specs[name]) == leaf.ndim

    def test_plan_tables_resident_per_shard(self, host_mesh_4):
        """device_put with the leaf shardings leaves each model shard
        holding exactly its channels' tables (t/2 rows per shard on the
        2-way model axis) — 'plan tables resident per-shard'."""
        pl = repro.plan(n=64, t=6, v=30)
        consts = jax.device_put(
            pl.consts, partition.plan_leaf_shardings(host_mesh_4, pl)
        )
        fwd = consts["ntt_fwd"]  # (t, n)
        assert not fwd.sharding.is_fully_replicated
        shard_shapes = {s.data.shape for s in fwd.addressable_shards}
        assert shard_shapes == {(3, 64)}
        assert consts["rns_q_limbs"].sharding.is_fully_replicated


class TestMeshShardedCascade:
    def test_model_axis_shard_map_bit_exact(self, host_mesh_4):
        """The acceptance criterion: the model-axis shard_map path of
        negacyclic_mul is bit-exact vs the single-device path."""
        rng = np.random.default_rng(5)
        pl = repro.plan(n=64, t=6, v=30)
        a = _rand_residues(pl, rng, batch=4)
        b = _rand_residues(pl, rng, batch=4)
        want = np.asarray(repro.negacyclic_mul(pl, a, b))
        got = negacyclic_mul_sharded(pl, a, b, mesh=host_mesh_4)
        assert np.array_equal(np.asarray(got), want)

    def test_sharded_cascade_reads_leaves_not_constants(self, host_mesh_4):
        """int64 leaves threaded, not jit constants: mutating a plan's
        twiddle leaf MUST change the sharded result — if the kernels
        bound tables from the static params, this would be a no-op."""
        rng = np.random.default_rng(6)
        pl = repro.plan(n=64, t=6, v=30)
        a = _rand_residues(pl, rng, batch=2)
        b = _rand_residues(pl, rng, batch=2)
        want = np.asarray(negacyclic_mul_sharded(pl, a, b, mesh=host_mesh_4))
        broken_consts = dict(pl.consts)
        broken_consts["ntt_fwd"] = (
            broken_consts["ntt_fwd"] ^ 1
        )  # flip low bits
        broken = api.Plan(
            config=pl.config, params=pl.params, consts=broken_consts
        )
        got = np.asarray(
            negacyclic_mul_sharded(broken, a, b, mesh=host_mesh_4)
        )
        assert not np.array_equal(got, want)

    def test_polymul_sharded_jit_bit_exact(self, host_mesh_4):
        rng = np.random.default_rng(7)
        pl = repro.plan(n=64, t=6, v=30)
        za, zb = _rand_segments(pl, rng, batch=4)
        za, zb = jnp.asarray(za), jnp.asarray(zb)
        want = np.asarray(repro.polymul(pl, za, zb))
        fn = jax.jit(
            lambda p, x, y: polymul_sharded(p, x, y, mesh=host_mesh_4)
        )
        assert np.array_equal(np.asarray(fn(pl, za, zb)), want)

    def test_sharded_rejects_bad_configs(self, host_mesh_4):
        rng = np.random.default_rng(8)
        pl = repro.plan(n=64, t=3, v=30)  # 3 channels % 2-way model != 0
        a = _rand_residues(pl, rng, batch=2)
        with pytest.raises(ValueError, match="do not divide the model"):
            negacyclic_mul_sharded(pl, a, a, mesh=host_mesh_4)
        # wide plans now shard (see TestWideMeshSharding in
        # test_sharding.py); only the host-bigint oracle width is refused
        orc = repro.plan(n=32, t=2, v=50)
        res = jnp.zeros((4, 2, 32), jnp.int64)
        with pytest.raises(ValueError, match="int64/wide-width plans only"):
            negacyclic_mul_sharded(orc, res, res, mesh=host_mesh_4)
        pl6 = repro.plan(n=64, t=6, v=30)
        odd = _rand_residues(pl6, rng, batch=3)  # 3 % data-size 2 != 0
        with pytest.raises(ValueError, match="does not divide the data"):
            negacyclic_mul_sharded(pl6, odd, odd, mesh=host_mesh_4)

    def test_engine_mesh_mode_end_to_end(self, host_mesh_4):
        rng = np.random.default_rng(9)
        eng = PolymulEngine(batch_slots=4, mesh=host_mesh_4)
        pl = eng.plan(n=64, t=6, v=30)
        futs, want = [], []
        for _ in range(6):
            za, zb = _rand_segments(pl, rng)
            futs.append(eng.submit(pl, za, zb))
            want.append(
                np.asarray(repro.polymul(pl, jnp.asarray(za), jnp.asarray(zb)))
            )
        eng.run_until_idle()
        for fut, w in zip(futs, want):
            assert np.array_equal(fut.result(), w)
        assert eng.trace_count == 1
        assert eng.stats["dispatches"] == 2
        assert eng.stats["padded_slots"] == 2

    def test_engine_mesh_mode_places_tables_per_shard(self, host_mesh_4):
        """The engine's mesh-mode plan holds each model shard's channel
        tables on that shard's devices, not everything on device 0."""
        eng = PolymulEngine(batch_slots=4, mesh=host_mesh_4)
        pl = eng.plan(n=64, t=6, v=30)
        specs = partition.plan_leaf_specs(host_mesh_4, pl)
        sharded = [k for k, sp in specs.items() if len(sp) and sp[0] == "model"]
        assert sharded
        for k in sharded:
            shards = pl.consts[k].addressable_shards
            assert {s.data.shape[0] for s in shards} == {3}
            assert len({s.device for s in shards}) == 4
        assert eng.plan(n=64, t=6, v=30) is pl

    def test_engine_mesh_mode_rejects_nonsharding_slots(self, host_mesh_4):
        with pytest.raises(ValueError, match="batch_slots"):
            PolymulEngine(batch_slots=3, mesh=host_mesh_4)
        eng = PolymulEngine(batch_slots=4, mesh=host_mesh_4)
        orc = repro.plan(n=32, t=2, v=50)
        z = np.zeros((32, orc.config.seg_count), np.int64)
        with pytest.raises(ValueError, match="int64/wide-width plans only"):
            eng.submit(orc, z, z)

    def test_engine_mesh_mode_rejects_indivisible_t_at_submit(
        self, host_mesh_4
    ):
        """A config that could only fail at trace time would lose its
        already-popped requests — the engine must refuse it at submit
        (the queue stays intact, no future is ever orphaned)."""
        eng = PolymulEngine(batch_slots=4, mesh=host_mesh_4)
        pl = repro.plan(n=64, t=3, v=30)  # 3 % 2-way model != 0
        z = np.zeros((64, pl.config.seg_count), np.int64)
        with pytest.raises(ValueError, match="do not divide"):
            eng.submit(pl, z, z)
        assert eng.pending() == 0
        assert eng.stats["submitted"] == 0


class TestFailureSemantics:
    """PR 8: the engine's robustness contract — no request is ever lost,
    every future resolves exactly once with a value or a typed error."""

    def _mk(self, pl, rng):
        shape = (pl.n, pl.config.seg_count)
        return (
            rng.integers(0, 1 << pl.v, size=shape),
            rng.integers(0, 1 << pl.v, size=shape),
        )

    def test_dispatch_failure_requeues_not_loses(self):
        """THE regression for the request-loss bug: a dispatch that
        raises must leave its popped requests requeued (futures still
        pending and eventually served), not dropped with unresolvable
        futures."""
        rng = np.random.default_rng(0)
        eng = PolymulEngine(batch_slots=4, backoff_base_s=1e-4)
        pl = eng.plan(n=64, t=3, v=30)
        raw = eng.executor
        boom = {"left": 1}

        def flaky(p, za, zb):
            if boom["left"] > 0:
                boom["left"] -= 1
                raise RuntimeError("transient device fault")
            return raw(p, za, zb)

        eng.executor = flaky
        reqs = [self._mk(pl, rng) for _ in range(3)]
        futs = [eng.submit(pl, za, zb) for za, zb in reqs]
        assert eng.step() == 0  # failed dispatch resolves nothing...
        assert eng.pending() == 3  # ...and loses nothing
        assert all(not f.done() for f in futs)
        eng.run_until_idle()
        for f, (za, zb) in zip(futs, reqs):
            assert f.exception() is None
            want = np.asarray(api.polymul(pl, za[None], zb[None]))[0]
            assert np.array_equal(f.result(), want)
        assert eng.stats["retried"] == 3
        assert eng.stats["dispatch_failures"] == 1
        assert eng.stats["served"] == 3

    def test_retries_exhausted_fails_typed(self):
        from repro.errors import BackendFailedError, EngineError

        rng = np.random.default_rng(1)
        eng = PolymulEngine(
            batch_slots=2, max_retries=2, breaker_threshold=100,
            backoff_base_s=1e-4,
        )
        pl = eng.plan(n=64, t=3, v=30)

        def dead(p, za, zb):
            raise RuntimeError("hard fault")

        eng.executor = dead
        fut = eng.submit(pl, *self._mk(pl, rng))
        eng.run_until_idle()
        exc = fut.exception()
        assert isinstance(exc, BackendFailedError)
        assert isinstance(exc, EngineError)
        assert exc.attempts == 3  # first attempt + max_retries
        assert isinstance(exc.__cause__, RuntimeError)
        with pytest.raises(BackendFailedError):
            fut.result()
        assert fut.state == "FAILED"
        assert eng.stats["failed"] == 1
        assert eng.stats["served"] == 0

    def test_breaker_degrades_bit_exact_and_recovers(self):
        """Consecutive e2e failures open the bucket's breaker onto the
        pallas fallback (same n/t/v -> bit-exact), and the post-cooldown
        probe restores the original backend."""
        import time as _time

        rng = np.random.default_rng(2)
        eng = PolymulEngine(
            batch_slots=2, max_retries=6, breaker_threshold=2,
            breaker_cooldown_s=0.05, backoff_base_s=1e-4,
        )
        pl = eng.plan(n=64, t=3, v=30, backend="pallas_fused_e2e")
        raw = eng.executor

        def e2e_down(p, za, zb):
            if api.plan_key(p).backend == "pallas_fused_e2e":
                raise RuntimeError("fused-e2e kernel fault")
            return raw(p, za, zb)

        eng.executor = e2e_down
        za, zb = self._mk(pl, rng)
        fut = eng.submit(pl, za, zb)
        eng.run_until_idle()
        snap = eng.snapshot()
        assert snap["breaker_opened"] == 1
        assert snap["degraded_buckets"] == 1
        assert list(snap["bucket_backends"].values()) == ["pallas"]
        want = np.asarray(api.polymul(pl, za[None], zb[None]))[0]
        assert np.array_equal(fut.result(), want)  # degraded, bit-exact

        eng.executor = raw  # backend "repaired"
        _time.sleep(0.06)  # past the cool-down
        fut2 = eng.submit(pl, *self._mk(pl, rng))
        eng.run_until_idle()
        snap = eng.snapshot()
        assert snap["probes"] >= 1
        assert snap["breaker_recovered"] == 1
        assert snap["degraded_buckets"] == 0
        assert list(snap["bucket_backends"].values()) == [
            "pallas_fused_e2e"
        ]
        assert fut2.exception() is None

    def test_compile_failure_is_typed_and_never_retried(self):
        """A plan whose executable does not compile fails at warmup with
        CompileFailedError, and its requests fail with it at once: no
        retry, no backoff, no breaker, no other backend in its place."""
        from repro.errors import BackendFailedError, CompileFailedError

        class Refuses:
            def lower(self, *args):
                raise ValueError("the compiler refused the kernel")

        rng = np.random.default_rng(5)
        eng = PolymulEngine(batch_slots=2, breaker_threshold=1)
        pl = eng.plan(n=64, t=3, v=30, backend="pallas_fused_e2e")
        eng._exec = Refuses()
        with pytest.raises(CompileFailedError) as ei:
            eng.warmup(pl)
        assert isinstance(ei.value.__cause__, ValueError)
        futs = [eng.submit(pl, *self._mk(pl, rng)) for _ in range(3)]
        eng.run_until_idle()
        for f in futs:
            exc = f.exception()
            assert isinstance(exc, CompileFailedError)
            assert isinstance(exc, BackendFailedError)
            assert isinstance(exc.__cause__, ValueError)
            assert exc.attempts == 0
        snap = eng.snapshot()
        assert snap["failed"] == 3
        assert snap["retried"] == snap["dispatch_failures"] == 0
        assert snap["breaker_opened"] == snap["degraded_buckets"] == 0
        assert eng.trace_count == 0

    def test_deadline_shed_typed_never_dropped(self):
        from repro.errors import DeadlineExceededError

        rng = np.random.default_rng(3)
        eng = PolymulEngine(batch_slots=2)
        pl = eng.plan(n=64, t=3, v=30)
        # dead on arrival: shed at submit
        doa = eng.submit(pl, *self._mk(pl, rng), deadline=0.0)
        assert doa.done()
        assert isinstance(doa.exception(), DeadlineExceededError)
        assert doa.exception().request_seq is not None
        # expires while queued: shed at the next step
        import time as _time

        late = eng.submit(pl, *self._mk(pl, rng), deadline=0.005)
        _time.sleep(0.01)
        eng.step()
        assert isinstance(late.exception(), DeadlineExceededError)
        assert late.exception().late_s > 0
        assert eng.stats["shed"] == 2
        assert eng.stats["served"] == 0

    def test_backpressure_blocks_and_rejects(self):
        from repro.errors import QueueFullError

        rng = np.random.default_rng(4)
        eng = PolymulEngine(batch_slots=2, max_pending=2)
        pl = eng.plan(n=64, t=3, v=30)
        f1 = eng.submit(pl, *self._mk(pl, rng))
        f2 = eng.submit(pl, *self._mk(pl, rng))
        assert eng.try_submit(pl, *self._mk(pl, rng)) is None
        with pytest.raises(QueueFullError) as ei:
            eng.submit(pl, *self._mk(pl, rng), timeout=0.02)
        assert ei.value.queue_depth == 2
        assert ei.value.max_pending == 2
        assert eng.stats["rejected"] == 2
        eng.run_until_idle()
        assert eng.try_submit(pl, *self._mk(pl, rng)) is not None
        eng.run_until_idle()
        assert f1.done() and f2.done()

    def test_edf_orders_across_buckets_and_priority_ties(self):
        """EDF: the tighter-deadline bucket dispatches first even when
        the other bucket's request arrived earlier; among equal
        deadlines, higher priority wins."""
        rng = np.random.default_rng(5)
        eng = PolymulEngine(batch_slots=1)
        pl_a = eng.plan(n=64, t=3, v=30)
        pl_b = eng.plan(n=32, t=4, v=45)
        slow = eng.submit(pl_a, *self._mk(pl_a, rng), deadline=60.0)
        fast = eng.submit(pl_b, *self._mk(pl_b, rng), deadline=5.0)
        eng.step()
        assert fast.done() and not slow.done()
        eng.run_until_idle()
        # priority ties within one bucket at equal (absent) deadlines
        lo = eng.submit(pl_a, *self._mk(pl_a, rng), priority=0)
        hi = eng.submit(pl_a, *self._mk(pl_a, rng), priority=5)
        eng.step()
        assert hi.done() and not lo.done()
        eng.run_until_idle()

    def test_future_lifecycle_and_latency_stats(self):
        rng = np.random.default_rng(6)
        eng = PolymulEngine(batch_slots=2)
        pl = eng.plan(n=64, t=3, v=30)
        fut = eng.submit(pl, *self._mk(pl, rng))
        assert fut.state == "PENDING" and not fut.done()
        with pytest.raises(RuntimeError, match="not served"):
            fut.result()
        with pytest.raises(TimeoutError):
            fut.result(timeout=0.01)
        eng.run_until_idle()
        assert fut.state == "DONE" and fut.done()
        assert fut.exception() is None
        assert fut.latency_s >= 0
        assert fut.dispatch_index == 0
        snap = eng.snapshot()
        assert snap["latency_p50_ms"] is not None
        assert snap["latency_p99_ms"] >= snap["latency_p50_ms"]
        assert snap["queue_depth"] == 0 and snap["inflight"] == 0
        # exactly-once: a second resolution attempt is an engine bug
        with pytest.raises(RuntimeError, match="resolved twice"):
            fut._resolve(None, 0.0)

    def test_async_dispatcher_end_to_end(self):
        rng = np.random.default_rng(7)
        eng = PolymulEngine(batch_slots=4, max_pending=8)
        pl = eng.plan(n=64, t=3, v=30)
        reqs = [self._mk(pl, rng) for _ in range(10)]
        with eng:
            assert eng.running
            futs = [
                eng.submit(pl, za, zb, timeout=5.0) for za, zb in reqs
            ]
            outs = [f.result(timeout=30.0) for f in futs]
        assert not eng.running
        for (za, zb), out in zip(reqs, outs):
            want = np.asarray(api.polymul(pl, za[None], zb[None]))[0]
            assert np.array_equal(out, want)
        assert eng.stats["served"] == 10
        assert eng.pending() == 0


class _Gate:
    """Test-double wrapper of an engine executor: every call blocks
    until ``release`` is set, and the calls in progress are counted."""

    def __init__(self, inner):
        self.inner = inner
        self.release = threading.Event()
        self._cond = threading.Condition()
        self.active = 0
        self.peak = 0

    def __call__(self, pl, za, zb):
        with self._cond:
            self.active += 1
            self.peak = max(self.peak, self.active)
            self._cond.notify_all()
        try:
            assert self.release.wait(60), "the gate never opened"
            return self.inner(pl, za, zb)
        finally:
            with self._cond:
                self.active -= 1

    def wait_active(self, k: int) -> None:
        with self._cond:
            assert self._cond.wait_for(lambda: self.active >= k, 60), (
                f"{self.active} executor calls in progress, waited for {k}"
            )


def _overlapped_total(eng) -> float:
    return eng._registry.get("repro_engine_overlapped_dispatches_total").labels(
        engine=eng.name
    ).value


def _assert_exact(pl, operands, outs):
    for (a, b), out in zip(operands, outs):
        want = np.asarray(api.polymul(pl, a[None], b[None]))[0]
        assert np.array_equal(out, want)


class TestTwoInFlight:
    """Under ``start()`` two dispatcher workers keep up to two executor
    calls outstanding, and a second only on a full, healthy bucket."""

    def _gated(self, batch_slots=2, warm=True, **kw):
        eng = PolymulEngine(batch_slots=batch_slots,
                            registry=MetricsRegistry(), **kw)
        pl = eng.plan(n=64, t=3, v=30)
        if warm:
            eng.warmup(pl)
        gate = _Gate(eng.executor)
        eng.executor = gate
        return eng, pl, gate

    def test_full_batches_run_two_at_once_bit_exact(self):
        log = obs.SpanLog(None)
        eng, pl, gate = self._gated(span_log=log)
        rng = np.random.default_rng(16)
        operands = [_rand_segments(pl, rng) for _ in range(8)]  # 4 batches
        futs = [eng.submit(pl, a, b) for a, b in operands]
        with eng:
            gate.wait_active(2)
            time.sleep(0.1)
            assert gate.active == 2 and eng.snapshot()["inflight"] == 4
            gate.release.set()
            outs = [f.result(timeout=60) for f in futs]
        assert gate.peak == 2
        _assert_exact(pl, operands, outs)
        steps = [s for s in log.spans() if s["name"] == "engine.step"]
        assert len(steps) == eng.stats["dispatches"] == 4
        overlapped = sum(s["attrs"]["overlapped"] for s in steps)
        assert overlapped >= 1  # the second call began beside the first
        assert _overlapped_total(eng) == overlapped
        eng.reset_stats()
        assert _overlapped_total(eng) == 0

    def test_partial_batch_waits_behind_a_running_dispatch(self):
        eng, pl, gate = self._gated(batch_slots=4)
        rng = np.random.default_rng(17)
        operands = [_rand_segments(pl, rng) for _ in range(4)]
        with eng:
            futs = [eng.submit(pl, *operands[0])]
            gate.wait_active(1)
            futs += [eng.submit(pl, a, b) for a, b in operands[1:]]
            time.sleep(0.2)  # 3 < batch_slots queued: no second call
            assert gate.active == 1 and eng.pending() == 3
            gate.release.set()
            outs = [f.result(timeout=60) for f in futs]
        assert gate.peak == 1
        assert eng.stats["dispatches"] == 2  # the three went as one batch
        assert _overlapped_total(eng) == 0
        _assert_exact(pl, operands, outs)

    @pytest.mark.parametrize("state", ["degraded", "probing", "failing"])
    def test_unhealthy_bucket_keeps_one_dispatch(self, state):
        eng, pl, gate = self._gated(
            breaker_cooldown_s=0.0 if state == "probing" else 60.0
        )
        rng = np.random.default_rng(18)
        operands = [_rand_segments(pl, rng) for _ in range(6)]  # 3 batches
        futs = [eng.submit(pl, a, b) for a, b in operands]
        bucket = eng._buckets[api.plan_key(pl)]
        if state == "failing":
            bucket.failures = 1
        else:  # a fallback of the same plan: nothing new to compile
            bucket.chain.append(pl)
            bucket.level = 1
            bucket.opened_at = time.perf_counter()
        with eng:
            gate.wait_active(1)
            time.sleep(0.2)
            assert gate.active == 1 and eng.pending() == 4
            gate.release.set()
            outs = [f.result(timeout=60) for f in futs]
        _assert_exact(pl, operands, outs)
        snap = eng.snapshot()
        assert snap["probes"] == (state == "probing")
        if state == "degraded":  # stays degraded: one dispatch at a time
            assert gate.peak == 1 and snap["degraded_buckets"] == 1
            assert _overlapped_total(eng) == 0
        else:  # the first dispatch restored the bucket's health
            assert snap["degraded_buckets"] == 0 and bucket.failures == 0

    def test_two_workers_that_miss_compile_once(self):
        eng, pl, gate = self._gated(warm=False)
        gate.release.set()
        inner, compiles = eng._exec, []

        class SlowCompile:  # widens the window in which both miss
            def lower(self, *args):
                lowered = inner.lower(*args)

                class Lowered:
                    def compile(self):
                        compiles.append(1)
                        time.sleep(0.3)
                        return lowered.compile()

                return Lowered()

        eng._exec = SlowCompile()
        rng = np.random.default_rng(19)
        operands = [_rand_segments(pl, rng) for _ in range(8)]
        futs = [eng.submit(pl, a, b) for a, b in operands]
        with eng:
            outs = [f.result(timeout=120) for f in futs]
        assert len(compiles) == 1 and eng.trace_count == 1
        _assert_exact(pl, operands, outs)

    def test_stress_many_submitters_keep_the_counts(self):
        """16 submitting threads against the two workers, with the
        interpreter switching threads every 10 us: a lost update of
        ``_inflight``, the dispatch index or a counter breaks a count."""
        import sys

        log = obs.SpanLog(None)
        eng = PolymulEngine(batch_slots=4, registry=MetricsRegistry(),
                            span_log=log)
        pl = eng.plan(n=64, t=3, v=30)
        eng.warmup(pl)
        za, zb = _rand_segments(pl, np.random.default_rng(21), batch=128)
        want = np.asarray(api.polymul(pl, za, zb))
        futs = [None] * 128

        def client(c):
            for k in range(c, 128, 16):
                futs[k] = eng.submit(pl, za[k], zb[k])
                if k % 3 == 0:
                    futs[k].result(timeout=60)

        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            with eng:
                clients = [threading.Thread(target=client, args=(c,))
                           for c in range(16)]
                for t in clients:
                    t.start()
                for t in clients:
                    t.join(120)
                outs = [f.result(timeout=60) for f in futs]
        finally:
            sys.setswitchinterval(was)
        assert np.array_equal(np.stack(outs), want)
        snap = eng.snapshot()
        assert snap["served"] == snap["submitted"] == 128
        assert snap["inflight"] == snap["queue_depth"] == 0
        steps = [s for s in log.spans() if s["name"] == "engine.step"]
        indices = sorted(s["attrs"]["dispatch_index"] for s in steps)
        assert indices == list(range(snap["dispatches"]))
        assert {f.dispatch_index for f in futs} == set(indices)
        assert _overlapped_total(eng) == sum(
            s["attrs"]["overlapped"] for s in steps
        )

    def test_stop_joins_both_workers(self):
        eng = PolymulEngine(batch_slots=2, registry=MetricsRegistry())
        for _ in range(2):  # and starts again after a stop
            eng.start()
            workers = list(eng._threads)
            assert eng.running and len(workers) == 2
            assert all(t.is_alive() for t in workers)
            eng.stop()
            assert not eng.running
            assert not any(t.is_alive() for t in workers)

    def test_step_without_start_runs_one_dispatch(self):
        eng = PolymulEngine(batch_slots=2, registry=MetricsRegistry())
        pl = eng.plan(n=64, t=3, v=30)
        rng = np.random.default_rng(20)
        operands = [_rand_segments(pl, rng) for _ in range(6)]
        futs = [eng.submit(pl, a, b) for a, b in operands]
        assert eng.step() == 2
        assert eng.stats["dispatches"] == 1 and eng.pending() == 4
        eng.run_until_idle()
        assert eng.stats["dispatches"] == 3
        assert _overlapped_total(eng) == 0
        _assert_exact(pl, operands, [f.result() for f in futs])


class TestEntryPoints:
    def test_serve_crypto_exit_code_reports_failures(
        self, monkeypatch, tmp_path
    ):
        """The launcher exits 0 on a clean run and non-zero once any
        request failed, so a run that did not serve cannot pass."""
        from repro.launch import serve_crypto

        # a set variable makes compile_cache.enable() leave JAX's config
        # alone, so this test does not turn the cache on for the worker
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        argv = ["--presets", "16:2:30", "--requests", "4", "--slots", "2"]
        assert serve_crypto.main(argv) == 0

        def dead(self, pl, za, zb):
            raise RuntimeError("device fault")

        monkeypatch.setattr(PolymulEngine, "_execute_batch", dead)
        assert serve_crypto.main(argv) == 1

    def test_compile_cache_placed_from_outside(self, monkeypatch, tmp_path):
        """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without
        it the cache goes to one fixed directory in the checkout."""
        from repro import compile_cache

        was = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        try:
            got = compile_cache.enable()
            assert got == str(compile_cache.DEFAULT_DIR)
            assert compile_cache.DEFAULT_DIR.parent.joinpath("chip_smoke.py").exists()
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
