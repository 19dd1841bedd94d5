"""Core PaReNTT correctness: primes, NTT, RNS, polymul, schedule, Barrett."""
import functools
import random

import numpy as np
import pytest

import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # degrade property tests to per-test skips, not errors
    from _hypothesis_fallback import given, settings, st

import repro
from repro.core import bigint, ntt as ntt_mod, params as params_mod
from repro.core import polymul as pm, primes as primes_mod, rns as rns_mod
from repro.core import schedule as sched


# --------------------------------------------------------------------------
# primes
# --------------------------------------------------------------------------


class TestPrimes:
    def test_miller_rabin(self):
        assert primes_mod.is_prime(2**31 - 1)
        assert not primes_mod.is_prime(2**32 - 1)
        assert primes_mod.is_prime(0x3FDE0001)

    def test_factorize_roundtrip(self):
        for x in [2**30 - 1, 7 * 11 * 13 * 17, 2**45 - 2**29 + 2**13 + 1]:
            fac = primes_mod.factorize(x)
            y = 1
            for p, e in fac.items():
                assert primes_mod.is_prime(p)
                y *= p**e
            assert y == x

    @pytest.mark.parametrize(
        "t,v,mu,pot,expected",
        [
            (4, 45, 105, 4, 12),
            (4, 45, 120, 4, 33),
            (4, 45, 105, 5, 126),
            (4, 45, 120, 5, 480),
            (6, 30, 75, 4, 8),
            (6, 30, 90, 4, 26),
            (6, 30, 75, 5, 23),
            (6, 30, 90, 5, 169),
        ],
    )
    def test_table_iii_counts(self, t, v, mu, pot, expected):
        """Exact reproduction of every row of paper Table III."""
        found = primes_mod.find_special_primes(v=v, n=4096, mu=mu, pot=pot, n_beta=2)
        assert len(found) == expected

    def test_prime_properties(self):
        for s in primes_mod.default_prime_set(4096, 6, 30):
            assert primes_mod.is_prime(s.q)
            assert (s.q - 1) % (2 * 4096) == 0
            assert s.q == (1 << s.v) - s.beta
            assert s.pot_terms == 4

    def test_root_of_unity(self):
        q = 0x3FDE0001
        psi = primes_mod.root_of_unity(q, 2 * 4096)
        assert pow(psi, 4096, q) == q - 1  # psi^n = -1 (negacyclic)
        assert pow(psi, 8192, q) == 1


# --------------------------------------------------------------------------
# NTT
# --------------------------------------------------------------------------

SMALL_Q = 0x3FDE0001  # 30-bit special prime, 2*4096 | q-1 (so all n <= 4096 ok)


def _tables(n, q=SMALL_Q):
    return ntt_mod.make_tables(q, n)


class TestNtt:
    @pytest.mark.parametrize(
        "n",
        [8, 16, 64, 256, pytest.param(1024, marks=pytest.mark.slow)],
    )
    def test_roundtrip(self, n):
        tb = _tables(n)
        rng = np.random.default_rng(n)
        a = jnp.asarray(rng.integers(0, tb.q, size=(3, n)))
        out = ntt_mod.intt(ntt_mod.ntt(a, tb), tb)
        assert np.array_equal(np.asarray(out), np.asarray(a))

    @pytest.mark.parametrize("n", [8, 64, 512])
    def test_convolution_theorem(self, n):
        tb = _tables(n)
        rng = np.random.default_rng(n + 1)
        a = rng.integers(0, tb.q, size=n)
        b = rng.integers(0, tb.q, size=n)
        got = ntt_mod.negacyclic_mul(jnp.asarray(a), jnp.asarray(b), tb)
        want = pm.schoolbook_negacyclic(a.tolist(), b.tolist(), tb.q)
        assert np.asarray(got).tolist() == want

    @pytest.mark.parametrize("n", [16, 64, 4096])
    def test_output_is_bit_reversed_and_shuffle_is_redundant(self, n):
        """The forward NTT's output is the natural-order evaluation in
        bit-reversed order, so the explicitly shuffled cascade of Fig 17
        (reorder to natural order, multiply, reorder back) equals the
        no-permute cascade."""
        import jax

        tb = _tables(n)
        brv = ntt_mod.bit_reverse_indices(n)
        rng = np.random.default_rng(n + 2)
        a = rng.integers(0, tb.q, size=(2, n))
        b = rng.integers(0, tb.q, size=(2, n))

        @jax.jit
        def shuffled(a, b):
            fa = ntt_mod.ntt(a, tb)[:, brv]
            fb = ntt_mod.ntt(b, tb)[:, brv]
            prod = ntt_mod.mul_mod(fa, fb, tb.q)
            return fa, ntt_mod.intt(prod[:, np.argsort(brv)], tb)

        fa, got = shuffled(jnp.asarray(a), jnp.asarray(b))
        if n <= 64:
            want = [
                sum(int(x) * pow(tb.psi, (2 * k + 1) * j, tb.q)
                    for j, x in enumerate(a[0])) % tb.q
                for k in range(n)
            ]
            assert np.asarray(fa[0]).tolist() == want
        direct = jax.jit(lambda a, b: ntt_mod.negacyclic_mul(a, b, tb))
        want = direct(jnp.asarray(a), jnp.asarray(b))
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_no_permutation_between_stages(self):
        """The cascade lowers with zero gather/scatter/permute ops — the
        JAX-level expression of the no-shuffle contribution."""
        import jax

        tb = _tables(256)
        fn = jax.jit(lambda a, b: ntt_mod.negacyclic_mul(a, b, tb))
        a = jnp.zeros((256,), jnp.int64)
        txt = fn.lower(a, a).as_text()
        for op in ("gather", "scatter", "sort"):
            assert op not in txt, f"unexpected {op} in cascade HLO"

    @given(
        st.integers(0, SMALL_Q - 1),
        st.integers(0, SMALL_Q - 1),
        st.integers(0, SMALL_Q - 1),
    )
    @settings(max_examples=25, deadline=None)
    def test_linearity_property(self, c1, c2, seed):
        n = 32
        tb = _tables(n)
        rng = np.random.default_rng(seed)
        a = jnp.asarray(rng.integers(0, tb.q, size=n))
        b = jnp.asarray(rng.integers(0, tb.q, size=n))
        lhs = ntt_mod.ntt((c1 * a + c2 * b) % tb.q, tb)
        rhs = (c1 * ntt_mod.ntt(a, tb) + c2 * ntt_mod.ntt(b, tb)) % tb.q
        assert np.array_equal(np.asarray(lhs), np.asarray(rhs))

    def test_negacyclic_wraparound_sign(self):
        # x^(n-1) * x = x^n = -1 mod (x^n + 1)
        n = 16
        tb = _tables(n)
        a = np.zeros(n, dtype=np.int64)
        b = np.zeros(n, dtype=np.int64)
        a[n - 1] = 1
        b[1] = 1
        got = np.asarray(ntt_mod.negacyclic_mul(jnp.asarray(a), jnp.asarray(b), tb))
        want = np.zeros(n, dtype=np.int64)
        want[0] = tb.q - 1
        assert np.array_equal(got, want)

    def test_channels(self):
        p = params_mod.make_params(n=64, t=3, v=30)
        ct = p.tables
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.integers(0, 2**29, size=(3, 2, 64)))
        b = jnp.asarray(rng.integers(0, 2**29, size=(3, 2, 64)))
        got = np.asarray(ntt_mod.negacyclic_mul_channels(a, b, ct))
        for c in range(3):
            for r in range(2):
                want = pm.schoolbook_negacyclic(
                    np.asarray(a)[c, r].tolist(),
                    np.asarray(b)[c, r].tolist(),
                    int(ct.qs[c]),
                )
                assert got[c, r].tolist() == want


# --------------------------------------------------------------------------
# Barrett
# --------------------------------------------------------------------------


class TestBarrett:
    @pytest.mark.parametrize("c", [35, 45, 54])
    def test_barrett_reduce(self, c):
        q = SMALL_Q
        eps, s1, s2 = rns_mod.barrett_constants(q, c, 30)
        rng = np.random.default_rng(c)
        xs = np.concatenate(
            [
                rng.integers(0, 1 << c, size=4096),
                np.array([0, 1, q - 1, q, q + 1, (1 << c) - 1, (1 << c) - q]),
            ]
        )
        got = np.asarray(rns_mod.barrett_reduce(jnp.asarray(xs), q, eps, s1, s2))
        assert np.array_equal(got, xs % q)


# --------------------------------------------------------------------------
# bigint
# --------------------------------------------------------------------------


class TestBigint:
    @given(st.integers(0, 2**180 - 1))
    @settings(max_examples=50, deadline=None)
    def test_limb_roundtrip(self, x):
        limbs = bigint.int_to_limbs(x, 28, 7)
        assert bigint.limbs_to_int(limbs, 28) == x

    @given(st.integers(0, 2**170), st.integers(0, 2**170))
    @settings(max_examples=50, deadline=None)
    def test_compare_sub(self, a, b):
        a, b = max(a, b), min(a, b)
        la = jnp.asarray(bigint.int_to_limbs(a, 28, 7))
        lb = jnp.asarray(bigint.int_to_limbs(b, 28, 7))
        assert bool(bigint.compare_ge(la, lb))
        diff = bigint.sub_limbs(la, lb, 28)
        assert bigint.limbs_to_int(np.asarray(diff), 28) == a - b

    def test_carry_normalize(self):
        x = jnp.asarray(np.array([[2**60, 2**55, 3, 0, 0, 0, 0]], dtype=np.int64))
        out = bigint.carry_normalize(x, 28)
        assert bigint.limbs_to_int(np.asarray(out)[0], 28) == 2**60 + (2**55 << 28) + (3 << 56)


# --------------------------------------------------------------------------
# RNS
# --------------------------------------------------------------------------


class TestRns:
    @pytest.fixture(scope="class")
    def p(self):
        return params_mod.make_params(n=64, t=3, v=30)

    def test_crt_roundtrip(self, p):
        rng = random.Random(0)
        xs = [rng.randrange(p.q) for _ in range(64)]
        z = jnp.asarray(pm.ints_to_segments(xs, p.plan))
        res = rns_mod.decompose(z, p.plan)
        out = rns_mod.compose(res, p.plan)
        assert pm.limbs_out_to_ints(np.asarray(out), p.plan) == xs

    def test_sau_equals_generic(self, p):
        rng = np.random.default_rng(2)
        z = jnp.asarray(rng.integers(0, 1 << 30, size=(5, p.plan.seg_count)))
        a = np.asarray(rns_mod.decompose(z, p.plan))
        b = np.asarray(rns_mod.decompose_sau(z, p.plan))
        assert np.array_equal(a, b)

    def test_conventional_equals_optimized(self, p):
        rng = random.Random(3)
        xs = [rng.randrange(p.q) for _ in range(32)]
        res = jnp.asarray(
            np.array([[x % int(q) for x in xs] for q in p.plan.qs])
        )
        a = rns_mod.compose(res, p.plan)
        b = rns_mod.compose_conventional(res, p.plan)
        ia = pm.limbs_out_to_ints(np.asarray(a), p.plan)
        ib = pm.limbs_out_to_ints(np.asarray(b), p.plan)
        assert ia == ib == xs

    def test_sau_equals_generic_paper_preset(self):
        """Table IV at the paper's preset (n=4096, t=6, v=30)."""
        p = params_mod.make_params(n=4096, t=6, v=30)
        rng = np.random.default_rng(0)
        z = jnp.asarray(rng.integers(0, 1 << 30, size=(4096, p.plan.seg_count)))
        a = np.asarray(rns_mod.decompose(z, p.plan))
        b = np.asarray(rns_mod.decompose_sau(z, p.plan))
        assert np.array_equal(a, b)

    def test_conventional_equals_optimized_paper_preset(self):
        """Table V at the paper's preset (n=4096, t=6, v=30)."""
        p = params_mod.make_params(n=4096, t=6, v=30)
        rng = random.Random(1)
        xs = [rng.randrange(p.q) for _ in range(4096)]
        res = jnp.asarray(
            np.array([[x % int(q) for x in xs] for q in p.plan.qs])
        )
        a = rns_mod.compose(res, p.plan)
        b = rns_mod.compose_conventional(res, p.plan)
        assert pm.limbs_out_to_ints(np.asarray(a), p.plan) == xs
        assert pm.limbs_out_to_ints(np.asarray(b), p.plan) == xs

    @given(st.integers(0, 2**89))
    @settings(max_examples=40, deadline=None)
    def test_decompose_property(self, x):
        p = params_mod.make_params(n=64, t=3, v=30)
        x %= p.q
        z = jnp.asarray(bigint.int_to_limbs(x, p.plan.v, p.plan.seg_count))
        res = np.asarray(rns_mod.decompose(z, p.plan))
        for i, qi in enumerate(p.plan.qs):
            assert int(res[i]) == x % int(qi)


# --------------------------------------------------------------------------
# End-to-end multiplier
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _cached_plan():
    return repro.plan(n=64, t=3, v=30)


class TestPolymul:
    @pytest.mark.parametrize("t,v,n", [(3, 30, 64), (6, 30, 128)])
    def test_jit_pipeline_matches_schoolbook(self, t, v, n):
        p = params_mod.make_params(n=n, t=t, v=v)
        rng = random.Random(42)
        a = [rng.randrange(p.q) for _ in range(n)]
        b = [rng.randrange(p.q) for _ in range(n)]
        pl = repro.plan(n=n, t=t, v=v)
        assert repro.polymul_ints(pl, a, b) == pm.schoolbook_negacyclic(a, b, p.q)

    def test_sau_and_generic_paths_agree(self):
        p = params_mod.make_params(n=64, t=3, v=30)
        rng = random.Random(7)
        a = [rng.randrange(p.q) for _ in range(64)]
        b = [rng.randrange(p.q) for _ in range(64)]
        pl1 = repro.plan(n=64, t=3, v=30, use_sau=True)
        pl2 = repro.plan(n=64, t=3, v=30, use_sau=False)
        assert repro.polymul_ints(pl1, a, b) == repro.polymul_ints(pl2, a, b)

    def test_oracle_v45(self):
        """The paper's t=4, v=45, 180-bit configuration (oracle path)."""
        p = params_mod.make_params(n=64, t=4, v=45)
        assert p.q.bit_length() == 180
        rng = random.Random(8)
        a = [rng.randrange(p.q) for _ in range(64)]
        b = [rng.randrange(p.q) for _ in range(64)]
        assert pm.oracle_multiply(a, b, p) == pm.schoolbook_negacyclic(a, b, p.q)

    def test_batched(self):
        p = params_mod.make_params(n=64, t=3, v=30)
        pl = repro.plan(n=64, t=3, v=30)
        rng = np.random.default_rng(11)
        ints = lambda: [
            [int(x) for x in rng.integers(0, 2**60, size=64)] for _ in range(2)
        ]
        A, B = ints(), ints()
        za = jnp.asarray(np.stack([pm.ints_to_segments(r, p.plan) for r in A]))
        zb = jnp.asarray(np.stack([pm.ints_to_segments(r, p.plan) for r in B]))
        out = np.asarray(repro.execute(pl, za, zb))
        for r in range(2):
            got = pm.limbs_out_to_ints(out[r], p.plan)
            assert got == pm.schoolbook_negacyclic(A[r], B[r], p.q)

    @given(st.integers(0, 2**64), st.integers(2, 2**20))
    @settings(max_examples=10, deadline=None)
    def test_ring_homomorphism_property(self, seed, scale):
        """(c*a) * b == c * (a*b) in R_q — multiplier respects module structure."""
        p = params_mod.make_params(n=64, t=3, v=30)
        rng = random.Random(seed)
        a = [rng.randrange(p.q) for _ in range(64)]
        b = [rng.randrange(p.q) for _ in range(64)]
        pl = _cached_plan()
        ca = [(scale * x) % p.q for x in a]
        lhs = repro.polymul_ints(pl, ca, b)
        ab = repro.polymul_ints(pl, a, b)
        rhs = [(scale * x) % p.q for x in ab]
        assert lhs == rhs


# --------------------------------------------------------------------------
# Schedule (contribution 1 at the clock level)
# --------------------------------------------------------------------------


class TestSchedule:
    @pytest.mark.parametrize("n", [16, 64, 256, 1024, 4096])
    def test_bit_reversed_folding_needs_zero_buffer(self, n):
        sim = sched.simulate_cascade(n, bit_reversed_intt=True)
        assert sim.max_buffer_pairs == 0
        assert sim.added_latency == 0

    @pytest.mark.parametrize("n", [16, 64, 256, 1024, 4096])
    def test_same_folding_needs_buffer(self, n):
        sim = sched.simulate_cascade(n, bit_reversed_intt=False)
        assert sim.max_buffer_pairs >= n // 8
        assert sim.added_latency > 0

    def test_timing_formulas(self):
        # Fig 17 / §V-B numbers for n = 4096
        assert sched.bpp_cycles(4096) == 2048
        assert sched.latency_cycles(4096) == 4094
        assert sched.latency_cycles(4096, with_shuffle=True) == 4094 + 1024
        # paper: shuffling increases latency by ~20.0%
        inc = sched.latency_cycles(4096, with_shuffle=True) / sched.latency_cycles(4096)
        assert abs(inc - 1.25 * 0.8 - 0.2) < 0.06 or abs(inc - 1.2) < 0.06

    def test_table_vii_latency_and_roy_ratio(self):
        # §V-D: n=4096 with the 152-cycle pipeline at 240 MHz is the
        # paper's 4246-4254 cycles (17.4-17.7 us), 49.2x below Roy [7]'s
        # normalized 196,003 cycles at 225 MHz
        lat = sched.latency_cycles(4096, t_pipe=152)
        assert 4246 <= lat <= 4254
        assert 17.4 <= lat / 240 <= 17.7
        assert round((196_003 / 225) / (lat / 240), 1) == 49.2

    def test_folding_tables_match_paper_16pt(self):
        # Eq (1): NTT folding sets for n=16
        assert sched.ntt_folding_order(16, 0).tolist() == [0, 1, 2, 3, 4, 5, 6, 7]
        assert sched.ntt_folding_order(16, 1).tolist() == [4, 5, 6, 7, 0, 1, 2, 3]
        assert sched.ntt_folding_order(16, 2).tolist() == [2, 3, 4, 5, 6, 7, 0, 1]
        assert sched.ntt_folding_order(16, 3).tolist() == [1, 2, 3, 4, 5, 6, 7, 0]
        # Eq (2): iNTT folding sets
        assert sched.intt_folding_order(16, 0).tolist() == [4, 2, 6, 1, 5, 3, 7, 0]
        assert sched.intt_folding_order(16, 1).tolist() == [0, 4, 2, 6, 1, 5, 3, 7]
