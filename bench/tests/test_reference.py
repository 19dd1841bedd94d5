import random

import numpy as np
import pytest

from bench import reference


def _primes_and_q(n, t, v):
    from repro import api

    pl = api.plan(n=n, t=t, v=v, backend="jnp")
    return [p.q for p in pl.params.primes], pl.q, pl.params


@pytest.mark.parametrize("n,t", [(64, 6), (64, 2), (128, 3)])
def test_reference_agrees_with_the_program_oracle(n, t):
    from repro.core import polymul

    _, q, params = _primes_and_q(n, t, 30)
    rng = random.Random(n * t)
    for _ in range(3):
        a = [rng.randrange(q) for _ in range(n)]
        b = [rng.randrange(q) for _ in range(n)]
        assert reference.negacyclic_mod(a, b, q) == polymul.oracle_multiply(a, b, params)


def test_reference_folds_x_to_the_n_as_minus_one():
    q, n = 97, 4
    x3 = [0, 0, 0, 1]
    x2 = [0, 0, 1, 0]
    # x^3 * x^2 = x^5 = -x mod x^4 + 1
    assert reference.negacyclic_mod(x3, x2, q) == [0, q - 1, 0, 0]


def test_digits_round_trip():
    rng = random.Random(1)
    xs = [rng.randrange(1 << 180) for _ in range(50)]
    z = reference.ints_to_digits(xs, 30, 6)
    assert z.shape == (50, 6) and z.dtype == np.int64 and int(z.max()) < (1 << 30)
    assert reference.digits_to_ints(z, 30) == xs
    with pytest.raises(ValueError):
        reference.ints_to_digits([1 << 180], 30, 6)


def test_control_is_wrong_at_full_width():
    primes, q, _ = _primes_and_q(64, 6, 30)
    rng = random.Random(2)
    a = [rng.randrange(q) for _ in range(64)]
    b = [rng.randrange(q) for _ in range(64)]
    want = reference.negacyclic_mod(a, b, q)
    got = reference.control_multiply(a, b, primes)
    assert sum(x != y for x, y in zip(got, want)) > 32


def test_control_is_exact_where_float64_suffices():
    # residues below 2^10 at n=8: convolution sums fit 53 bits
    primes = [1009, 1013]
    q = 1009 * 1013
    rng = random.Random(3)
    a = [rng.randrange(q) for _ in range(8)]
    b = [rng.randrange(q) for _ in range(8)]
    assert reference.control_multiply(a, b, primes) == reference.negacyclic_mod(a, b, q)
