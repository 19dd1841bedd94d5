"""The plain reference: negacyclic products mod (x^n + 1, q) by
Kronecker substitution, with nothing taken from the program under test.

Each coefficient is written into a fixed-width decimal slot, the two
packed numbers are multiplied exactly by Python's ``decimal`` module
(whose big multiplication is fast), and the slots of the product are
the coefficients of the plain product a * b.  Folding x^n = -1 and
reducing mod q gives the answer.  q comes from the configuration file.

``control_multiply`` is the control: the same product computed per RNS
prime with float64 FFTs (53-bit mantissas where exact sums need about
72 bits) and joined by CRT.  It breaks the configuration's guarantee,
an exact result, and the comparison has to call it wrong.
"""
from __future__ import annotations

import decimal
import math

import numpy as np

_CTX = decimal.Context(prec=decimal.MAX_PREC, Emax=decimal.MAX_EMAX,
                       Emin=decimal.MIN_EMIN)


def digits_to_ints(z: np.ndarray, bits: int) -> list[int]:
    """``(n, k)`` base-2^bits digits, least significant first -> ints."""
    out = [0] * z.shape[0]
    for j in range(z.shape[1] - 1, -1, -1):
        col = z[:, j].tolist()
        out = [(x << bits) | int(c) for x, c in zip(out, col)]
    return out


def ints_to_digits(xs: list[int], bits: int, count: int) -> np.ndarray:
    """Inverse of :func:`digits_to_ints`: ``(n, count)`` int64 digits."""
    mask = (1 << bits) - 1
    out = np.empty((len(xs), count), np.int64)
    cur = list(xs)
    for j in range(count):
        out[:, j] = [x & mask for x in cur]
        cur = [x >> bits for x in cur]
    if any(cur):
        raise ValueError(f"a value needs more than {count} digits of {bits} bits")
    return out


def negacyclic_mod(a: list[int], b: list[int], q: int) -> list[int]:
    """(a * b mod x^n + 1) mod q, exactly; a and b hold n ints in [0, q)."""
    n = len(a)
    width = len(str(n * (q - 1) ** 2)) + 1  # one plain coefficient per slot
    fmt = f"%0{width}d"
    A = decimal.Decimal("".join(fmt % x for x in reversed(a)))
    B = decimal.Decimal("".join(fmt % x for x in reversed(b)))
    s = format(_CTX.multiply(A, B), "f").rjust(2 * n * width, "0")
    end = len(s)
    plain = [int(s[end - (i + 1) * width:end - i * width]) for i in range(2 * n)]
    return [(plain[i] - plain[i + n]) % q for i in range(n)]


def control_multiply(a: list[int], b: list[int], primes: list[int]) -> list[int]:
    """The control: per-prime negacyclic products in float64, joined by
    CRT.  Exact only where every convolution sum fits 53 bits."""
    n = len(a)
    q = math.prod(primes)
    acc = [0] * n
    for p in primes:
        ra = np.array([x % p for x in a], np.float64)
        rb = np.array([x % p for x in b], np.float64)
        full = np.fft.irfft(np.fft.rfft(ra, 2 * n) * np.fft.rfft(rb, 2 * n), 2 * n)
        r = np.mod(np.rint(full[:n] - full[n:]), p).astype(np.int64).tolist()
        m = q // p
        k = m * pow(m, -1, p)
        acc = [x + int(c) * k for x, c in zip(acc, r)]
    return [x % q for x in acc]
