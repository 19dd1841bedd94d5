"""RNS/CRT pre- and post-processing (paper §IV-C/D/F, contribution 3).

Pre-processing (residual polynomial computation, Alg 1 / Alg 2):
  input coefficients arrive as base-B segments (B = 2^v, Alg 1 line 1):
      a_j = z_0 + z_1 B + ... + z_{t-1} B^{t-1}
  and each residue is  a_j mod q_i = sum_k z_k * (B^k mod q_i) mod q_i.
  Two datapaths are provided:
    * ``decompose``      — generic: precomputed (B^k mod q_i) constants and
      one multiply per segment (the Fig 11(a) baseline, minus its per-
      segment Barrett units).
    * ``decompose_sau``  — the paper's optimized path: multiplication by
      beta_i = B mod q_i done with Shift-Add Units (low-Hamming-weight
      special primes, Eq 5), factorized blocks of t' = 3 (Alg 2), one
      Barrett per block plus one generic v x v multiply for [beta^{t'rho}].
      int64 adaptation: SAU depth capped at 1 with a Barrett between SAU
      applications (the paper's own Approach-1 hybrid, Fig 14) because a
      depth-2 SAU word (v + 2(v1+1) bits) can exceed 63 bits.

Post-processing (inverse CRT, Eq 10 / HPS [33]):
      p = sum_i [p_i * q_i~]_{q_i} * q_i^  mod q
  with q_i^ = q / q_i held as base-2^w limbs; the final sum is < t*q and is
  reduced by at most (t-1) conditional subtractions — no Barrett over the
  full q is ever instantiated (the content of Fig 16(b)).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import bigint
from repro.core.modmath import barrett_constants, barrett_reduce  # noqa: F401
# ^ canonical implementations live in modmath (shared with the Pallas
#   kernels); re-exported here because the RNS datapaths and their tests
#   historically import them from this module.


# --------------------------------------------------------------------------
# Plan
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChannelDecompose:
    """Static pre-processing constants for ONE RNS channel (= one of the
    paper's specialized SAU circuits).

    Packaged on the plan so every in-kernel decompose stage — the
    standalone per-channel ``pallas_call`` in :mod:`repro.kernels.crt`
    and the fully fused e2e kernel in :mod:`repro.kernels.ntt` — bakes
    the same flat layout of python ints into its closure instead of
    re-deriving Barrett constants at every call site.
    """

    qi: int
    beta_terms: tuple[tuple[int, int], ...]  # signed-PoT terms of beta_i
    block_consts: tuple[int, ...]  # [beta_i^{t'*rho}]_{q_i} per Alg-2 block
    sau_barrett: tuple[int, int, int]  # (eps, s1, s2) for SAU/block words
    acc_barrett: tuple[int, int, int]  # (eps, s1, s2) for the accumulator


@dataclasses.dataclass(frozen=True, eq=False)  # identity hash: jit-static-safe
class RnsPlan:
    """All host-precomputed constants for one (n, v, t) RNS configuration."""

    n: int
    v: int
    t: int
    q: int  # composed modulus, prod(qs)
    qs: np.ndarray  # (t,) int64
    beta_terms: tuple[tuple[tuple[int, int], ...], ...]  # per prime
    # pre-processing
    seg_count: int  # number of base-2^v segments of an input coefficient
    beta_pows: np.ndarray  # (t, seg_count): B^k mod q_i
    t_prime: int  # Alg 2 block width (t')
    block_consts: np.ndarray  # (t, n_blocks): [beta_i^{t'*rho}]_{q_i}
    # post-processing
    w: int  # post-processing limb width
    L: int  # post-processing limb count
    qi_tilde: np.ndarray  # (t,): (q/q_i)^{-1} mod q_i
    qi_star_limbs: np.ndarray  # (t, L): q/q_i in base 2^w
    q_limbs: np.ndarray  # (L,)
    # per-channel in-kernel decompose constants; None when the int64
    # kernels cannot serve the config (v > 31, or a channel's SAU word
    # falls outside the 63-bit-safe Barrett window 2*(v1 + 4) <= 63) —
    # the jnp datapaths still work, the kernel entry points raise
    dec: tuple[ChannelDecompose, ...] | None = None

    @property
    def jnp_safe(self) -> bool:
        """int64 datapaths require q_i < 2^31; v=45 is served by the
        Python-bigint oracle in polymul.py."""
        return self.v <= 31

    # -- device-resident constants, uploaded once at construction time.
    # Eager on purpose: a lazy first touch could happen inside a jit
    # trace, where jnp.asarray yields a tracer that must not be cached.
    def __post_init__(self):
        object.__setattr__(self, "qs_d", jnp.asarray(self.qs))
        object.__setattr__(self, "beta_pows_d", jnp.asarray(self.beta_pows))
        object.__setattr__(self, "qi_tilde_d", jnp.asarray(self.qi_tilde))
        object.__setattr__(self, "qi_star_limbs_d", jnp.asarray(self.qi_star_limbs))
        object.__setattr__(self, "q_limbs_d", jnp.asarray(self.q_limbs))


def make_plan(qs: list[int], n: int, v: int, beta_terms, t_prime: int = 3) -> RnsPlan:
    t = len(qs)
    q = 1
    for qi in qs:
        q *= int(qi)
    seg_count = -(-q.bit_length() // v)
    beta_pows = np.array(
        [[pow(1 << v, k, int(qi)) for k in range(seg_count)] for qi in qs],
        dtype=np.int64,
    )
    n_blocks = -(-seg_count // t_prime)
    block_consts = np.array(
        [[pow(1 << v, t_prime * r, int(qi)) for r in range(n_blocks)] for qi in qs],
        dtype=np.int64,
    )
    w = 28
    # final accumulator < t * q: size limbs for that
    L = -(-(q.bit_length() + t.bit_length()) // w)
    qi_star = [q // int(qi) for qi in qs]
    qi_tilde = np.array(
        [pow(s % int(qi), int(qi) - 2, int(qi)) for s, qi in zip(qi_star, qs)],
        dtype=np.int64,
    )
    qi_star_limbs = bigint.ints_to_limbs(qi_star, w, L)
    q_limbs = bigint.int_to_limbs(q, w, L)
    dec = None
    # Same windows the constants below assert: SAU words need
    # 2*(v1 + 4) <= 63 per channel, accumulator words 2*4 <= 63.  Gating
    # here (instead of letting barrett_constants assert) keeps plan
    # construction working for every config the jnp datapaths serve —
    # only the in-kernel decompose circuits become unavailable.
    if v <= 31 and all(2 * (terms[0][0] + 4) <= 63 for terms in beta_terms):
        dec = tuple(
            ChannelDecompose(
                qi=int(qi),
                beta_terms=tuple(terms),
                block_consts=tuple(int(c) for c in block_consts[i]),
                # SAU output + block-sum headroom: c = v + v1 + 3 bits
                sau_barrett=barrett_constants(int(qi), v + terms[0][0] + 3, v),
                # accumulator of <= n_blocks reduced terms: < 2^{v+3}
                acc_barrett=barrett_constants(int(qi), v + 3, v),
            )
            for i, (qi, terms) in enumerate(zip(qs, beta_terms))
        )
    return RnsPlan(
        n=n,
        v=v,
        t=t,
        q=q,
        qs=np.array(qs, dtype=np.int64),
        beta_terms=tuple(beta_terms),
        seg_count=seg_count,
        beta_pows=beta_pows,
        t_prime=t_prime,
        block_consts=block_consts,
        w=w,
        L=L,
        qi_tilde=qi_tilde,
        qi_star_limbs=qi_star_limbs,
        q_limbs=q_limbs,
        dec=dec,
    )


# --------------------------------------------------------------------------
# Pre-processing
# --------------------------------------------------------------------------


def decompose(z: jnp.ndarray, plan: RnsPlan) -> jnp.ndarray:
    """Generic residue computation.  z: (..., S) base-2^v segments (each
    < 2^v) -> residues (t, ...)."""
    assert plan.jnp_safe
    qs = plan.qs_d  # (t,)
    bp = plan.beta_pows_d  # (t, S)
    terms = (z[..., None, :] * bp) % qs[:, None]  # (..., t, S)
    r = terms.sum(axis=-1) % qs  # (..., t)
    return jnp.moveaxis(r, -1, 0)


def _sau_mul_beta(z: jnp.ndarray, terms) -> jnp.ndarray:
    """z * beta via shifts/adds; beta = sum(sign * 2^e) - 1 (Eq 5, Fig 12).
    Input z < 2^v  ->  output < 2^{v + v1 + 1} (<= 52 bits for v<=30)."""
    acc = -z
    for e, s in terms:
        acc = acc + s * (z << e)
    return acc


def decompose_sau(z: jnp.ndarray, plan: RnsPlan) -> jnp.ndarray:
    """Paper-faithful optimized pre-processing (Alg 2 with SAUs).

    Per channel i, per block rho of t' segments:
        block = z_{rho t'} + SAU(z_{rho t' + 1}) + SAU(Barrett(SAU(z_{rho t'+2})))
        sum_rho = Barrett(block) * block_consts[i, rho]        (v x v multiply)
        a_i = Barrett(sum_rho accumulated)
    SAU depth capped at 1 (Approach-1 hybrid) for 63-bit safety.
    """
    S, tp = plan.seg_count, plan.t_prime
    n_blocks = -(-S // tp)
    pad = n_blocks * tp - S
    if pad:
        z = jnp.concatenate([z, jnp.zeros(z.shape[:-1] + (pad,), z.dtype)], axis=-1)
    outs = []
    for i in range(plan.t):
        qi = int(plan.qs[i])
        terms = plan.beta_terms[i]
        v1 = terms[0][0]
        c_sau = plan.v + v1 + 1 + 2  # SAU output + block-sum headroom
        eps, s1, s2 = barrett_constants(qi, c_sau, plan.v)
        # Accumulator of <= n_blocks already-reduced terms: < 2^{v+3}
        epsa, sa1, sa2 = barrett_constants(qi, plan.v + 3, plan.v)
        acc = jnp.zeros(z.shape[:-1], dtype=z.dtype)
        for rho in range(n_blocks):
            z0 = z[..., rho * tp + 0]
            blk = z0
            if tp > 1:
                blk = blk + _sau_mul_beta(z[..., rho * tp + 1], terms)
            for k in range(2, tp):
                # z * beta^k with Barrett between SAU applications (depth 1)
                x = _sau_mul_beta(z[..., rho * tp + k], terms)
                x = barrett_reduce(x, qi, eps, s1, s2)
                for _ in range(k - 1):
                    x = _sau_mul_beta(x, terms)
                    x = barrett_reduce(x, qi, eps, s1, s2)
                blk = blk + x
            blk = barrett_reduce(blk, qi, eps, s1, s2)
            if rho == 0:
                acc = acc + blk
            else:
                # The one generic v x v multiply per block (Eq 8).  The
                # paper reduces its 2v-bit product with the wide (mu-bit)
                # Barrett unit; a 63-bit-safe Barrett for c = 2v does not
                # exist for v = 30, so the int64 model uses rem here
                # (hardware cost accounting lives in benchmarks).
                prod = blk * int(plan.block_consts[i, rho])
                acc = acc + (prod % qi)
        acc = barrett_reduce(acc, qi, epsa, sa1, sa2)
        outs.append(acc)
    return jnp.stack(outs, axis=0)


# --------------------------------------------------------------------------
# Post-processing
# --------------------------------------------------------------------------


def compose(residues: jnp.ndarray, plan: RnsPlan) -> jnp.ndarray:
    """Inverse CRT per Eq 10: residues (t, ...) -> base-2^w limbs (..., L).

    No full-width Barrett over q: the t-term sum is < t*q and is finished
    with (t-1) conditional subtractions (Fig 16(b))."""
    qs = plan.qs_d.reshape((plan.t,) + (1,) * (residues.ndim - 1))
    y = (residues * plan.qi_tilde_d.reshape(qs.shape)) % qs  # (t, ...)
    star = plan.qi_star_limbs_d  # (t, L)
    star_b = star.reshape((plan.t,) + (1,) * (residues.ndim - 1) + (plan.L,))
    contrib = y[..., None] * star_b  # (t, ..., L), products < 2^58
    acc = contrib.sum(axis=0)  # (..., L), < t * 2^58
    acc = bigint.carry_normalize(acc, plan.w)
    q_limbs = plan.q_limbs_d
    q_b = q_limbs.reshape((1,) * (acc.ndim - 1) + (plan.L,))
    return bigint.mod_by_subtraction(acc, jnp.broadcast_to(q_b, acc.shape), plan.w, plan.t - 1)


def compose_conventional(residues: jnp.ndarray, plan: RnsPlan) -> jnp.ndarray:
    """Baseline Fig 16(a): multiply residues by the full-width constants
    e_i = q_i^ * q_i~ mod q and reduce the sum mod q by subtraction.  Kept
    as the Table V comparison target: the 'expensive' variant differs in
    *datapath cost*, not in this functional model, and the tests hold
    the two equal."""
    # e_i as limbs, wide enough for the un-reduced sum (< t * q * 2^v)
    Lw = max(plan.L, -(-(plan.q.bit_length() + plan.v + 8) // plan.w))
    e = [
        (int(plan.qi_tilde[i]) * (plan.q // int(plan.qs[i]))) % plan.q
        for i in range(plan.t)
    ]
    e_limbs = bigint.ints_to_limbs(e, plan.w, Lw)  # (t, Lw)
    # residue (31b) x limb (28b) products, accumulated
    e_b = jnp.asarray(e_limbs).reshape(
        (plan.t,) + (1,) * (residues.ndim - 1) + (Lw,)
    )
    contrib = residues[..., None] * e_b
    padded = bigint.carry_normalize(contrib.sum(axis=0), plan.w)
    # each term < q * 2^v; reduce with a subtraction ladder over shifted q
    # (host-precomputed powers-of-two multiples), modeling the wide
    # reduction over q that the paper's Fig 16(b) eliminates.
    q_mults = [plan.q << s for s in range(plan.v + plan.t.bit_length(), -1, -1)]
    for qm in q_mults:
        if qm.bit_length() > Lw * plan.w:
            continue
        qm_limbs = jnp.asarray(bigint.int_to_limbs(qm, plan.w, Lw))
        qm_b = jnp.broadcast_to(
            qm_limbs.reshape((1,) * (padded.ndim - 1) + (Lw,)), padded.shape
        )
        padded = bigint.cond_sub(padded, qm_b, plan.w)
    return padded[..., : plan.L]
