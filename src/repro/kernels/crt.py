"""Pallas kernels for RNS pre-processing (residual computation, Alg 1/2
with SAU strength reduction) and post-processing (inverse CRT, Eq 10).

Hardware mapping notes
----------------------
* Pre-processing: each RNS channel is its *own specialized circuit* in the
  paper (the SAU wiring is fixed by beta_i's signed-PoT terms).  We mirror
  that exactly: one pallas_call per channel with the shift/add network
  baked in statically — shifts and adds only, no integer multiplier, on
  the VPU int lanes.
* Post-processing: the (t -> limbs) recombination is a static einsum-like
  network: v-bit x w-bit limb products, a carry ripple (static L-step
  loop), and (t-1) conditional big-int subtractions.  No reduction over
  the wide modulus q ever materializes (Fig 16(b)).
* Both halves are factored as reusable *in-kernel stages*
  (:func:`decompose_stage`, :func:`compose_finalize`) so the fused
  end-to-end kernel in :mod:`repro.kernels.ntt` runs the identical
  circuits with the residues held in VMEM instead of round-tripping HBM
  between three pallas_calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import modmath
from repro.core import rns as rns_mod

BLK = 256  # coefficients per grid step


# --------------------------------------------------------------------------
# pre-processing (one specialized circuit per channel, SAU network static)
# --------------------------------------------------------------------------


def decompose_stage(z, ch: rns_mod.ChannelDecompose, *, seg_count: int,
                    t_prime: int):
    """In-kernel pre-processing stage for one RNS channel.

    z: (..., S) base-2^v segments -> residues (...) mod ``ch.qi``.  The
    SAU shift/add network and both Barrett constant sets arrive baked in
    ``ch`` (a :class:`repro.core.rns.ChannelDecompose` off the plan), so
    this traces to shifts, adds and the per-block v x v multiply only —
    usable both from the standalone per-channel ``pallas_call`` below
    and inside the fused e2e kernel, where the residues it produces stay
    VMEM-resident.
    """
    qi = ch.qi
    eps, s1, s2 = ch.sau_barrett
    epsa, sa1, sa2 = ch.acc_barrett
    n_blocks = -(-seg_count // t_prime)

    def sau(x):
        acc = -x
        for e, s in ch.beta_terms:
            acc = acc + s * (x << e)
        return acc

    def red(x):
        return modmath.barrett_reduce(x, qi, eps, s1, s2)

    acc = jnp.zeros(z.shape[:-1], dtype=z.dtype)
    for rho in range(n_blocks):
        blk = z[..., rho * t_prime]
        if t_prime > 1 and rho * t_prime + 1 < seg_count:
            blk = blk + sau(z[..., rho * t_prime + 1])
        for k in range(2, t_prime):
            if rho * t_prime + k >= seg_count:
                break
            x = red(sau(z[..., rho * t_prime + k]))
            for _ in range(k - 1):
                x = red(sau(x))
            blk = blk + x
        blk = red(blk)
        if rho == 0:
            acc = acc + blk
        else:
            acc = acc + (blk * ch.block_consts[rho]) % qi
    return modmath.barrett_reduce(acc, qi, epsa, sa1, sa2)


def decompose_stage_dyn(z, *, qi, sau_eps, sau_s2, acc_eps, beta_e, beta_s,
                        block_consts, v: int, seg_count: int, t_prime: int):
    """Data-driven twin of :func:`decompose_stage` for the channel-tiled
    e2e grid: per-channel constants arrive as traced scalars/vectors
    (read from channel-indexed blocks) instead of python ints baked into
    the closure, so ONE kernel body serves every RNS channel.

    The SAU network becomes ``sum_k beta_s[k] * (x << beta_e[k]) - x``
    with zero-signed padding entries contributing nothing; the only
    per-channel Barrett shift that varies (s2 of the SAU window, v1 + 4)
    is applied as a traced scalar shift.  Bit-identical to the
    specialized circuits — asserted by the backend tests."""
    s1 = v - 1

    def sau(x):
        return (beta_s * (x[..., None] << beta_e)).sum(axis=-1) - x

    def red(x):
        return modmath.barrett_reduce(x, qi, sau_eps, s1, sau_s2)

    n_blocks = -(-seg_count // t_prime)
    acc = jnp.zeros(z.shape[:-1], dtype=z.dtype)
    for rho in range(n_blocks):
        blk = z[..., rho * t_prime]
        if t_prime > 1 and rho * t_prime + 1 < seg_count:
            blk = blk + sau(z[..., rho * t_prime + 1])
        for k in range(2, t_prime):
            if rho * t_prime + k >= seg_count:
                break
            x = red(sau(z[..., rho * t_prime + k]))
            for _ in range(k - 1):
                x = red(sau(x))
            blk = blk + x
        blk = red(blk)
        if rho == 0:
            acc = acc + blk
        else:
            acc = acc + (blk * block_consts[rho]) % qi
    # accumulator window is c = v + 3 for every channel => s2 = 4 static
    return modmath.barrett_reduce(acc, qi, acc_eps, s1, 4)


@functools.lru_cache(maxsize=None)
def plan_dec_arrays(plan: rns_mod.RnsPlan) -> dict:
    """Stacked (t, ...) numpy views of ``plan.dec`` for the channel-tiled
    e2e grid (one row per channel, SAU terms zero-padded to the widest
    channel).  Cached per plan object (plans hash by identity)."""
    dec = require_dec(plan)
    t = plan.t
    t_max = max(len(c.beta_terms) for c in dec)
    beta_e = np.zeros((t, t_max), dtype=np.int64)
    beta_s = np.zeros((t, t_max), dtype=np.int64)
    for i, c in enumerate(dec):
        for j, (e, s) in enumerate(c.beta_terms):
            beta_e[i, j] = e
            beta_s[i, j] = s
    return {
        "sau_eps": np.array([c.sau_barrett[0] for c in dec], dtype=np.int64),
        "sau_s2": np.array([c.sau_barrett[2] for c in dec], dtype=np.int64),
        "acc_eps": np.array([c.acc_barrett[0] for c in dec], dtype=np.int64),
        "beta_e": beta_e,
        "beta_s": beta_s,
        "block_consts": np.array(
            [c.block_consts for c in dec], dtype=np.int64
        ),
    }


def require_dec(plan: rns_mod.RnsPlan):
    """The shared guard for every kernel needing in-kernel decompose
    constants (standalone decompose and the fused e2e kernel)."""
    if plan.dec is None:
        raise ValueError(
            f"plan (v={plan.v}) has no in-kernel decompose constants: the "
            "int64 Pallas datapaths require v <= 31 and SAU words inside "
            "the 63-bit-safe Barrett window (2*(v1 + 4) <= 63)"
        )
    return plan.dec


def _make_decompose_kernel(ch: rns_mod.ChannelDecompose, seg_count: int,
                           t_prime: int):
    """Kernel closure with the channel's SAU circuit baked in."""

    def kernel(z_ref, o_ref):
        o_ref[...] = decompose_stage(
            z_ref[...], ch, seg_count=seg_count, t_prime=t_prime
        )

    return kernel


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def decompose_pallas(z, *, plan: rns_mod.RnsPlan, interpret: bool):
    """z: (rows, S) segments -> residues (t, rows).  One specialized
    pallas_call per RNS channel (= per hardware circuit)."""
    rows, S = z.shape
    dec = require_dec(plan)
    pad = (-rows) % BLK
    zp = jnp.pad(z, ((0, pad), (0, 0))) if pad else z
    outs = []
    for i in range(plan.t):
        kern = _make_decompose_kernel(dec[i], plan.seg_count, plan.t_prime)
        out = pl.pallas_call(
            kern,
            grid=(zp.shape[0] // BLK,),
            in_specs=[pl.BlockSpec((BLK, S), lambda r: (r, 0))],
            out_specs=pl.BlockSpec((BLK,), lambda r: (r,)),
            out_shape=jax.ShapeDtypeStruct((zp.shape[0],), z.dtype),
            interpret=interpret,
        )(zp)
        outs.append(out[:rows])
    return jnp.stack(outs)


# --------------------------------------------------------------------------
# post-processing (Eq 10)
# --------------------------------------------------------------------------


def compose_finalize(acc, q_limbs, *, w: int, t: int):
    """In-kernel post-processing tail: raw limb-product sums -> canonical
    base-2^w limbs of the composed value mod q.

    acc: (..., L) per-limb accumulations of ``y_i * q_i^`` products (each
    < t * 2^{v+w}); q_limbs: (L,).  Static carry ripple followed by the
    (t-1) conditional big-int subtractions of Fig 16(b) — no reduction
    over the wide q ever materializes.  Shared by the standalone compose
    ``pallas_call`` and the fused e2e kernel.
    """
    L = acc.shape[-1]
    mask = (1 << w) - 1
    # carry ripple (static)
    outs = []
    carry = jnp.zeros_like(acc[..., 0])
    for i in range(L):
        s = acc[..., i] + carry
        outs.append(s & mask)
        carry = s >> w
    acc = jnp.stack(outs, axis=-1)
    # (t-1) conditional big-int subtractions of q
    for _ in range(t - 1):
        ge = jnp.ones(acc.shape[:-1], dtype=bool)
        decided = jnp.zeros(acc.shape[:-1], dtype=bool)
        for i in range(L - 1, -1, -1):
            gt = acc[..., i] > q_limbs[i]
            lt = acc[..., i] < q_limbs[i]
            ge = jnp.where(~decided & gt, True, ge)
            ge = jnp.where(~decided & lt, False, ge)
            decided = decided | gt | lt
        borrow = jnp.zeros_like(acc[..., 0])
        subbed = []
        for i in range(L):
            d = acc[..., i] - q_limbs[i] - borrow
            neg = d < 0
            subbed.append(jnp.where(neg, d + (1 << w), d))
            borrow = neg.astype(acc.dtype)
        sub = jnp.stack(subbed, axis=-1)
        acc = jnp.where(ge[..., None], sub, acc)
    return acc


def _make_compose_kernel(plan: rns_mod.RnsPlan):
    t, w = plan.t, plan.w

    def kernel(res_ref, qs_ref, tilde_ref, star_ref, qlimb_ref, o_ref):
        res = res_ref[...]  # (t, blk)
        tilde = tilde_ref[...]  # (t, 1)
        star = star_ref[...]  # (t, L)
        qs = qs_ref[...]  # (t, 1)
        y = (res * tilde) % qs  # (t, blk)
        contrib = y[:, :, None] * star[:, None, :]  # (t, blk, L)
        acc = contrib.sum(axis=0)  # (blk, L)
        o_ref[...] = compose_finalize(acc, qlimb_ref[0], w=w, t=t)

    return kernel


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def compose_pallas(residues, *, plan: rns_mod.RnsPlan, qs=None, qi_tilde=None,
                   star=None, q_limbs=None, interpret: bool):
    """residues: (t, rows) -> limbs (rows, L) of the composed value mod q.

    The CRT table operands default to the plan's own device uploads;
    the ops layer overrides them with a Plan's pytree leaves so that
    ``device_put``/sharding of the leaves redirects this kernel too
    (``plan`` itself stays jit-static — circuit structure only)."""
    t, rows = residues.shape
    L = plan.L
    qs = plan.qs_d if qs is None else qs
    qi_tilde = plan.qi_tilde_d if qi_tilde is None else qi_tilde
    star = plan.qi_star_limbs_d if star is None else star
    q_limbs = plan.q_limbs_d if q_limbs is None else q_limbs
    pad = (-rows) % BLK
    rp = jnp.pad(residues, ((0, 0), (0, pad))) if pad else residues
    kern = _make_compose_kernel(plan)
    out = pl.pallas_call(
        kern,
        grid=(rp.shape[1] // BLK,),
        in_specs=[
            pl.BlockSpec((t, BLK), lambda r: (0, r)),
            pl.BlockSpec((t, 1), lambda r: (0, 0)),
            pl.BlockSpec((t, 1), lambda r: (0, 0)),
            pl.BlockSpec((t, L), lambda r: (0, 0)),
            pl.BlockSpec((1, L), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLK, L), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rp.shape[1], L), residues.dtype),
        interpret=interpret,
    )(
        rp,
        qs.reshape(t, 1),
        qi_tilde.reshape(t, 1),
        star,
        q_limbs.reshape(1, L),
    )
    return out[:rows]
