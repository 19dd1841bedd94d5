"""Pallas TPU kernels for the batched NWC NTT / iNTT and the fused
no-shuffle polynomial-multiplication cascade (paper contribution 1 mapped
to the TPU memory hierarchy).

TPU mapping
-----------
* One grid step processes a (ROWS, n) tile of polynomials for one RNS
  channel, resident in VMEM; twiddles (n,) for that channel are also VMEM
  blocks.  Per-channel moduli and Barrett constants arrive as (1, 1)
  SMEM-style scalar blocks.
* The fused kernel runs NTT(a), NTT(b), the pointwise product and the
  iNTT inside ONE pallas_call: the NTT-domain product never exists in HBM.
  This is the TPU analogue of the paper's buffer-free NTT->iNTT cascade —
  on the FPGA the eliminated resource is the DSD shuffle buffer; here it
  is an HBM round-trip of 2 x ROWS x n x 8 bytes per channel.
* The fused *e2e* kernel goes one step further (the paper's full
  feed-forward datapath, Fig 10): CRT pre-processing, the cascade and
  CRT post-processing in ONE pallas_call, reusing the in-kernel stages
  of :mod:`repro.kernels.crt` — residue polynomials never exist in HBM
  either; only segments enter and product limbs leave.  Where the RNS
  plan allows (`t >= 2` with in-kernel decompose constants), the grid
  gains a channel-tiled axis: each grid step runs ONE channel's
  specialized circuit with per-channel constants delivered as scalar
  blocks (the data-driven decompose of :func:`repro.kernels.crt
  .decompose_stage_dyn`), accumulating the Eq-10 contributions in the
  revisited output block — per-step VMEM drops by t, so ``row_blk`` can
  grow past the fixed DEFAULT_E2E_ROWS=1 static unroll.
* Stage schedule (DESIGN.md §6): ``schedule="radix2"`` is the flat loop
  whose late forward (early inverse) stages pair at lane strides < 128;
  ``schedule="four_step"`` is the lane-aligned (n1, n2) tile schedule —
  column stages pair along the sublane axis, then the tile is transposed
  in VMEM and the row stages (twist-merged per-row twiddle tables) pair
  along the sublane axis too, so NO butterfly stage ever pairs along the
  lane axis at stride < 128.  The fused cascades keep the tiles
  transposed across the pointwise product: two transposes per cascade
  instead of four.
* Butterfly modular arithmetic is imported from
  :mod:`repro.core.modmath` — the same helpers the pure-jnp reference
  oracle uses, so kernel and oracle cannot drift.  When ``shifts`` is
  given (static), the per-channel Barrett constant ``eps`` replaces the
  generic ``%``; when ``lazy=(window, beta)`` is given the butterflies
  switch to Harvey lazy reduction (Shoup twiddle products, values in
  [0, window*q)) with ONE canonicalizing reduce at transform/cascade
  exit — O(1) conditional subtractions per transform instead of 5 per
  stage.

VMEM budget per grid step (n = 4096, ROWS = 8, int64):
  a, b tiles 2 x 256 KiB + twiddles 2 x 32 KiB + scratch ≈ 0.8 MiB << 128 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import modmath
from repro.core.modmath import add_mod, div2_mod, mul_mod, sub_mod
from repro.kernels.crt import (
    compose_finalize,
    decompose_stage,
    decompose_stage_dyn,
    plan_dec_arrays,
    require_dec,
)

DEFAULT_ROWS = 8
DEFAULT_E2E_ROWS = 1  # polynomials per grid step, unrolled-channel kernel
DEFAULT_E2E_ROWS_CHGRID = 4  # channel-tiled grid: per-step VMEM is ~1/t


# --------------------------------------------------------------------------
# butterfly closures (strict Barrett vs Harvey lazy) and stage loops
# --------------------------------------------------------------------------


def _butterflies(q, half=None, eps=None, shifts=None, lazy=None):
    """(ct, gs) butterfly pair.  Strict: canonical [0, q) values, 5
    conditional subtractions per stage.  Lazy (window, beta): values stay
    in [0, window*q), 1-2 conditional subtractions per stage."""
    if lazy is not None:
        window, beta = lazy

        def ct(u, v, w, ws):
            return modmath.lazy_ct_butterfly(
                u, v, w, ws, q, beta=beta, window=window
            )

        def gs(u, v, w, ws):
            return modmath.lazy_gs_butterfly(
                u, v, w, ws, q, half, beta=beta, window=window
            )

    else:

        def ct(u, v, w, ws):
            p = mul_mod(v, w, q, eps, shifts)
            return add_mod(u, p, q), sub_mod(u, p, q)

        def gs(u, v, w, ws):
            s = add_mod(u, v, q)
            d = mul_mod(sub_mod(u, v, q), w, q, eps, shifts)
            return div2_mod(s, half), div2_mod(d, half)

    return ct, gs


def _canon(x, q, lazy):
    """The single exit reduce of a lazy transform; identity when strict."""
    return x if lazy is None else modmath.canonicalize(x, q, lazy[0])


def _slc(tab, lo, hi, bcast):
    """Static twiddle-table slice, reshaped for broadcast; None-safe for
    the shoup table of a strict transform."""
    if tab is None:
        return None
    return jax.lax.slice_in_dim(tab, lo, hi)[bcast]


_B2 = (None, slice(None), None)  # (1, m, 1)          radix-2 tiles
_B3C = (None, slice(None), None, None)  # (1, m, 1, 1)   four-step columns
_B3R = (None, slice(None), None, slice(None))  # (1, m, 1, n1) rows


def _radix2_fwd(a, fwd, fwd_sh, ct):
    """CT/DIT stages on the last axis of a (rows, n) tile (flat
    schedule: stage pair stride n/2 .. 1)."""
    rows, n = a.shape
    m, t = 1, n
    while m < n:
        t //= 2
        w = _slc(fwd, m, 2 * m, _B2)
        ws = _slc(fwd_sh, m, 2 * m, _B2)
        x = a.reshape(rows, m, 2, t)
        hi, lo = ct(x[:, :, 0, :], x[:, :, 1, :], w, ws)
        a = jnp.stack([hi, lo], axis=2).reshape(rows, n)
        m *= 2
    return a


def _radix2_inv(a, inv, inv_sh, gs):
    """Mirror-order GS stages with the per-stage halving (Fig 9 PE)."""
    rows, n = a.shape
    h, t = n // 2, 1
    while h >= 1:
        w = _slc(inv, h, 2 * h, _B2)
        ws = _slc(inv_sh, h, 2 * h, _B2)
        x = a.reshape(rows, h, 2, t)
        s, d = gs(x[:, :, 0, :], x[:, :, 1, :], w, ws)
        a = jnp.stack([s, d], axis=2).reshape(rows, n)
        h //= 2
        t *= 2
    return a


def _fs_cols_fwd(x, fwd, fwd_sh, ct):
    """Column stages on the (rows, n1, n2) tile: pairing along the n1
    (sublane) axis, lane axis n2 intact; twiddles = fwd[:n1] prefix."""
    rows, n1, n2 = x.shape
    m, tc = 1, n1
    while m < n1:
        tc //= 2
        w = _slc(fwd, m, 2 * m, _B3C)
        ws = _slc(fwd_sh, m, 2 * m, _B3C)
        y = x.reshape(rows, m, 2, tc, n2)
        hi, lo = ct(y[:, :, 0], y[:, :, 1], w, ws)
        x = jnp.stack([hi, lo], axis=2).reshape(rows, n1, n2)
        m *= 2
    return x


def _fs_rows_fwd(xt, row_fwd, row_sh, ct):
    """Row stages on the TRANSPOSED (rows, n2, n1) tile: pairing along
    the n2 (sublane) axis with the (n2, n1) twist-merged row tables."""
    rows, n2, n1 = xt.shape
    m, tr = 1, n2
    while m < n2:
        tr //= 2
        w = _slc(row_fwd, m, 2 * m, _B3R)
        ws = _slc(row_sh, m, 2 * m, _B3R)
        y = xt.reshape(rows, m, 2, tr, n1)
        hi, lo = ct(y[:, :, 0], y[:, :, 1], w, ws)
        xt = jnp.stack([hi, lo], axis=2).reshape(rows, n2, n1)
        m *= 2
    return xt


def _fs_rows_inv(xt, row_inv, row_sh, gs):
    rows, n2, n1 = xt.shape
    h, tr = n2 // 2, 1
    while h >= 1:
        w = _slc(row_inv, h, 2 * h, _B3R)
        ws = _slc(row_sh, h, 2 * h, _B3R)
        y = xt.reshape(rows, h, 2, tr, n1)
        s, d = gs(y[:, :, 0], y[:, :, 1], w, ws)
        xt = jnp.stack([s, d], axis=2).reshape(rows, n2, n1)
        h //= 2
        tr *= 2
    return xt


def _fs_cols_inv(x, inv, inv_sh, gs):
    rows, n1, n2 = x.shape
    h, tc = n1 // 2, 1
    while h >= 1:
        w = _slc(inv, h, 2 * h, _B3C)
        ws = _slc(inv_sh, h, 2 * h, _B3C)
        y = x.reshape(rows, h, 2, tc, n2)
        s, d = gs(y[:, :, 0], y[:, :, 1], w, ws)
        x = jnp.stack([s, d], axis=2).reshape(rows, n1, n2)
        h //= 2
        tc *= 2
    return x


# -- hierarchical column transforms (DESIGN.md §10): at n >= 8192 the
# level-0 column length n1 = n/128 no longer fits a vreg-height tile, so
# the column transform itself recurses through the canonical
# four_step_chain — a length-c sub-transform along the sublane-side axis
# of a (rows, c, B) view, with deeper levels reached by RESHAPE only
# (the one physical transpose stays at level 0).


def _slc_sub(tab, lo, hi):
    """Static sub-row twiddle slice: a (sr, sc) per-level table sliced
    along sr and laid out (1, sc, m, 1, 1) to broadcast over the
    (rows, sc, m, tr, B) pairing view; None-safe."""
    if tab is None:
        return None
    w = jnp.swapaxes(jax.lax.slice_in_dim(tab, lo, hi), 0, 1)
    return w[None, :, :, None, None]


def _fs_sub_rows_fwd(x, rtab, rsh, ct):
    """Sub-row CT stages on a (rows, sc, sr, B) view: pairing along the
    sr axis with the per-sub-column twist-merged tables (rtab: (sr, sc))."""
    rows, sc, sr, B = x.shape
    m, tr = 1, sr
    while m < sr:
        tr //= 2
        w = _slc_sub(rtab, m, 2 * m)
        ws = _slc_sub(rsh, m, 2 * m)
        y = x.reshape(rows, sc, m, 2, tr, B)
        hi, lo = ct(y[:, :, :, 0], y[:, :, :, 1], w, ws)
        x = jnp.stack([hi, lo], axis=3).reshape(rows, sc, sr, B)
        m *= 2
    return x


def _fs_sub_rows_inv(x, rtab, rsh, gs):
    rows, sc, sr, B = x.shape
    h, tr = sr // 2, 1
    while h >= 1:
        w = _slc_sub(rtab, h, 2 * h)
        ws = _slc_sub(rsh, h, 2 * h)
        y = x.reshape(rows, sc, h, 2, tr, B)
        s, d = gs(y[:, :, :, 0], y[:, :, :, 1], w, ws)
        x = jnp.stack([s, d], axis=3).reshape(rows, sc, sr, B)
        h //= 2
        tr *= 2
    return x


def _fs_cols_fwd_hier(x, fwd, fwd_sh, sub_tabs, sub_shs, ct):
    """Length-c forward transform along axis 1 of a (rows, c, B) tile;
    ``sub_tabs`` holds the remaining per-level (sr, sc) sub-row tables
    (empty -> plain column stages on the fwd[:c] prefix).  The sub-column
    recursion folds sr into the batch axis — a reshape, not a transpose."""
    if not sub_tabs:
        return _fs_cols_fwd(x, fwd, fwd_sh, ct)
    rows, c, B = x.shape
    rtab = sub_tabs[0]
    sr, sc = rtab.shape[-2:]
    x = x.reshape(rows, sc, sr * B)
    x = _fs_cols_fwd_hier(x, fwd, fwd_sh, sub_tabs[1:], sub_shs[1:], ct)
    x = _fs_sub_rows_fwd(x.reshape(rows, sc, sr, B), rtab, sub_shs[0], ct)
    return x.reshape(rows, c, B)


def _fs_cols_inv_hier(x, inv, inv_sh, sub_tabs, sub_shs, gs):
    """Inverse mirror: sub-row GS stages first, then the sub-column
    recursion."""
    if not sub_tabs:
        return _fs_cols_inv(x, inv, inv_sh, gs)
    rows, c, B = x.shape
    rtab = sub_tabs[0]
    sr, sc = rtab.shape[-2:]
    x = _fs_sub_rows_inv(x.reshape(rows, sc, sr, B), rtab, sub_shs[0], gs)
    x = x.reshape(rows, sc, sr * B)
    x = _fs_cols_inv_hier(x, inv, inv_sh, sub_tabs[1:], sub_shs[1:], gs)
    return x.reshape(rows, c, B)


def _as_level_tuple(x):
    """Normalize a row-table argument: None / per-level tuple kept,
    single array -> 1-tuple (the historical depth-1 calling convention)."""
    if x is None or isinstance(x, tuple):
        return x
    return (x,)


def _level_shoups(row_sh, depth):
    """Per-level shoup companions; (None,) * depth for strict
    butterflies so the hier recursion can zip them with the tables."""
    if row_sh is None:
        return (None,) * depth
    return row_sh


def _fwd_stages(a, tabs, ct, *, schedule, to_transposed=False):
    """One forward transform of a (rows, n) tile.

    tabs = (fwd, fwd_shoup, row_fwd, row_fwd_shoup); the shoup entries
    are None for strict butterflies, the row entries for radix2.  Row
    entries are per-level tuples for the hierarchical schedule (a single
    array means depth 1).  With ``to_transposed`` the four-step result
    is returned as the (rows, n2, n1) transposed tile so a fused cascade
    can run the pointwise product and start the inverse without
    transposing back."""
    fwd, fwd_sh, row_fwd, row_sh = tabs
    if schedule != "four_step":
        return _radix2_fwd(a, fwd, fwd_sh, ct)
    row_fwd = _as_level_tuple(row_fwd)
    row_sh = _level_shoups(_as_level_tuple(row_sh), len(row_fwd))
    rows, n = a.shape
    n2, n1 = row_fwd[0].shape[-2:]
    x = _fs_cols_fwd_hier(
        a.reshape(rows, n1, n2), fwd, fwd_sh, row_fwd[1:], row_sh[1:], ct
    )
    xt = _fs_rows_fwd(jnp.swapaxes(x, -1, -2), row_fwd[0], row_sh[0], ct)
    if to_transposed:
        return xt
    return jnp.swapaxes(xt, -1, -2).reshape(rows, n)


def _inv_stages(a, tabs, gs, *, schedule, from_transposed=False):
    """One inverse transform; accepts the transposed tile when the
    caller (fused cascade) kept it transposed through the product."""
    inv, inv_sh, row_inv, row_sh = tabs
    if schedule != "four_step":
        return _radix2_inv(a, inv, inv_sh, gs)
    row_inv = _as_level_tuple(row_inv)
    row_sh = _level_shoups(_as_level_tuple(row_sh), len(row_inv))
    n2, n1 = row_inv[0].shape[-2:]
    rows = a.shape[0]
    if from_transposed:
        xt = a
    else:
        xt = jnp.swapaxes(a.reshape(rows, n1, n2), -1, -2)
    xt = _fs_rows_inv(xt, row_inv[0], row_sh[0], gs)
    x = _fs_cols_inv_hier(
        jnp.swapaxes(xt, -1, -2), inv, inv_sh, row_inv[1:], row_sh[1:], gs
    )
    return x.reshape(rows, n1 * n2)


def _cascade(a, b, ftabs, itabs, q, half, eps, shifts, lazy, schedule):
    """NTT(a) ⊙ NTT(b) -> iNTT entirely in VMEM.  Four-step tiles stay
    transposed across the pointwise product (2 transposes per cascade,
    not 4); lazy values are canonicalized once before the product (Shoup
    needs one canonical operand-pair) and once at exit."""
    ct, gs = _butterflies(q, half=half, eps=eps, shifts=shifts, lazy=lazy)
    tr = schedule == "four_step"
    fa = _canon(_fwd_stages(a, ftabs, ct, schedule=schedule, to_transposed=tr), q, lazy)
    fb = _canon(_fwd_stages(b, ftabs, ct, schedule=schedule, to_transposed=tr), q, lazy)
    prod = mul_mod(fa, fb, q, eps, shifts)
    out = _inv_stages(prod, itabs, gs, schedule=schedule, from_transposed=tr)
    return _canon(out, q, lazy)


# --------------------------------------------------------------------------
# kernels (shifts/schedule/lazy are static closure args; eps_ref is a
# dummy zero block when shifts is None and butterflies fall back to %)
# --------------------------------------------------------------------------


def _take(it, cond):
    return next(it) if cond else None


def _ref_or_none(ref):
    return None if ref is None else ref[...]


def _take_levels(it, cond, depth, load=True):
    """Consume one ref per hierarchy level (ORDER CONTRACT below): a
    per-level tuple when cond, else None.  ``load=False`` keeps the refs
    unread for kernels that slice per channel."""
    if not cond:
        return None
    refs = tuple(next(it) for _ in range(depth))
    return tuple(r[...] for r in refs) if load else refs


def _make_ntt_kernel(shifts, schedule, lazy, depth=1):
    four = schedule == "four_step"

    def kernel(*refs):
        it = iter(refs)
        q_ref, eps_ref, fwd_ref = next(it), next(it), next(it)
        fwd_sh = _ref_or_none(_take(it, lazy is not None))
        row_fwd = _take_levels(it, four, depth)
        row_sh = _take_levels(it, four and lazy is not None, depth)
        a_ref, o_ref = next(it), next(it)
        q = q_ref[0]
        eps = eps_ref[0] if shifts is not None else None
        ct, _ = _butterflies(q, eps=eps, shifts=shifts, lazy=lazy)
        out = _fwd_stages(
            a_ref[...], (fwd_ref[...], fwd_sh, row_fwd, row_sh), ct,
            schedule=schedule,
        )
        o_ref[...] = _canon(out, q, lazy)

    return kernel


def _make_intt_kernel(shifts, schedule, lazy, depth=1):
    four = schedule == "four_step"

    def kernel(*refs):
        it = iter(refs)
        q_ref, eps_ref, half_ref, inv_ref = next(it), next(it), next(it), next(it)
        inv_sh = _ref_or_none(_take(it, lazy is not None))
        row_inv = _take_levels(it, four, depth)
        row_sh = _take_levels(it, four and lazy is not None, depth)
        a_ref, o_ref = next(it), next(it)
        q = q_ref[0]
        eps = eps_ref[0] if shifts is not None else None
        half = half_ref[0]
        _, gs = _butterflies(q, half=half, eps=eps, shifts=shifts, lazy=lazy)
        out = _inv_stages(
            a_ref[...], (inv_ref[...], inv_sh, row_inv, row_sh), gs,
            schedule=schedule,
        )
        o_ref[...] = _canon(out, q, lazy)

    return kernel


def _make_fused_kernel(shifts, schedule, lazy, depth=1):
    four = schedule == "four_step"

    def kernel(*refs):
        it = iter(refs)
        q_ref, eps_ref, half_ref = next(it), next(it), next(it)
        fwd_ref, inv_ref = next(it), next(it)
        fwd_sh = _ref_or_none(_take(it, lazy is not None))
        inv_sh = _ref_or_none(_take(it, lazy is not None))
        row_fwd = _take_levels(it, four, depth)
        row_inv = _take_levels(it, four, depth)
        row_fsh = _take_levels(it, four and lazy is not None, depth)
        row_ish = _take_levels(it, four and lazy is not None, depth)
        a_ref, b_ref, o_ref = next(it), next(it), next(it)
        q = q_ref[0]
        eps = eps_ref[0] if shifts is not None else None
        half = half_ref[0]
        o_ref[...] = _cascade(
            a_ref[...], b_ref[...],
            (fwd_ref[...], fwd_sh, row_fwd, row_fsh),
            (inv_ref[...], inv_sh, row_inv, row_ish),
            q, half, eps, shifts, lazy, schedule,
        )

    return kernel


def _chan_tabs(ref, i):
    """Channel i's slice of a stacked (t, ...) table ref; None-safe and
    per-level for the hierarchical row-table tuples."""
    if ref is None:
        return None
    if isinstance(ref, tuple):
        return tuple(r[i] for r in ref)
    return ref[i]


def _make_fused_e2e_kernel(plan, scalars, shifts, schedule, lazy, depth=1):
    """The paper's full feed-forward datapath in ONE kernel: CRT
    pre-processing, the per-channel NTT -> ⊙ -> iNTT cascade and CRT
    post-processing, with every residue polynomial VMEM-resident.

    The channel loop is a static unroll: each iteration is one of the
    paper's t parallel specialized circuits, its moduli/Barrett/SAU
    constants baked in from the plan (``plan.dec`` + ``scalars``), its
    twiddles read from the (t, n) VMEM table blocks.  Only the segment
    tiles enter and the limb tile leaves through HBM."""
    four = schedule == "four_step"

    def kernel(*refs):
        it = iter(refs)
        fwd_ref, inv_ref = next(it), next(it)
        fwd_sh = _take(it, lazy is not None)
        inv_sh = _take(it, lazy is not None)
        row_fwd = _take_levels(it, four, depth, load=False)
        row_inv = _take_levels(it, four, depth, load=False)
        row_fsh = _take_levels(it, four and lazy is not None, depth, load=False)
        row_ish = _take_levels(it, four and lazy is not None, depth, load=False)
        star_ref, qlimb_ref, za_ref, zb_ref, o_ref = (
            next(it), next(it), next(it), next(it), next(it)
        )
        za = za_ref[...]  # (blk, n, S)
        zb = zb_ref[...]
        acc = jnp.zeros(za.shape[:-1] + (plan.L,), dtype=za.dtype)
        for i, (qi, half, eps) in enumerate(scalars):
            ch = plan.dec[i]
            # Step 1: residual computation (Alg 2, SAU circuit)
            ra = decompose_stage(za, ch, seg_count=plan.seg_count,
                                 t_prime=plan.t_prime)  # (blk, n)
            rb = decompose_stage(zb, ch, seg_count=plan.seg_count,
                                 t_prime=plan.t_prime)
            # Step 2: no-shuffle NTT cascade, product never leaves VMEM
            pi = _cascade(
                ra, rb,
                (fwd_ref[i], _chan_tabs(fwd_sh, i),
                 _chan_tabs(row_fwd, i), _chan_tabs(row_fsh, i)),
                (inv_ref[i], _chan_tabs(inv_sh, i),
                 _chan_tabs(row_inv, i), _chan_tabs(row_ish, i)),
                qi, half, eps, shifts, lazy, schedule,
            )
            # Step 3: this channel's Eq-10 contribution y_i * q_i^
            y = mul_mod(pi, int(plan.qi_tilde[i]), qi, eps, shifts)
            acc = acc + y[..., None] * star_ref[i][None, None, :]
        o_ref[...] = compose_finalize(acc, qlimb_ref[0], w=plan.w, t=plan.t)

    return kernel


def _make_fused_e2e_chgrid_kernel(plan, shifts, schedule, lazy, t, depth=1):
    """Channel-tiled variant: grid (row_blocks, t), ONE channel per grid
    step.  The per-channel SAU/Barrett/twiddle constants arrive as
    channel-indexed blocks (the data-driven decompose), the Eq-10
    contributions accumulate in the revisited output block, and the
    carry/subtract finalize runs on the last channel step.  Per-step
    VMEM is ~1/t of the unrolled kernel, so row_blk can grow."""
    four = schedule == "four_step"

    def kernel(*refs):
        it = iter(refs)
        (q_ref, eps_ref, half_ref, tilde_ref, sau_eps_ref, sau_s2_ref,
         acc_eps_ref, beta_e_ref, beta_s_ref, bc_ref) = (
            next(it) for _ in range(10)
        )
        fwd_ref, inv_ref = next(it), next(it)
        fwd_sh = _ref_or_none(_take(it, lazy is not None))
        inv_sh = _ref_or_none(_take(it, lazy is not None))
        row_fwd = _take_levels(it, four, depth)
        row_inv = _take_levels(it, four, depth)
        row_fsh = _take_levels(it, four and lazy is not None, depth)
        row_ish = _take_levels(it, four and lazy is not None, depth)
        star_ref, qlimb_ref, za_ref, zb_ref, o_ref = (
            next(it), next(it), next(it), next(it), next(it)
        )
        c = pl.program_id(1)
        qi = q_ref[0]
        eps = eps_ref[0] if shifts is not None else None
        half = half_ref[0]
        dec = functools.partial(
            decompose_stage_dyn,
            qi=qi, sau_eps=sau_eps_ref[0], sau_s2=sau_s2_ref[0],
            acc_eps=acc_eps_ref[0], beta_e=beta_e_ref[...],
            beta_s=beta_s_ref[...], block_consts=bc_ref[...],
            v=plan.v, seg_count=plan.seg_count, t_prime=plan.t_prime,
        )
        ra = dec(za_ref[...])  # (blk, n)
        rb = dec(zb_ref[...])
        pi = _cascade(
            ra, rb,
            (fwd_ref[...], fwd_sh, row_fwd, row_fsh),
            (inv_ref[...], inv_sh, row_inv, row_ish),
            qi, half, eps, shifts, lazy, schedule,
        )
        y = mul_mod(pi, tilde_ref[0], qi, eps, shifts)
        contrib = y[..., None] * star_ref[...][None, None, :]

        @pl.when(c == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += contrib

        @pl.when(c == t - 1)
        def _finalize():
            o_ref[...] = compose_finalize(
                o_ref[...], qlimb_ref[0], w=plan.w, t=plan.t
            )

    return kernel


# --------------------------------------------------------------------------
# pallas_call wrappers (grid = (channels, row_blocks))
# --------------------------------------------------------------------------


def _grid_specs(t: int, rows: int, n: int, row_blk: int):
    """Common BlockSpecs (leading channel axis squeezed with None):
    per-channel scalars, (n,) tables, (row_blk, n) data tiles."""
    scalar = pl.BlockSpec((None, 1), lambda c, r: (c, 0))
    table = pl.BlockSpec((None, n), lambda c, r: (c, 0))
    data = pl.BlockSpec((None, row_blk, n), lambda c, r: (c, r, 0))
    return scalar, table, data


def _pad_rows(x, row_blk):
    rows = x.shape[1]
    pad = (-rows) % row_blk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x, rows


def _eps_block(eps, qs, t):
    """(t, 1) Barrett-eps block; zeros (same dtype as qs) when unused."""
    if eps is None:
        return jnp.zeros_like(qs).reshape(t, 1)
    return eps.reshape(t, 1)


def _stage_tables(inputs, specs, lazy, four, make_table_spec, make_fs_spec,
                  shoups, rows, row_shoups):
    """Append the optional shoup/four-step table inputs + specs.

    ORDER CONTRACT (the single owner, used by every wrapper; the kernel
    factories unpack with ``_take``/``_take_levels`` in the same order):
    [shoup tables...] when lazy, then [four-step row tables...] when
    four, then [their shoup tables...] when both.  ``shoups``/``rows``/
    ``row_shoups`` are per-direction tuples (1 entry for the
    single-direction kernels, fwd+inv for the fused ones); each
    direction's row entry may itself be a per-level tuple for the
    hierarchical schedule, flattened direction-major, level-minor.
    ``make_table_spec``/``make_fs_spec`` build the grid-appropriate
    BlockSpec from the array."""
    if lazy is not None:
        for x in shoups:
            inputs.append(x)
            specs.append(make_table_spec(x))
    if four:
        for x in rows:
            for lv in (x if isinstance(x, tuple) else (x,)):
                inputs.append(lv)
                specs.append(make_fs_spec(lv))
        if lazy is not None:
            for x in row_shoups:
                for lv in (x if isinstance(x, tuple) else (x,)):
                    inputs.append(lv)
                    specs.append(make_fs_spec(lv))


# BlockSpec builders for the three grid layouts the tables ride in:
# per-channel blocks on a (channels, row_blocks) grid, full blocks on a
# (row_blocks,) grid, per-channel blocks on a (row_blocks, channels) grid.


def _chan_table_spec(x):
    return pl.BlockSpec((None, x.shape[-1]), lambda c, r: (c, 0))


def _chan_fs_spec(x):
    return pl.BlockSpec((None,) + x.shape[-2:], lambda c, r: (c, 0, 0))


def _full_table_spec(x):
    return pl.BlockSpec(x.shape, lambda r: (0,) * x.ndim)


def _chgrid_table_spec(x):
    return pl.BlockSpec((None, x.shape[-1]), lambda r, c: (c, 0))


def _chgrid_fs_spec(x):
    return pl.BlockSpec((None,) + x.shape[-2:], lambda r, c: (c, 0, 0))


@functools.partial(
    jax.jit,
    static_argnames=("shifts", "schedule", "lazy", "row_blk", "interpret"),
)
def ntt_channels_pallas(
    a, qs, fwd, eps=None, fwd_shoup=None, row_fwd=None, row_fwd_shoup=None,
    *, shifts=None, schedule: str = "radix2", lazy=None,
    row_blk: int = DEFAULT_ROWS, interpret: bool,
):
    """a: (t, rows, n) -> forward NTT per channel.  qs: (t,), fwd: (t, n);
    row_fwd: (t, n2, n1) twist-merged row tables (four_step only) or a
    per-level tuple of them for the hierarchical schedule; the *_shoup
    tables ride along (same structure) when lazy=(window, beta).
    ``schedule`` is a concrete string or a resolved ScheduleSpec."""
    kind = getattr(schedule, "kind", schedule)
    row_fwd = _as_level_tuple(row_fwd)
    row_fwd_shoup = _as_level_tuple(row_fwd_shoup)
    depth = len(row_fwd) if isinstance(row_fwd, tuple) else 1
    t, _, n = a.shape
    a, rows = _pad_rows(a, row_blk)
    scalar, table, data = _grid_specs(t, a.shape[1], n, row_blk)
    inputs = [qs.reshape(t, 1), _eps_block(eps, qs, t), fwd]
    specs = [scalar, scalar, table]
    _stage_tables(
        inputs, specs, lazy, kind == "four_step",
        _chan_table_spec, _chan_fs_spec,
        (fwd_shoup,), (row_fwd,), (row_fwd_shoup,),
    )
    inputs.append(a)
    specs.append(data)
    out = pl.pallas_call(
        _make_ntt_kernel(shifts, kind, lazy, depth),
        grid=(t, a.shape[1] // row_blk),
        in_specs=specs,
        out_specs=data,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(*inputs)
    return out[:, :rows]


@functools.partial(
    jax.jit,
    static_argnames=("shifts", "schedule", "lazy", "row_blk", "interpret"),
)
def intt_channels_pallas(
    a, qs, half, inv, eps=None, inv_shoup=None, row_inv=None, row_inv_shoup=None,
    *, shifts=None, schedule: str = "radix2", lazy=None,
    row_blk: int = DEFAULT_ROWS, interpret: bool,
):
    kind = getattr(schedule, "kind", schedule)
    row_inv = _as_level_tuple(row_inv)
    row_inv_shoup = _as_level_tuple(row_inv_shoup)
    depth = len(row_inv) if isinstance(row_inv, tuple) else 1
    t, _, n = a.shape
    a, rows = _pad_rows(a, row_blk)
    scalar, table, data = _grid_specs(t, a.shape[1], n, row_blk)
    inputs = [qs.reshape(t, 1), _eps_block(eps, qs, t), half.reshape(t, 1), inv]
    specs = [scalar, scalar, scalar, table]
    _stage_tables(
        inputs, specs, lazy, kind == "four_step",
        _chan_table_spec, _chan_fs_spec,
        (inv_shoup,), (row_inv,), (row_inv_shoup,),
    )
    inputs.append(a)
    specs.append(data)
    out = pl.pallas_call(
        _make_intt_kernel(shifts, kind, lazy, depth),
        grid=(t, a.shape[1] // row_blk),
        in_specs=specs,
        out_specs=data,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(*inputs)
    return out[:, :rows]


@functools.partial(
    jax.jit,
    static_argnames=("shifts", "schedule", "lazy", "row_blk", "interpret"),
)
def fused_polymul_pallas(
    a, b, qs, half, fwd, inv, eps=None, fwd_shoup=None, inv_shoup=None,
    row_fwd=None, row_inv=None, row_fwd_shoup=None, row_inv_shoup=None,
    *, shifts=None, schedule: str = "radix2", lazy=None,
    row_blk: int = DEFAULT_ROWS, interpret: bool,
):
    """(t, rows, n) x (t, rows, n) -> negacyclic products, fused cascade."""
    kind = getattr(schedule, "kind", schedule)
    row_fwd, row_inv = _as_level_tuple(row_fwd), _as_level_tuple(row_inv)
    row_fwd_shoup = _as_level_tuple(row_fwd_shoup)
    row_inv_shoup = _as_level_tuple(row_inv_shoup)
    depth = len(row_fwd) if isinstance(row_fwd, tuple) else 1
    t, _, n = a.shape
    a, rows = _pad_rows(a, row_blk)
    b, _ = _pad_rows(b, row_blk)
    scalar, table, data = _grid_specs(t, a.shape[1], n, row_blk)
    inputs = [
        qs.reshape(t, 1), _eps_block(eps, qs, t), half.reshape(t, 1), fwd, inv,
    ]
    specs = [scalar, scalar, scalar, table, table]
    _stage_tables(
        inputs, specs, lazy, kind == "four_step",
        _chan_table_spec, _chan_fs_spec,
        (fwd_shoup, inv_shoup), (row_fwd, row_inv),
        (row_fwd_shoup, row_inv_shoup),
    )
    inputs += [a, b]
    specs += [data, data]
    out = pl.pallas_call(
        _make_fused_kernel(shifts, kind, lazy, depth),
        grid=(t, a.shape[1] // row_blk),
        in_specs=specs,
        out_specs=data,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(*inputs)
    return out[:, :rows]


@functools.partial(
    jax.jit,
    static_argnames=(
        "plan", "schedule", "lazy", "channel_grid", "row_blk", "interpret",
    ),
)
def fused_e2e_polymul_pallas(
    za, zb, fwd, inv, star, q_limbs, fwd_shoup=None, inv_shoup=None,
    row_fwd=None, row_inv=None, row_fwd_shoup=None, row_inv_shoup=None,
    *, plan, schedule: str = "radix2", lazy=None,
    channel_grid: bool | None = None, row_blk: int | None = None,
    interpret: bool,
):
    """za, zb: (rows, n, S) base-2^v segment tiles -> (rows, n, L) limbs
    of the negacyclic products mod q: decompose -> NTT -> ⊙ -> iNTT ->
    compose inside ONE pallas_call.

    fwd/inv: (t, n) twiddle tables, star: (t, L) q_i^ limbs, q_limbs:
    (L,) — all device-resident uploads off the tables/plan.  Grid:

    * ``channel_grid=False`` — (row_blocks,): the channel loop unrolls
      inside the kernel (every channel's circuit in one grid step, the
      Eq-10 recombination done in registers).
    * ``channel_grid=True`` (default whenever t >= 2) — (row_blocks, t):
      one channel per grid step with per-channel constants as
      channel-indexed blocks; Eq-10 contributions accumulate in the
      revisited output block (index map constant in the channel axis, so
      the block stays VMEM-resident across the t inner steps — no extra
      HBM traffic) and the finalize runs on the last channel step.

    VMEM per grid step at the paper's point (n=4096, t=6, S=6, L=7,
    int64): unrolled row_blk=1 ~= 1 MiB; channel grid row_blk=4 ~= 1.5
    MiB — both << 16 MiB.
    """
    require_dec(plan)
    kind = getattr(schedule, "kind", schedule)
    row_fwd, row_inv = _as_level_tuple(row_fwd), _as_level_tuple(row_inv)
    row_fwd_shoup = _as_level_tuple(row_fwd_shoup)
    row_inv_shoup = _as_level_tuple(row_inv_shoup)
    depth = len(row_fwd) if isinstance(row_fwd, tuple) else 1
    rows, n, S = za.shape
    t, L = plan.t, plan.L
    scalars, shifts = modmath.channel_mul_constants(plan.qs)
    if channel_grid is None:
        channel_grid = t >= 2
    if row_blk is None:
        row_blk = getattr(schedule, "row_blk", 0) or (
            DEFAULT_E2E_ROWS_CHGRID if channel_grid else DEFAULT_E2E_ROWS
        )
    pad = (-rows) % row_blk
    if pad:
        zpad = ((0, pad), (0, 0), (0, 0))
        za = jnp.pad(za, zpad)
        zb = jnp.pad(zb, zpad)
    row_blocks = za.shape[0] // row_blk
    four = kind == "four_step"
    if not channel_grid:
        table = pl.BlockSpec((t, n), lambda r: (0, 0))
        data = pl.BlockSpec((row_blk, n, S), lambda r: (r, 0, 0))
        inputs = [fwd, inv]
        specs = [table, table]
        _stage_tables(
            inputs, specs, lazy, four, _full_table_spec, _full_table_spec,
            (fwd_shoup, inv_shoup), (row_fwd, row_inv),
            (row_fwd_shoup, row_inv_shoup),
        )
        inputs += [star, q_limbs.reshape(1, L), za, zb]
        specs += [
            pl.BlockSpec((t, L), lambda r: (0, 0)),
            pl.BlockSpec((1, L), lambda r: (0, 0)),
            data,
            data,
        ]
        out = pl.pallas_call(
            _make_fused_e2e_kernel(plan, scalars, shifts, kind, lazy, depth),
            grid=(row_blocks,),
            in_specs=specs,
            out_specs=pl.BlockSpec((row_blk, n, L), lambda r: (r, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((za.shape[0], n, L), za.dtype),
            interpret=interpret,
        )(*inputs)
        return out[:rows]
    # channel-tiled grid: (row_blocks, t), channel axis innermost so the
    # revisited output block accumulates in VMEM
    dec_arrs = plan_dec_arrays(plan)
    qs_d = jnp.asarray(plan.qs)
    scal = pl.BlockSpec((None, 1), lambda r, c: (c, 0))
    table = pl.BlockSpec((None, n), lambda r, c: (c, 0))
    data = pl.BlockSpec((row_blk, n, S), lambda r, c: (r, 0, 0))

    def vec_spec(x):
        return pl.BlockSpec((None, x.shape[-1]), lambda r, c: (c, 0))

    # per-channel (qi, half, eps) come from the SAME `scalars` tuple the
    # unrolled kernel bakes into its closure, so the two e2e variants
    # cannot disagree on the Barrett envelope
    eps_arr = (
        None
        if scalars[0][2] is None
        else jnp.asarray([s[2] for s in scalars])
    )
    inputs = [
        qs_d.reshape(t, 1),
        _eps_block(eps_arr, qs_d, t),
        jnp.asarray([s[1] for s in scalars]).reshape(t, 1),
        jnp.asarray(plan.qi_tilde).reshape(t, 1),
        jnp.asarray(dec_arrs["sau_eps"]).reshape(t, 1),
        jnp.asarray(dec_arrs["sau_s2"]).reshape(t, 1),
        jnp.asarray(dec_arrs["acc_eps"]).reshape(t, 1),
        jnp.asarray(dec_arrs["beta_e"]),
        jnp.asarray(dec_arrs["beta_s"]),
        jnp.asarray(dec_arrs["block_consts"]),
    ]
    specs = [scal] * 7 + [vec_spec(x) for x in inputs[7:]]
    inputs += [fwd, inv]
    specs += [table, table]
    _stage_tables(
        inputs, specs, lazy, four, _chgrid_table_spec, _chgrid_fs_spec,
        (fwd_shoup, inv_shoup), (row_fwd, row_inv),
        (row_fwd_shoup, row_inv_shoup),
    )
    inputs += [star, q_limbs.reshape(1, L), za, zb]
    specs += [
        pl.BlockSpec((None, L), lambda r, c: (c, 0)),
        pl.BlockSpec((1, L), lambda r, c: (0, 0)),
        data,
        data,
    ]
    out = pl.pallas_call(
        _make_fused_e2e_chgrid_kernel(plan, shifts, kind, lazy, t, depth),
        grid=(row_blocks, t),
        in_specs=specs,
        out_specs=pl.BlockSpec((row_blk, n, L), lambda r, c: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((za.shape[0], n, L), za.dtype),
        interpret=interpret,
    )(*inputs)
    return out[:rows]
