"""Pallas TPU kernels for the solver's hot-spot ops.

The kernels mirror the fused PyTorch kernels (einsum/addcmul) that make
torchode fast, re-thought for the TPU memory hierarchy:

  - ``fused_update``: one HBM->VMEM pass over the stage tensor K produces BOTH
    the solution update and the embedded error estimate.  The stage weights are
    compile-time constants (Butcher tableau), so the combination is a fully
    unrolled multiply-add chain on the VPU -- no reduction loop, no second pass.
  - ``stage_accum``: same structure for intermediate stage states.
  - ``error_norm``: the weighted-RMS error norm fused with its scale
    computation; accumulates sum-of-squares across feature tiles in the output
    block (grid is sequential on TPU), finalizing sqrt(mean) on the last tile.
  - ``interp_eval``: masked Horner evaluation of the dense-output cubic into the
    (aliased) output buffer -- torchode's "evaluation tracking" hot spot.
  - ``batched_lu_factor`` + ``fused_newton_iter``: the factor-once Newton
    path.  The LU kernel factors (8, n, n) batch tiles; one transpose per
    factorisation then lays the factors out with INSTANCES ON LANES,
    ``(n, n, bq)``, so that factor column j of a block of L instances is one
    aligned (n, L) slab.  L is 128 once the 8-padded batch fills a lane
    tile (the batch padded to a multiple of 128 with identity instances),
    else the whole 8-padded batch.  Each Newton iteration is then one
    substitution launch whose column steps are independent vreg ops over
    (n, L) vectors, with no cross-lane reduction.
  - ``masked_bisect_refine``: one masked bisection step of the event-time
    localizer -- bracket halving fused with the Horner evaluation of the
    dense-output cubic at the new midpoint.

Tiling: (8, 128)-aligned blocks (f32 VREG lane layout); wrappers pad
non-aligned shapes and slice back, so kernels always see divisible shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

BB = 8  # batch tile
BF = 128  # feature tile (lane dimension)


def _pad_to(x, axis, mult, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _cdiv(a, b):
    return (a + b - 1) // b


# ---------------------------------------------------------------- fused update


def _fused_update_kernel(y_ref, k_ref, dt_ref, y1_ref, err_ref, *, b_sol, b_err):
    y = y_ref[...]
    dt = dt_ref[...]  # (BB, 1)
    acc_sol = jnp.zeros_like(y)
    acc_err = jnp.zeros_like(y)
    for j in range(k_ref.shape[0]):  # unrolled: s is 1..7
        k = k_ref[j]
        if b_sol[j] != 0.0:
            acc_sol = acc_sol + b_sol[j] * k
        if b_err[j] != 0.0:
            acc_err = acc_err + b_err[j] * k
    y1_ref[...] = y + dt * acc_sol
    err_ref[...] = dt * acc_err


def fused_update(y, K, dt, b_sol, b_err, *, interpret=False):
    b_sol = np.asarray(b_sol, dtype=np.float64)
    b_err = np.asarray(b_err, dtype=np.float64)
    b, f = y.shape
    s = K.shape[0]
    yp = _pad_to(_pad_to(y, 0, BB), 1, BF)
    Kp = _pad_to(_pad_to(K, 1, BB), 2, BF)
    dtp = _pad_to(dt[:, None], 0, BB)
    bp, fp = yp.shape
    grid = (bp // BB, fp // BF)
    kernel = functools.partial(
        _fused_update_kernel, b_sol=tuple(b_sol.tolist()), b_err=tuple(b_err.tolist())
    )
    y1, err = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BB, BF), lambda i, j: (i, j)),
            pl.BlockSpec((s, BB, BF), lambda i, j: (0, i, j)),
            pl.BlockSpec((BB, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BB, BF), lambda i, j: (i, j)),
            pl.BlockSpec((BB, BF), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(yp.shape, y.dtype),
            jax.ShapeDtypeStruct(yp.shape, y.dtype),
        ],
        interpret=interpret,
    )(yp, Kp, dtp)
    return y1[:b, :f], err[:b, :f]


# ---------------------------------------------------------------- stage accum


def _stage_accum_kernel(y_ref, k_ref, dt_ref, out_ref, *, coeffs):
    acc = jnp.zeros_like(y_ref[...])
    for j in range(k_ref.shape[0]):
        if coeffs[j] != 0.0:
            acc = acc + coeffs[j] * k_ref[j]
    out_ref[...] = y_ref[...] + dt_ref[...] * acc


def stage_accum(y, dt, K, coeffs, *, interpret=False):
    coeffs = np.asarray(coeffs, dtype=np.float64)
    b, f = y.shape
    s = K.shape[0]
    yp = _pad_to(_pad_to(y, 0, BB), 1, BF)
    Kp = _pad_to(_pad_to(K, 1, BB), 2, BF)
    dtp = _pad_to(dt[:, None], 0, BB)
    bp, fp = yp.shape
    out = pl.pallas_call(
        functools.partial(_stage_accum_kernel, coeffs=tuple(coeffs.tolist())),
        grid=(bp // BB, fp // BF),
        in_specs=[
            pl.BlockSpec((BB, BF), lambda i, j: (i, j)),
            pl.BlockSpec((s, BB, BF), lambda i, j: (0, i, j)),
            pl.BlockSpec((BB, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BB, BF), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(yp.shape, y.dtype),
        interpret=interpret,
    )(yp, Kp, dtp)
    return out[:b, :f]


# ----------------------------------------------------------------- error norm


def _error_norm_kernel(err_ref, y0_ref, y1_ref, atol_ref, rtol_ref, out_ref, *, n_feat, nf_tiles):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    scale = atol_ref[...] + rtol_ref[...] * jnp.maximum(
        jnp.abs(y0_ref[...]), jnp.abs(y1_ref[...])
    )
    r = err_ref[...] / scale
    out_ref[...] += jnp.sum(r * r, axis=1, keepdims=True)

    @pl.when(j == nf_tiles - 1)
    def _finalize():
        out_ref[...] = jnp.sqrt(out_ref[...] / n_feat)


def error_norm(err, y0, y1, atol, rtol, *, interpret=False):
    b, f = err.shape
    dtype = err.dtype
    # Tolerances may be scalar, per-instance (b,) or full (b, f) -- same
    # contract as the ref oracle.  Shape is static, so the common scalar/(b,)
    # case keeps streaming cheap (BB, 1) tolerance blocks; only genuine
    # per-feature tolerances pay for full (BB, BF) tiles.
    atol, rtol = ref.broadcast_tolerances(atol, rtol, dtype)
    per_feature = atol.ndim == 2 and atol.shape[1] > 1 or rtol.ndim == 2 and rtol.shape[1] > 1
    if per_feature:
        atol = jnp.broadcast_to(atol, (b, f))
        rtol = jnp.broadcast_to(rtol, (b, f))
        tol_block, tol_index = (BB, BF), (lambda i, j: (i, j))
        atolp = _pad_to(_pad_to(atol, 0, BB, value=1), 1, BF, value=1)
        rtolp = _pad_to(_pad_to(rtol, 0, BB, value=1), 1, BF, value=1)
    else:
        atol = jnp.broadcast_to(atol.reshape((-1, 1)) if atol.ndim else atol, (b, 1))
        rtol = jnp.broadcast_to(rtol.reshape((-1, 1)) if rtol.ndim else rtol, (b, 1))
        tol_block, tol_index = (BB, 1), (lambda i, j: (i, 0))
        atolp = _pad_to(atol, 0, BB, value=1)
        rtolp = _pad_to(rtol, 0, BB, value=1)
    # Padding is exact: padded err entries are 0, padded y entries 1 and padded
    # atol cells 1, so every padded cell contributes 0 / (positive scale) = 0 to
    # the sum of squares; we divide by the TRUE feature count.
    errp = _pad_to(_pad_to(err, 0, BB), 1, BF)
    y0p = _pad_to(_pad_to(y0, 0, BB, value=1), 1, BF, value=1)
    y1p = _pad_to(_pad_to(y1, 0, BB, value=1), 1, BF, value=1)
    bp, fp = errp.shape
    nf_tiles = fp // BF
    out = pl.pallas_call(
        functools.partial(_error_norm_kernel, n_feat=float(f), nf_tiles=nf_tiles),
        grid=(bp // BB, nf_tiles),
        in_specs=[
            pl.BlockSpec((BB, BF), lambda i, j: (i, j)),
            pl.BlockSpec((BB, BF), lambda i, j: (i, j)),
            pl.BlockSpec((BB, BF), lambda i, j: (i, j)),
            pl.BlockSpec(tol_block, tol_index),
            pl.BlockSpec(tol_block, tol_index),
        ],
        out_specs=pl.BlockSpec((BB, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, 1), dtype),
        interpret=interpret,
    )(errp, y0p, y1p, atolp, rtolp)
    return out[:b, 0]


# ------------------------------------------------------------------ interp


BN = 128  # eval-point tile of the feature-lane interp layout
IB = 64  # row tile of the flattened interp layout
IL = 512  # lane tile over its flattened (point, feature) axis


def _interp_kernel(c0_ref, c1_ref, c2_ref, c3_ref, x_ref, m_ref, prev_ref, out_ref):
    """Feature-lane layout: (BB, BF) coefficient tiles, (BB, BN) points."""
    x = x_ref[...][:, :, None]  # (BB, BN, 1)
    c0 = c0_ref[...][:, None, :]  # (BB, 1, BF)
    c1 = c1_ref[...][:, None, :]
    c2 = c2_ref[...][:, None, :]
    c3 = c3_ref[...][:, None, :]
    acc = ((c3 * x + c2) * x + c1) * x + c0  # Horner
    m = m_ref[...][:, :, None] != 0  # int32 mask: Mosaic cannot reshape bool vectors
    out_ref[...] = jnp.where(m, acc, prev_ref[...])


def _interp_flat_kernel(c0_ref, c1_ref, c2_ref, c3_ref, x_ref, m_ref, prev_ref, out_ref):
    """Flattened layout: every operand already spans the (point, feature) axis."""
    x = x_ref[...]
    acc = ((c3_ref[...] * x + c2_ref[...]) * x + c1_ref[...]) * x + c0_ref[...]  # Horner
    out_ref[...] = jnp.where(m_ref[...] != 0, acc, prev_ref[...])


def interp_eval(coeffs, x, mask, out, *, interpret=False):
    """Two layouts, chosen by how much padding f to the lane tile costs.

    Features on the lane axis keep the per-point and per-feature operands
    compact, but pad the (b, n, f) buffer to 128 lanes: 64x the buffer at
    f = 2, more than a chip holds for a large dense-output batch.  So while
    that padding would more than quadruple the buffer, the kernel instead
    sees (b, n * f) with the per-point and per-feature operands broadcast
    onto the flattened axis outside it (four more buffer-sized operands),
    and is a plain elementwise select.  Either way the mask travels as
    int32: Mosaic cannot reshape or widen bool vectors.
    """
    b, n = x.shape
    f = coeffs[0].shape[1]
    if _cdiv(f, BF) * BF > 4 * f:
        return _interp_eval_flat(coeffs, x, mask, out, interpret=interpret)
    cs = [_pad_to(_pad_to(c, 0, BB), 1, BF) for c in coeffs]
    xp = _pad_to(_pad_to(x, 0, BB), 1, BN)
    mp = _pad_to(_pad_to(mask.astype(jnp.int32), 0, BB), 1, BN)
    outp = _pad_to(_pad_to(_pad_to(out, 0, BB), 1, BN), 2, BF)
    bp, np_ = xp.shape
    fp = cs[0].shape[1]
    res = pl.pallas_call(
        _interp_kernel,
        grid=(bp // BB, np_ // BN, fp // BF),
        in_specs=[
            pl.BlockSpec((BB, BF), lambda i, j, k: (i, k)),
            pl.BlockSpec((BB, BF), lambda i, j, k: (i, k)),
            pl.BlockSpec((BB, BF), lambda i, j, k: (i, k)),
            pl.BlockSpec((BB, BF), lambda i, j, k: (i, k)),
            pl.BlockSpec((BB, BN), lambda i, j, k: (i, j)),
            pl.BlockSpec((BB, BN), lambda i, j, k: (i, j)),
            pl.BlockSpec((BB, BN, BF), lambda i, j, k: (i, j, k)),
        ],
        out_specs=pl.BlockSpec((BB, BN, BF), lambda i, j, k: (i, j, k)),
        out_shape=jax.ShapeDtypeStruct(outp.shape, out.dtype),
        input_output_aliases={6: 0},
        interpret=interpret,
    )(*cs, xp, mp, outp)
    return res[:b, :n, :f]


def _interp_eval_flat(coeffs, x, mask, out, *, interpret):
    b, n = x.shape
    f = coeffs[0].shape[1]

    def flat(a):  # (b, n * f), padded to whole (IB, IL) tiles
        return _pad_to(_pad_to(a.reshape(b, n * f), 0, IB), 1, IL)

    cs = [flat(jnp.broadcast_to(c[:, None, :], (b, n, f))) for c in coeffs]
    xp = flat(jnp.broadcast_to(x[:, :, None], (b, n, f)))
    mp = flat(jnp.broadcast_to(mask[:, :, None], (b, n, f)).astype(jnp.int32))
    outp = flat(out)
    spec = pl.BlockSpec((IB, IL), lambda i, j: (i, j))
    res = pl.pallas_call(
        _interp_flat_kernel,
        grid=(outp.shape[0] // IB, outp.shape[1] // IL),
        in_specs=[spec] * 7,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(outp.shape, out.dtype),
        input_output_aliases={6: 0},
        interpret=interpret,
    )(*cs, xp, mp, outp)
    return res[:b, : n * f].reshape(b, n, f)


# ------------------------------------------------------ masked bisect refine


def _bisect_refine_kernel(
    c0_ref, c1_ref, c2_ref, c3_ref, lo_ref, hi_ref, vlo_ref, vmid_ref, act_ref,
    lo_out, hi_out, vlo_out, mid_out, y_out,
):
    lo = lo_ref[...]  # (BB, 1)
    hi = hi_ref[...]
    v_lo = vlo_ref[...]
    v_mid = vmid_ref[...]
    active = act_ref[...]
    mid = 0.5 * (lo + hi)
    left = jnp.sign(v_lo) != jnp.sign(v_mid)
    hi_new = jnp.where(active & left, mid, hi)
    lo_new = jnp.where(active & ~left, mid, lo)
    vlo_new = jnp.where(active & ~left, v_mid, v_lo)
    mid_new = 0.5 * (lo_new + hi_new)
    # The (BB, 1) bracket outputs are written once per feature tile; the
    # values do not depend on the feature tile, so the rewrite is idempotent
    # (the TPU grid runs sequentially).
    lo_out[...] = lo_new
    hi_out[...] = hi_new
    vlo_out[...] = vlo_new
    mid_out[...] = mid_new
    x = mid_new  # (BB, 1), broadcasts against the (BB, BF) coefficient tiles
    y_out[...] = ((c3_ref[...] * x + c2_ref[...]) * x + c1_ref[...]) * x + c0_ref[...]


def masked_bisect_refine(coeffs, lo, hi, v_lo, v_mid, active, *, interpret=False):
    c0, c1, c2, c3 = coeffs  # the stepper's dense output is cubic Hermite
    b, f = c0.shape
    cs = [_pad_to(_pad_to(c, 0, BB), 1, BF) for c in (c0, c1, c2, c3)]
    # Padded rows: values 0, active False -> sign(0) == sign(0) keeps the
    # bracket untouched; the padded outputs are sliced away.
    lop = _pad_to(lo[:, None], 0, BB)
    hip = _pad_to(hi[:, None], 0, BB)
    vlop = _pad_to(v_lo[:, None], 0, BB)
    vmidp = _pad_to(v_mid[:, None], 0, BB)
    actp = _pad_to(active[:, None], 0, BB)
    bp, fp = cs[0].shape
    scalar_spec = pl.BlockSpec((BB, 1), lambda i, j: (i, 0))
    tile_spec = pl.BlockSpec((BB, BF), lambda i, j: (i, j))
    lo_n, hi_n, vlo_n, mid_n, y_mid = pl.pallas_call(
        _bisect_refine_kernel,
        grid=(bp // BB, fp // BF),
        in_specs=[tile_spec, tile_spec, tile_spec, tile_spec,
                  scalar_spec, scalar_spec, scalar_spec, scalar_spec, scalar_spec],
        out_specs=[scalar_spec, scalar_spec, scalar_spec, scalar_spec, tile_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), lo.dtype),
            jax.ShapeDtypeStruct((bp, 1), hi.dtype),
            jax.ShapeDtypeStruct((bp, 1), v_lo.dtype),
            jax.ShapeDtypeStruct((bp, 1), lo.dtype),
            jax.ShapeDtypeStruct((bp, fp), c0.dtype),
        ],
        interpret=interpret,
    )(*cs, lop, hip, vlop, vmidp, actp)
    return lo_n[:b, 0], hi_n[:b, 0], vlo_n[:b, 0], mid_n[:b, 0], y_mid[:b, :f]


# ----------------------------------------------------- batched LU factorization


def _lu_factor_kernel(a_ref, lu_out, perm_out, *, n):
    """Partial-pivoted LU factorization, vectorized over the batch tile.

    One program owns BB instances and their full (R, C) matrices in VMEM
    (R = rows padded to the 8-sublane layout, C = columns padded to the
    128-lane layout -- stiff ODE systems are small, so rows are NOT padded
    to a full lane multiple).  Row and column selection is done with one-hot
    masked reductions (Mosaic lowers no dynamic gathers or value slices), the
    pivot search with a max-reduction + first-match instead of argmax, so
    every op vectorizes.  Only the true n columns are eliminated: the padded
    block is an identity that never mixes with real rows.  The factors are
    stored in place -- the unit-lower multipliers below the diagonal, U on
    and above -- and the row permutation is tracked as a (BB, R) int32
    vector (entry swaps mirror the row swaps).  This runs ONCE per implicit
    solver step; every ``fused_newton_iter`` launch then back-substitutes
    against the stored factors, so each Newton iteration costs O(n^2)
    triangular solves and no O(n^3) elimination.
    """
    A = a_ref[...]  # (BB, R, C)
    bt, R, C = A.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (bt, R), 1)  # (BB, R)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, C), 1)  # (BB, C)
    row3 = jax.lax.broadcasted_iota(jnp.int32, (1, R, C), 1)
    col3 = jax.lax.broadcasted_iota(jnp.int32, (1, R, C), 2)

    def body(i, carry):
        A, perm = carry
        col = jnp.sum(jnp.where(col3 == i, A, 0.0), axis=2)  # (BB, R)
        mag = jnp.where(rows >= i, jnp.abs(col), -1.0)
        m = jnp.max(mag, axis=1, keepdims=True)
        cand = mag == m
        p = jnp.min(jnp.where(cand, rows, R), axis=1, keepdims=True)  # (BB, 1)
        is_i = rows == i
        is_p = rows == p
        # (R, C)-shaped row masks come from the 3-D iota: Mosaic cannot
        # reshape a bool vector, so is_i[:, :, None] does not lower.
        is_i3 = row3 == i
        is_p3 = row3 == p[:, :, None]
        Ai = jnp.sum(jnp.where(is_i3, A, 0.0), axis=1)  # (BB, C)
        Ap = jnp.sum(jnp.where(is_p3, A, 0.0), axis=1)
        # swap rows i <-> p (no-op when p == i: is_i wins and Ap == Ai)
        A = jnp.where(
            is_i3, Ap[:, None, :], jnp.where(is_p3, Ai[:, None, :], A)
        )
        # dtype pinned: under x64 jnp.sum would promote int32 -> int64 and
        # break the fori_loop carry contract
        pi = jnp.sum(jnp.where(is_i, perm, 0), axis=1, keepdims=True,
                     dtype=jnp.int32)
        pp = jnp.sum(jnp.where(is_p, perm, 0), axis=1, keepdims=True,
                     dtype=jnp.int32)
        perm = jnp.where(is_i, pp, jnp.where(is_p, pi, perm))
        # multipliers below the diagonal; eliminate only the trailing columns
        piv = jnp.sum(jnp.where(cols == i, Ap, 0.0), axis=1, keepdims=True)  # (BB, 1)
        colnew = jnp.sum(jnp.where(col3 == i, A, 0.0), axis=2)  # (BB, R)
        factor = jnp.where(rows > i, colnew / piv, 0.0)  # (BB, R)
        A = A - jnp.where(col3 > i, factor[:, :, None] * Ap[:, None, :], 0.0)
        # store the multipliers in place of the eliminated column entries
        A = jnp.where((col3 == i) & (row3 > i), factor[:, :, None], A)
        return A, perm

    A, perm = jax.lax.fori_loop(0, n, body, (A, rows))
    lu_out[...] = A
    perm_out[...] = perm


def _lane_block(bp):
    """Instances per substitution program: a full lane tile once the
    8-padded batch fills one, else the whole (small) batch as one
    full-extent block, so a small batch reads no padded factors."""
    return BF if bp >= BF else bp


def batched_lu_factor(A, *, interpret=False):
    """Returns the operator in ``fused_newton_iter``'s layout, built once per
    factorisation so that no Newton iteration writes anything of size n^2:
    the packed factors with INSTANCES ON LANES, ``lut[c, r, i] == lu[i, r,
    c]``, as ``(fr, fr, bq)``, so factor column ``c`` of a block of instances
    is one contiguous ``(fr, L)`` slab (rows on sublanes); and the ``(fr,
    bq)`` int32 permutation.  ``fr`` is n padded to the 8-sublane tile; the
    lane block ``L`` is 128 when the 8-padded batch ``bp`` fills a lane tile
    (``bq`` then pads it to a multiple of 128) and ``bp`` itself otherwise.
    Padded rows and padded instances carry the identity.  ``unpack_lu``
    reads it back in ``lax.linalg.lu``'s layout."""
    b, f = A.shape[0], A.shape[1]
    # Rows to the 8-sublane layout, columns to the lane dimension, identity
    # on the padded diagonal so the padded block never pivots into the real
    # rows.
    Ap = _pad_to(_pad_to(_pad_to(A, 0, BB), 1, BB), 2, BF)
    bp_, fr, fc = Ap.shape
    rr = jnp.arange(fr)[:, None]
    pad_eye = (rr == jnp.arange(fc)[None, :]) & (
        (rr >= f) | (jnp.arange(bp_)[:, None, None] >= b)
    )
    Ap = Ap + pad_eye.astype(A.dtype)
    lu, perm = pl.pallas_call(
        functools.partial(_lu_factor_kernel, n=f),
        grid=(bp_ // BB,),
        in_specs=[pl.BlockSpec((BB, fr, fc), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((BB, fr, fc), lambda i: (i, 0, 0)),
            pl.BlockSpec((BB, fr), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp_, fr, fc), A.dtype),
            jax.ShapeDtypeStruct((bp_, fr), jnp.int32),
        ],
        interpret=interpret,
    )(Ap)
    # Instances past bp up to the lane block's multiple: identity factors
    # and permutation.  Then one transpose puts instances on lanes.
    bq = _cdiv(bp_, _lane_block(bp_)) * _lane_block(bp_)
    extra = (jnp.arange(bq) >= bp_)[:, None, None]
    lu = jnp.where(extra & (rr == jnp.arange(fr)[None, :]), 1.0,
                   _pad_to(lu[:, :, :fr], 0, bq))
    perm = jnp.where(extra[:, :, 0], jnp.arange(fr, dtype=jnp.int32)[None, :],
                     _pad_to(perm, 0, bq))
    return jnp.transpose(lu, (2, 1, 0)), perm.T


def unpack_lu(lut, perm, b, f):
    """``batched_lu_factor``'s operator as ``lax.linalg.lu`` lays it out:
    the ``(b, f, f)`` packed factors and the ``(b, f)`` permutation."""
    return jnp.transpose(lut, (2, 1, 0))[:b, :f, :f], perm.T[:b, :f]


# ----------------------------------------------------------- fused newton iter

# Bytes of one buffered block of factor columns in the substitution kernel:
# at n = 128 and a 128-instance lane block the whole factor set (8 MiB) is
# one block, so the kernel reads it once.
_FACTOR_BLOCK_BYTES = 8 << 20


def _factor_chunks(R, L, itemsize):
    """``(cc, nc)``: factor columns per grid step and the number of steps,
    the largest divisor ``cc`` of ``R`` whose ``(cc, R, L)`` block fits
    ``_FACTOR_BLOCK_BYTES``."""
    cc = max([d for d in range(1, R + 1)
              if R % d == 0 and d * R * L * itemsize <= _FACTOR_BLOCK_BYTES] or [1])
    return cc, R // cc


# Steps per loop trip where the trip count is static.  Mosaic unrolls a
# loop only fully; four steps a trip took the substitution launch at
# n = 128 from 20.2 to 16.4 us on a v5e.
_UNROLL = 4


def _column_loop(m, step, x):
    """``fori_loop(0, m, step, x)``, ``_UNROLL`` steps a trip for a static m."""
    if not isinstance(m, int):
        return jax.lax.fori_loop(0, m, step, x)

    def trip(q, x):
        for r in range(_UNROLL):
            x = step(q * _UNROLL + r, x)
        return x

    x = jax.lax.fori_loop(0, m // _UNROLL, trip, x)
    for t in range(m - m % _UNROLL, m):
        x = step(t, x)
    return x


def _newton_iter_kernel(
    lut_ref, perm_ref, k_ref, fk_ref, act_ref, scale_ref, k_out, res_out, x_ref,
    *, n, n_feat, cc, nc,
):
    """One whole chord-Newton iteration against the prefactored LU, for one
    block of L instances: residual, permutation gather, forward (unit lower)
    and backward (upper) substitution, the masked commit and the scaled-RMS
    convergence norm, with the elimination already paid for.

    INSTANCES ARE ON THE LANES and rows on sublanes: every vector is an
    ``(R, L)`` array, factor column j of the block is the aligned ``(R, L)``
    slab ``lut_ref[j]``, and x[j] for all L instances is row j of the VMEM
    scratch ``x_ref`` (a dynamic sublane load), so a column step is a few
    independent vreg ops on R/8 vregs with no cross-lane reduction.  Each
    element of x sees the operations of a plain column-oriented
    substitution, in its order; only the norm's sum runs along sublanes.

    The grid is (instance block, phase, column chunk): phase 0 runs the
    forward substitution over the chunks in order, phase 1 the backward one
    in reverse, with x carried in ``x_ref``.  When the factors of a block
    fit one chunk (``nc == 1``) both phases read the same block and it is
    fetched once.  Only the true n columns are visited: padded columns are
    identity and padded residual entries 0.
    """
    p = pl.program_id(1)
    c = pl.program_id(2)
    R, L = x_ref.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (R, L), 0)
    if nc == 1:  # one chunk: static trip counts, so the loops unroll
        j0, m = 0, n
    else:
        j0 = (c + p * (nc - 1 - 2 * c)) * cc  # first column of this chunk
        m = jnp.clip(n - j0, 0, cc)  # its true columns

    @pl.when((p == 0) & (c == 0))
    def _residual():  # x[r] = g[perm[r]], g = k - fk, one source row at a time
        perm = perm_ref[...]

        def gather(s, x):
            g_s = k_ref[pl.ds(s, 1), :] - fk_ref[pl.ds(s, 1), :]
            return jnp.where(perm == s, g_s, x)

        x_ref[...] = _column_loop(n, gather, jnp.zeros((R, L), x_ref.dtype))

    @pl.when(p == 0)
    def _forward():
        def fwd(t, x):  # unit lower: x[i > j] -= L[i, j] * x[j]
            j = j0 + t
            x = jnp.where(rows > j, x - lut_ref[t] * x_ref[pl.ds(j, 1), :], x)
            x_ref[...] = x
            return x

        _column_loop(m, fwd, x_ref[...])

    @pl.when(p == 1)
    def _backward():
        def bwd(t, x):  # upper: x[j] /= U[j, j]; then x[i < j] -= U[i, j] * x[j]
            jl = m - 1 - t
            j = j0 + jl
            xj = x_ref[pl.ds(j, 1), :] / lut_ref[jl, pl.ds(j, 1), :]
            x = jnp.where(rows == j, xj, jnp.where(rows < j, x - lut_ref[jl] * xj, x))
            x_ref[...] = x
            return x

        _column_loop(m, bwd, x_ref[...])

    @pl.when((p == 1) & (c == nc - 1))
    def _commit():
        delta = x_ref[...]
        k = k_ref[...]
        k_out[...] = jnp.where(act_ref[...] != 0, k - delta, k)
        r = delta / scale_ref[...]
        res_out[...] = jnp.sqrt(jnp.sum(r * r, axis=0, keepdims=True) / n_feat)


def fused_newton_iter(lut, perm, k, fk, active, scale, *, interpret=False):
    """``lut, perm`` is ``batched_lu_factor``'s operator, used as laid out.
    The ``(b, f)`` vectors move to the kernel's ``(fr, bq)`` layout
    (instances on lanes) and back: small XLA transposes, nothing of the
    factors' size.  Padded entries are 0 (scale 1, active False), so padded
    cells add 0 to the sum of squares, which is divided by the TRUE f.

    VMEM: two buffers of one ``(cc, fr, L)`` factor block (at most
    ``_FACTOR_BLOCK_BYTES`` each) plus 8 MiB for the vectors and the
    compiler's own scratch: 24 MiB at f = 128 with 128-instance blocks."""
    b, f = k.shape
    _, R, bq = lut.shape
    L = _lane_block(bq)
    cc, nc = _factor_chunks(R, L, lut.dtype.itemsize)
    scale = jnp.broadcast_to(jnp.asarray(scale, k.dtype), (b, f))

    def lanes(v, value=0):  # (b, f) -> (R, bq)
        return jnp.pad(v.T, ((0, R - f), (0, bq - b)), constant_values=value)

    vec = pl.BlockSpec((R, L), lambda i, p, c: (0, i))
    row = pl.BlockSpec((1, L), lambda i, p, c: (0, i))
    k_new, res = pl.pallas_call(
        functools.partial(_newton_iter_kernel, n=f, n_feat=float(f), cc=cc, nc=nc),
        grid=(bq // L, 2, nc),
        in_specs=[
            pl.BlockSpec((cc, R, L), lambda i, p, c: (c + p * (nc - 1 - 2 * c), 0, i)),
            vec, vec, vec, row, vec,
        ],
        out_specs=[vec, row],
        out_shape=[
            jax.ShapeDtypeStruct((R, bq), k.dtype),
            jax.ShapeDtypeStruct((1, bq), k.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((R, L), k.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * cc * R * L * lut.dtype.itemsize + (8 << 20),
        ),
        interpret=interpret,
    )(lut, perm, lanes(k), lanes(fk),
      jnp.pad(active.astype(jnp.int32)[None, :], ((0, 0), (0, bq - b))),
      lanes(scale, value=1))
    return k_new[:f, :b].T, res[0, :b]


# -------------------------------------------------------------- fused RK step
#
# The megakernel: one kernel launch per explicit-RK step attempt.  One grid
# program owns a BB-row batch tile with the FULL feature axis resident in
# VMEM ((s + ~8) * BB * fp * 4 bytes -- comfortably inside VMEM for the
# torchode regime f <= ~256 and far beyond), so the cross-feature error-norm
# reduction, the (b,)-shaped controller decision and the (b, f) commits all
# happen in-register without a second pass or a cross-tile accumulator.


def _ctrl_decide(ratio, dt_cur, run, pi1, pi2, *, ctrl, ctrl_mode):
    """The (BB, 1) controller decision of the kernel tail.  ``ctrl_mode``
    selects between the two baked-in programs: ``"pid"`` mirrors
    ``ref.pid_update`` exactly; ``"fixed"`` is the ``FixedController``
    contract -- accept everything running, keep the standing dt proposal,
    pass the error history through.  Note ``new_inv``/``new_inv2`` use the
    UNMASKED accept (the controller's decision), matching the unfused order
    of operations; only the returned ``accept`` carries the ``run`` mask."""
    if ctrl_mode == "fixed":
        return jnp.ones_like(run) & run, dt_cur, pi1, pi2
    b1, b2, b3, safety, factor_min, factor_max, dt_min, dt_max = ctrl
    finite = jnp.isfinite(ratio)
    safe_ratio = jnp.where(finite & (ratio > 0.0), ratio, 1.0)
    inv = 1.0 / safe_ratio
    factor = safety * inv**b1 * pi1**b2 * pi2**b3
    factor = jnp.where(ratio == 0.0, factor_max, factor)
    factor = jnp.where(finite, factor, 0.5)
    factor = jnp.clip(factor, factor_min, factor_max)
    accept = finite & (ratio <= 1.0)
    factor = jnp.where(accept, factor, jnp.minimum(factor, 1.0))
    mag = jnp.clip(jnp.abs(dt_cur) * factor.astype(dt_cur.dtype), dt_min, dt_max)
    dt_next = jnp.sign(dt_cur) * mag
    new_inv = jnp.where(accept, inv, pi1)
    new_inv2 = jnp.where(accept, pi1, pi2)
    return accept & run, dt_next, new_inv, new_inv2


def _ctrl_commit(
    y, y1, err, f0, f1, t, t_new, dt_cur, run, pi1, pi2, atol, rtol, sdt,
    *, ctrl, ctrl_mode, n_feat, failed=None,
):
    """Shared kernel tail: WRMS norm -> controller decision -> masked commit
    -> Hermite coefficients, on one (BB, fp) tile.  Mirrors the ref-oracle
    expressions exactly.  ``failed`` (solver-failure column, implicit steps)
    forces the ratio to inf BEFORE the decision -- so the pid program rejects
    and shrinks dt -- and masks accept afterwards for the fixed program,
    matching ``ref.fused_step``'s order of operations."""
    scale = atol + rtol * jnp.maximum(jnp.abs(y), jnp.abs(y1))
    r = err / scale
    ratio = jnp.sqrt(jnp.sum(r * r, axis=1, keepdims=True) / n_feat)  # (BB, 1)
    if failed is not None:
        ratio = jnp.where(failed, jnp.inf, ratio)

    accept, dt_next, new_inv, new_inv2 = _ctrl_decide(
        ratio, dt_cur, run, pi1, pi2, ctrl=ctrl, ctrl_mode=ctrl_mode
    )
    if failed is not None:
        accept = accept & ~failed
    y_out = jnp.where(accept, y1, y)
    f_out = jnp.where(accept, f1, f0)
    t_out = jnp.where(accept, t_new, t)
    dt_out = jnp.where(run, dt_next, dt_cur)

    c1 = sdt * f0
    c2 = 3.0 * (y1 - y) - sdt * (2.0 * f0 + f1)
    c3 = 2.0 * (y - y1) + sdt * (f0 + f1)
    return ratio, accept, y_out, f_out, t_out, dt_out, new_inv, new_inv2, (c1, c2, c3)


def _stage_combine(y, sdt, ks, b_sol, b_err):
    """b_sol/b_err combination over a list/ref of stage tiles (unrolled)."""
    acc_sol = jnp.zeros_like(y)
    acc_err = jnp.zeros_like(y)
    for j in range(len(b_sol)):  # unrolled: s is 1..7
        k = ks[j]
        if b_sol[j] != 0.0:
            acc_sol = acc_sol + b_sol[j] * k
        if b_err[j] != 0.0:
            acc_err = acc_err + b_err[j] * k
    return y + sdt * acc_sol, sdt * acc_err


def _poly_stages(y, sdt, f0, poly_ref, a, s):
    """The fully unrolled in-kernel stage recursion for polynomial vector
    fields.  Returns ``(ks, vf)``; ``vf`` is reused for the non-FSAL trailing
    evaluation."""

    def vf(yi):  # Horner over the (deg+1, tile) coefficient rows
        acc = jnp.broadcast_to(poly_ref[poly_ref.shape[0] - 1][None, :], yi.shape)
        for d in range(poly_ref.shape[0] - 2, -1, -1):
            acc = acc * yi + poly_ref[d][None, :]
        return acc

    ks = [f0]
    for i in range(1, s):  # fully unrolled stage recursion, zero vf launches
        acc = jnp.zeros_like(y)
        for j in range(i):
            if a[i][j] != 0.0:
                acc = acc + a[i][j] * ks[j]
        ks.append(vf(y + sdt * acc))
    return ks, vf


def _fused_step_kernel(
    y_ref, k_ref, f1_ref, t_ref, tnew_ref, dtc_ref, sdt_ref, run_ref,
    pi1_ref, pi2_ref, atol_ref, rtol_ref, fail_ref,
    y1_out, ratio_out, acc_out, yo_out, fo_out, to_out, dto_out,
    i1_out, i2_out, c1_out, c2_out, c3_out,
    *, b_sol, b_err, ctrl, ctrl_mode, n_feat,
):
    y = y_ref[...]
    sdt = sdt_ref[...]  # (BB, 1)
    y1, err = _stage_combine(y, sdt, k_ref, b_sol, b_err)

    ratio, accept, y_out, f_out, t_out, dt_out, i1, i2, (c1, c2, c3) = _ctrl_commit(
        y, y1, err, k_ref[0], f1_ref[...], t_ref[...], tnew_ref[...], dtc_ref[...],
        run_ref[...], pi1_ref[...], pi2_ref[...], atol_ref[...], rtol_ref[...], sdt,
        ctrl=ctrl, ctrl_mode=ctrl_mode, n_feat=n_feat, failed=fail_ref[...] != 0,
    )
    y1_out[...] = y1
    ratio_out[...] = ratio
    acc_out[...] = accept.astype(jnp.int32)
    yo_out[...] = y_out
    fo_out[...] = f_out
    to_out[...] = t_out
    dto_out[...] = dt_out
    i1_out[...] = i1
    i2_out[...] = i2
    c1_out[...] = c1
    c2_out[...] = c2
    c3_out[...] = c3


def _fused_step_poly_kernel(
    y_ref, f0_ref, poly_ref, t_ref, tnew_ref, dtc_ref, sdt_ref, run_ref,
    pi1_ref, pi2_ref, atol_ref, rtol_ref,
    y1_out, ratio_out, acc_out, yo_out, fo_out, to_out, dto_out,
    i1_out, i2_out, c1_out, c2_out, c3_out,
    *, a, b_sol, b_err, ctrl, ctrl_mode, fsal, n_feat,
):
    y = y_ref[...]
    sdt = sdt_ref[...]

    ks, vf = _poly_stages(y, sdt, f0_ref[...], poly_ref, a, len(b_sol))
    y1, err = _stage_combine(y, sdt, ks, b_sol, b_err)
    # Non-FSAL tableaus: the trailing evaluation f(t + dt, y1) is one more
    # in-kernel Horner pass, not a launch.
    f1 = ks[-1] if fsal else vf(y1)

    ratio, accept, y_out, f_out, t_out, dt_out, i1, i2, (c1, c2, c3) = _ctrl_commit(
        y, y1, err, ks[0], f1, t_ref[...], tnew_ref[...], dtc_ref[...],
        run_ref[...], pi1_ref[...], pi2_ref[...], atol_ref[...], rtol_ref[...], sdt,
        ctrl=ctrl, ctrl_mode=ctrl_mode, n_feat=n_feat,
    )
    y1_out[...] = y1
    ratio_out[...] = ratio
    acc_out[...] = accept.astype(jnp.int32)
    yo_out[...] = y_out
    fo_out[...] = f_out
    to_out[...] = t_out
    dto_out[...] = dt_out
    i1_out[...] = i1
    i2_out[...] = i2
    c1_out[...] = c1
    c2_out[...] = c2
    c3_out[...] = c3


# ------------------------------------------------- feature-tiled schedule
#
# When the padded feature axis exceeds one (BB, BF) tile, the single-pass
# schedule above would stage (s + ~8) full (BB, fp) rows in VMEM -- fine for
# the torchode regime, a VMEM blowup for large f.  The tiled schedule runs
# grid (nb, 2, nf): phase p=0 sweeps the feature tiles accumulating per-tile
# WRMS partial sums into the (BB, 1) ratio output (constant block index, so
# it stays VMEM-resident across the sweep), finalizing the controller
# decision on the last tile; phase p=1 re-sweeps the tiles and writes every
# (BB, BF) tile output under the decided accept mask.  Per-tile state (y1,
# err, stages) is recomputed in phase 1 rather than staged in scratch --
# cheap VPU arithmetic against O(tile) VMEM, so f is unbounded.  Tile
# outputs are written ONLY in phase 1 (the final visit of each block, the
# revisit-safe contract); the (BB, 1) column outputs are written in phase 0
# and persist because their block index never changes within a batch tile.


def _tiled_commit(
    p, k, y, y1, err, f0, f1, sdt,
    t_ref, tnew_ref, dtc_ref, run_ref, pi1_ref, pi2_ref, atol_ref, rtol_ref,
    y1_out, ratio_out, acc_out, yo_out, fo_out, to_out, dto_out,
    i1_out, i2_out, c1_out, c2_out, c3_out,
    *, ctrl, ctrl_mode, n_feat, nf_tiles, fail_ref=None,
):
    """The two-phase tail shared by the tiled megakernels: WRMS partial-sum
    accumulation + controller decision (phase 0), masked tile commits +
    Hermite coefficients (phase 1).  Same expressions as ``_ctrl_commit``,
    split across the two feature sweeps."""

    @pl.when(p == 0)
    def _reduce():
        @pl.when(k == 0)
        def _init():
            ratio_out[...] = jnp.zeros_like(ratio_out)

        scale = atol_ref[...] + rtol_ref[...] * jnp.maximum(jnp.abs(y), jnp.abs(y1))
        r = err / scale
        ratio_out[...] += jnp.sum(r * r, axis=1, keepdims=True)

        @pl.when(k == nf_tiles - 1)
        def _decide():
            ratio = jnp.sqrt(ratio_out[...] / n_feat)  # (BB, 1)
            if fail_ref is not None:  # solver-failure column (implicit steps)
                failed = fail_ref[...] != 0
                ratio = jnp.where(failed, jnp.inf, ratio)
            run = run_ref[...]
            dt_cur = dtc_ref[...]
            accept, dt_next, new_inv, new_inv2 = _ctrl_decide(
                ratio, dt_cur, run, pi1_ref[...], pi2_ref[...],
                ctrl=ctrl, ctrl_mode=ctrl_mode,
            )
            if fail_ref is not None:
                accept = accept & ~failed
            ratio_out[...] = ratio
            acc_out[...] = accept.astype(jnp.int32)
            to_out[...] = jnp.where(accept, tnew_ref[...], t_ref[...])
            dto_out[...] = jnp.where(run, dt_next, dt_cur)
            i1_out[...] = new_inv
            i2_out[...] = new_inv2

    @pl.when(p == 1)
    def _commit():
        accept = acc_out[...] != 0  # decided in phase 0, still resident
        y1_out[...] = y1
        yo_out[...] = jnp.where(accept, y1, y)
        fo_out[...] = jnp.where(accept, f1, f0)
        c1_out[...] = sdt * f0
        c2_out[...] = 3.0 * (y1 - y) - sdt * (2.0 * f0 + f1)
        c3_out[...] = 2.0 * (y - y1) + sdt * (f0 + f1)


def _fused_step_tiled_kernel(
    y_ref, k_ref, f1_ref, t_ref, tnew_ref, dtc_ref, sdt_ref, run_ref,
    pi1_ref, pi2_ref, atol_ref, rtol_ref, fail_ref,
    y1_out, ratio_out, acc_out, yo_out, fo_out, to_out, dto_out,
    i1_out, i2_out, c1_out, c2_out, c3_out,
    *, b_sol, b_err, ctrl, ctrl_mode, n_feat, nf_tiles,
):
    p = pl.program_id(1)
    k = pl.program_id(2)
    y = y_ref[...]  # (BB, BF) tile
    sdt = sdt_ref[...]
    y1, err = _stage_combine(y, sdt, k_ref, b_sol, b_err)
    _tiled_commit(
        p, k, y, y1, err, k_ref[0], f1_ref[...], sdt,
        t_ref, tnew_ref, dtc_ref, run_ref, pi1_ref, pi2_ref, atol_ref, rtol_ref,
        y1_out, ratio_out, acc_out, yo_out, fo_out, to_out, dto_out,
        i1_out, i2_out, c1_out, c2_out, c3_out,
        ctrl=ctrl, ctrl_mode=ctrl_mode, n_feat=n_feat, nf_tiles=nf_tiles,
        fail_ref=fail_ref,
    )


def _fused_step_poly_tiled_kernel(
    y_ref, f0_ref, poly_ref, t_ref, tnew_ref, dtc_ref, sdt_ref, run_ref,
    pi1_ref, pi2_ref, atol_ref, rtol_ref,
    y1_out, ratio_out, acc_out, yo_out, fo_out, to_out, dto_out,
    i1_out, i2_out, c1_out, c2_out, c3_out,
    *, a, b_sol, b_err, ctrl, ctrl_mode, fsal, n_feat, nf_tiles,
):
    p = pl.program_id(1)
    k = pl.program_id(2)
    y = y_ref[...]
    sdt = sdt_ref[...]
    # The polynomial vf is elementwise, so the whole stage recursion is
    # tile-local (recomputed per phase; see the schedule note above).
    ks, vf = _poly_stages(y, sdt, f0_ref[...], poly_ref, a, len(b_sol))
    y1, err = _stage_combine(y, sdt, ks, b_sol, b_err)
    f1 = ks[-1] if fsal else vf(y1)
    _tiled_commit(
        p, k, y, y1, err, ks[0], f1, sdt,
        t_ref, tnew_ref, dtc_ref, run_ref, pi1_ref, pi2_ref, atol_ref, rtol_ref,
        y1_out, ratio_out, acc_out, yo_out, fo_out, to_out, dto_out,
        i1_out, i2_out, c1_out, c2_out, c3_out,
        ctrl=ctrl, ctrl_mode=ctrl_mode, n_feat=n_feat, nf_tiles=nf_tiles,
    )


def _fused_tol_blocks(atol, rtol, b, f, bp, fp, dtype, *, tiled=False):
    """Tolerance blocks for the fused kernels, mirroring ``error_norm``'s
    shape contract: scalar/(b,) stream cheap (BB, 1) blocks, genuine (b, f)
    tolerances pay for full rows (feature tiles under the tiled schedule).
    Padded cells are 1 so padded err cells (always 0) contribute 0/positive
    = 0 to the norm."""
    atol, rtol = ref.broadcast_tolerances(atol, rtol, dtype)
    per_feature = atol.ndim == 2 and atol.shape[1] > 1 or rtol.ndim == 2 and rtol.shape[1] > 1
    if per_feature:
        atolp = _pad_to(_pad_to(jnp.broadcast_to(atol, (b, f)), 0, BB, value=1), 1, BF, value=1)
        rtolp = _pad_to(_pad_to(jnp.broadcast_to(rtol, (b, f)), 0, BB, value=1), 1, BF, value=1)
        spec = (
            pl.BlockSpec((BB, BF), lambda i, p, k: (i, k))
            if tiled else pl.BlockSpec((BB, fp), lambda i: (i, 0))
        )
    else:
        atolp = _pad_to(jnp.broadcast_to(atol.reshape((-1, 1)) if atol.ndim else atol, (b, 1)),
                        0, BB, value=1)
        rtolp = _pad_to(jnp.broadcast_to(rtol.reshape((-1, 1)) if rtol.ndim else rtol, (b, 1)),
                        0, BB, value=1)
        spec = (
            pl.BlockSpec((BB, 1), lambda i, p, k: (i, 0))
            if tiled else pl.BlockSpec((BB, 1), lambda i: (i, 0))
        )
    return atolp, rtolp, spec


def _fused_row_col_specs(fp, *, tiled):
    """(row, col) block specs matching the schedule's grid arity."""
    if tiled:
        return (
            pl.BlockSpec((BB, BF), lambda i, p, k: (i, k)),
            pl.BlockSpec((BB, 1), lambda i, p, k: (i, 0)),
        )
    return (
        pl.BlockSpec((BB, fp), lambda i: (i, 0)),
        pl.BlockSpec((BB, 1), lambda i: (i, 0)),
    )


def _fused_out_specs(bp, fp, dtype, *, tiled=False):
    row, col = _fused_row_col_specs(fp, tiled=tiled)
    specs = [row, col, col, row, row, col, col, col, col, row, row, row]
    shapes = [
        jax.ShapeDtypeStruct((bp, fp), dtype),  # y1
        jax.ShapeDtypeStruct((bp, 1), dtype),   # err_ratio
        jax.ShapeDtypeStruct((bp, 1), jnp.int32),  # accept
        jax.ShapeDtypeStruct((bp, fp), dtype),  # y_out
        jax.ShapeDtypeStruct((bp, fp), dtype),  # f_out
        jax.ShapeDtypeStruct((bp, 1), dtype),   # t_out
        jax.ShapeDtypeStruct((bp, 1), dtype),   # dt_out
        jax.ShapeDtypeStruct((bp, 1), dtype),   # new_inv
        jax.ShapeDtypeStruct((bp, 1), dtype),   # new_inv2
        jax.ShapeDtypeStruct((bp, fp), dtype),  # c1
        jax.ShapeDtypeStruct((bp, fp), dtype),  # c2
        jax.ShapeDtypeStruct((bp, fp), dtype),  # c3
    ]
    return specs, shapes


def _fused_returns(outs, y, b, f, want_coeffs):
    y1, ratio, accept, y_out, f_out, t_out, dt_out, i1, i2, c1, c2, c3 = outs
    coeffs = None
    if want_coeffs:
        # c0 is the (unpadded) input state itself -- no kernel output needed.
        coeffs = (y, c1[:b, :f], c2[:b, :f], c3[:b, :f])
    return (
        y1[:b, :f], ratio[:b, 0], accept[:b, 0].astype(bool),
        y_out[:b, :f], f_out[:b, :f], t_out[:b, 0], dt_out[:b, 0],
        i1[:b, 0], i2[:b, 0], coeffs,
    )


def fused_step(
    y, K, f1, t, t_new, dt_cur, safe_dt, running, prev_inv, prev2_inv,
    atol, rtol, *, b_sol, b_err, ctrl, want_coeffs, ctrl_mode="pid",
    failed=None, interpret=False,
):
    b, f = y.shape
    s = K.shape[0]
    dtype = y.dtype
    # Feature padding: y pads with 1 and K/f1 with 0, so padded err cells are
    # 0 and the norm is exact (divide by the TRUE feature count below).
    yp = _pad_to(_pad_to(y, 0, BB, value=1), 1, BF, value=1)
    Kp = _pad_to(_pad_to(K, 1, BB), 2, BF)
    f1p = _pad_to(_pad_to(f1, 0, BB), 1, BF)
    bp, fp = yp.shape
    nf = fp // BF
    tiled = nf > 1  # one tile -> the verified single-pass schedule
    atolp, rtolp, tol_spec = _fused_tol_blocks(atol, rtol, b, f, bp, fp, dtype, tiled=tiled)
    cols = [t, t_new, dt_cur, safe_dt, running, prev_inv, prev2_inv]
    colp = [_pad_to(x[:, None], 0, BB) for x in cols]
    # Solver-failure column (implicit steps); all-zeros when absent so the
    # kernel's failure masking is a numeric no-op on the explicit path.
    fail = jnp.zeros((b,), jnp.int32) if failed is None else failed.astype(jnp.int32)
    failp = _pad_to(fail[:, None], 0, BB)
    row, col = _fused_row_col_specs(fp, tiled=tiled)
    out_specs, out_shapes = _fused_out_specs(bp, fp, dtype, tiled=tiled)
    if tiled:
        grid = (bp // BB, 2, nf)
        k_spec = pl.BlockSpec((s, BB, BF), lambda i, p, k: (0, i, k))
        kernel = functools.partial(
            _fused_step_tiled_kernel, b_sol=tuple(b_sol), b_err=tuple(b_err),
            ctrl=tuple(ctrl), ctrl_mode=ctrl_mode, n_feat=float(f), nf_tiles=nf,
        )
    else:
        grid = (bp // BB,)
        k_spec = pl.BlockSpec((s, BB, fp), lambda i: (0, i, 0))
        kernel = functools.partial(
            _fused_step_kernel, b_sol=tuple(b_sol), b_err=tuple(b_err),
            ctrl=tuple(ctrl), ctrl_mode=ctrl_mode, n_feat=float(f),
        )
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            row,
            k_spec,
            row,
            col, col, col, col, col, col, col,  # t, t_new, dt_cur, sdt, run, pi1, pi2
            tol_spec, tol_spec,
            col,  # failed
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(yp, Kp, f1p, colp[0], colp[1], colp[2], colp[3], colp[4], colp[5], colp[6],
      atolp, rtolp, failp)
    return _fused_returns(outs, y, b, f, want_coeffs)


def fused_step_poly(
    y, f0, t, t_new, dt_cur, safe_dt, running, prev_inv, prev2_inv,
    atol, rtol, *, a, c, b_sol, b_err, poly, ctrl, want_coeffs, fsal=True,
    ctrl_mode="pid", interpret=False,
):
    del c  # autonomous polynomial dynamics
    b, f = y.shape
    dtype = y.dtype
    yp = _pad_to(_pad_to(y, 0, BB, value=1), 1, BF, value=1)
    f0p = _pad_to(_pad_to(f0, 0, BB), 1, BF)
    bp, fp = yp.shape
    nf = fp // BF
    tiled = nf > 1
    # Static polynomial coefficients materialize as one small (deg+1, fp)
    # input streamed to every program (scalars broadcast across features).
    poly_rows = np.stack(
        [np.broadcast_to(np.asarray(cd, dtype=dtype), (f,)) for cd in poly]
    )
    polyp = _pad_to(jnp.asarray(poly_rows), 1, BF)
    atolp, rtolp, tol_spec = _fused_tol_blocks(atol, rtol, b, f, bp, fp, dtype, tiled=tiled)
    cols = [t, t_new, dt_cur, safe_dt, running, prev_inv, prev2_inv]
    colp = [_pad_to(x[:, None], 0, BB) for x in cols]
    row, col = _fused_row_col_specs(fp, tiled=tiled)
    out_specs, out_shapes = _fused_out_specs(bp, fp, dtype, tiled=tiled)
    static = dict(
        a=tuple(tuple(r) for r in a), b_sol=tuple(b_sol), b_err=tuple(b_err),
        ctrl=tuple(ctrl), ctrl_mode=ctrl_mode, fsal=fsal, n_feat=float(f),
    )
    if tiled:
        grid = (bp // BB, 2, nf)
        poly_spec = pl.BlockSpec((len(poly), BF), lambda i, p, k: (0, k))
        kernel = functools.partial(_fused_step_poly_tiled_kernel, nf_tiles=nf, **static)
    else:
        grid = (bp // BB,)
        poly_spec = pl.BlockSpec((len(poly), fp), lambda i: (0, 0))
        kernel = functools.partial(_fused_step_poly_kernel, **static)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            row,
            row,
            poly_spec,
            col, col, col, col, col, col, col,
            tol_spec, tol_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(yp, f0p, polyp, colp[0], colp[1], colp[2], colp[3], colp[4], colp[5], colp[6],
      atolp, rtolp)
    return _fused_returns(outs, y, b, f, want_coeffs)


# ------------------------------------------------------------ fused event ops
#
# The event layer's per-step fixed cost -- E sign tests at detection, the
# terminal resolution + bookkeeping update at commit -- runs as two kernels
# so a solve with events launches O(1) extra programs per step instead of
# O(E) elementwise ops.  E is tiny (a handful of events), so the E axis
# rides whole inside each block like the (BB, 1) scalar columns elsewhere;
# bool in/outputs travel as bool in / int32 out, the ``fused_step`` accept
# convention.


def _event_detect_kernel(
    vp_ref, vn_ref, fired_ref, acc_ref, newly_out, vkeep_out, *, directions
):
    v0 = vp_ref[...]  # (BB, E)
    v1 = vn_ref[...]
    accept = acc_ref[...] != 0  # (BB, 1), broadcasts over E
    up = (v0 <= 0.0) & (v1 >= 0.0)
    down = (v0 >= 0.0) & (v1 <= 0.0)
    # Per-event direction choice unrolled over the static tuple (a materialized
    # direction vector would be a captured constant, which pallas forbids),
    # masked by lane index with logical ops only: Mosaic can neither
    # concatenate nor select between bool vectors.
    lane = jax.lax.broadcasted_iota(jnp.int32, v0.shape, 1)
    crossed = None
    for i, d in enumerate(directions):
        c = (lane == i) & (up if d > 0 else down if d < 0 else up | down)
        crossed = c if crossed is None else crossed | c
    crossed = crossed & ((v0 != 0.0) | (v1 != 0.0))
    newly = crossed & (fired_ref[...] == 0) & accept
    newly_out[...] = newly.astype(jnp.int32)
    vkeep_out[...] = jnp.where(accept, v1, v0)


def fused_event_detect(v_prev, v_new, fired, accept, *, directions, interpret=False):
    b, E = v_prev.shape
    vpp = _pad_to(v_prev, 0, BB)
    vnp_ = _pad_to(v_new, 0, BB)
    firedp = _pad_to(fired.astype(jnp.int32), 0, BB)
    accp = _pad_to(accept.astype(jnp.int32)[:, None], 0, BB)
    bp = vpp.shape[0]
    espec = pl.BlockSpec((BB, E), lambda i: (i, 0))
    cspec = pl.BlockSpec((BB, 1), lambda i: (i, 0))
    newly, v_keep = pl.pallas_call(
        functools.partial(
            _event_detect_kernel, directions=tuple(float(d) for d in directions)
        ),
        grid=(bp // BB,),
        in_specs=[espec, espec, espec, cspec],
        out_specs=[espec, espec],
        out_shape=[
            jax.ShapeDtypeStruct((bp, E), jnp.int32),
            jax.ShapeDtypeStruct((bp, E), v_prev.dtype),
        ],
        interpret=interpret,
    )(vpp, vnp_, firedp, accp)
    return newly[:b].astype(bool), v_keep[:b]


def _event_commit_kernel(
    x_ref, yev_ref, newly_ref, ynew_ref, t0_ref, dt_ref,
    fired_ref, evt_ref, evy_ref,
    fired_out, evt_out, evy_out, stop_out, tstop_out, ystop_out, nnew_out,
    *, terminal,
):
    x = x_ref[...]  # (BB, E)
    newly = newly_ref[...] != 0  # int32 in: bool columns do not lower
    t0 = t0_ref[...]  # (BB, 1)
    dt = dt_ref[...]
    yev = yev_ref[...]  # (BB, E, BF) feature tile
    # Terminal resolution: the earliest terminal crossing wins.  Unrolled
    # over the static terminal flags, same expressions as the ref op.
    x_stop = jnp.full(t0.shape, jnp.asarray(jnp.inf, x.dtype), dtype=x.dtype)
    y_stop = ynew_ref[...]  # (BB, BF)
    stop = jnp.zeros(t0.shape, dtype=bool)
    for i, term in enumerate(terminal):
        if not term:
            continue
        n_i = newly_ref[:, i:i + 1] != 0  # (BB, 1)
        stop = stop | n_i
        earlier = n_i & (x[:, i:i + 1] < x_stop)
        y_stop = jnp.where(earlier, yev[:, i, :], y_stop)
        x_stop = jnp.where(earlier, x[:, i:i + 1], x_stop)
    rec = newly & (x <= x_stop)  # (BB, E)
    # The E-column and scalar-column outputs do not depend on the feature
    # tile; rewriting them once per tile is idempotent (bisect-kernel rule).
    fired_out[...] = ((fired_ref[...] != 0) | rec).astype(jnp.int32)
    evt_out[...] = jnp.where(rec, t0 + x * dt, evt_ref[...])
    rec3 = rec.astype(jnp.int32)[:, :, None] != 0  # widen as int32, not bool
    evy_out[...] = jnp.where(rec3, yev, evy_ref[...])
    stop_out[...] = stop.astype(jnp.int32)
    tstop_out[...] = t0 + jnp.where(stop, x_stop, 0.0) * dt
    ystop_out[...] = y_stop
    nnew_out[...] = jnp.sum(rec.astype(jnp.int32), axis=1, keepdims=True)


def fused_event_commit(
    x, y_ev, newly, y_new, t0, dt, fired, ev_t, ev_y, *, terminal, interpret=False
):
    b, E = x.shape
    f = y_new.shape[1]
    xp = _pad_to(x, 0, BB)
    yevp = _pad_to(_pad_to(y_ev, 0, BB), 2, BF)
    newlyp = _pad_to(newly.astype(jnp.int32), 0, BB)
    ynewp = _pad_to(_pad_to(y_new, 0, BB), 1, BF)
    t0p = _pad_to(t0[:, None], 0, BB)
    dtp = _pad_to(dt[:, None], 0, BB)
    firedp = _pad_to(fired.astype(jnp.int32), 0, BB)
    evtp = _pad_to(ev_t, 0, BB)
    evyp = _pad_to(_pad_to(ev_y, 0, BB), 2, BF)
    bp = xp.shape[0]
    fp = ynewp.shape[1]
    espec = pl.BlockSpec((BB, E), lambda i, k: (i, 0))
    cspec = pl.BlockSpec((BB, 1), lambda i, k: (i, 0))
    rowspec = pl.BlockSpec((BB, BF), lambda i, k: (i, k))
    e3spec = pl.BlockSpec((BB, E, BF), lambda i, k: (i, 0, k))
    outs = pl.pallas_call(
        functools.partial(
            _event_commit_kernel, terminal=tuple(bool(t) for t in terminal)
        ),
        grid=(bp // BB, fp // BF),
        in_specs=[espec, e3spec, espec, rowspec, cspec, cspec, espec, espec, e3spec],
        out_specs=[espec, espec, e3spec, cspec, cspec, rowspec, cspec],
        out_shape=[
            jax.ShapeDtypeStruct((bp, E), jnp.int32),       # fired
            jax.ShapeDtypeStruct((bp, E), t0.dtype),        # ev_t
            jax.ShapeDtypeStruct((bp, E, fp), y_ev.dtype),  # ev_y
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),       # stop
            jax.ShapeDtypeStruct((bp, 1), t0.dtype),        # t_stop
            jax.ShapeDtypeStruct((bp, fp), y_new.dtype),    # y_stop
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),       # n_new
        ],
        interpret=interpret,
    )(xp, yevp, newlyp, ynewp, t0p, dtp, firedp, evtp, evyp)
    fired_n, evt_n, evy_n, stop, t_stop, y_stop, n_new = outs
    return (
        fired_n[:b].astype(bool), evt_n[:b], evy_n[:b, :, :f],
        stop[:b, 0].astype(bool), t_stop[:b, 0], y_stop[:b, :f], n_new[:b, 0],
    )


# ------------------------------------------------------------- impl namespaces


class _Impl:
    def __init__(self, interpret: bool):
        self._i = interpret

    def stage_accum(self, y, dt, K, coeffs):
        return stage_accum(y, dt, K, coeffs, interpret=self._i)

    def fused_update(self, y, K, dt, b_sol, b_err):
        return fused_update(y, K, dt, b_sol, b_err, interpret=self._i)

    def error_norm(self, err, y0, y1, atol, rtol):
        return error_norm(err, y0, y1, atol, rtol, interpret=self._i)

    def interp_eval(self, coeffs, x, mask, out):
        return interp_eval(coeffs, x, mask, out, interpret=self._i)

    def batched_lu_factor(self, A):
        return batched_lu_factor(A, interpret=self._i)

    def fused_newton_iter(self, lut, perm, k, fk, active, scale):
        return fused_newton_iter(lut, perm, k, fk, active, scale, interpret=self._i)

    def masked_bisect_refine(self, coeffs, lo, hi, v_lo, v_mid, active):
        return masked_bisect_refine(coeffs, lo, hi, v_lo, v_mid, active, interpret=self._i)

    def fused_step(self, *args, **kwargs):
        return fused_step(*args, **kwargs, interpret=self._i)

    def fused_step_poly(self, *args, **kwargs):
        return fused_step_poly(*args, **kwargs, interpret=self._i)

    def fused_event_detect(self, *args, **kwargs):
        return fused_event_detect(*args, **kwargs, interpret=self._i)

    def fused_event_commit(self, *args, **kwargs):
        return fused_event_commit(*args, **kwargs, interpret=self._i)


_INTERPRET = _Impl(True)
_COMPILED = _Impl(False)


def interpret_impl() -> _Impl:
    return _INTERPRET


def compiled_impl() -> _Impl:
    return _COMPILED
