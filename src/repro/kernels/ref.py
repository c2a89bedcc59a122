"""Pure-jnp reference oracles for the solver's fused hot-spot ops.

These are the semantics the Pallas kernels must match (tests assert allclose
against these).  They are also the execution path on CPU, where Pallas interpret
mode would be much slower than XLA:CPU fusion.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.lax import linalg as lax_linalg


def stage_accum(y, dt, K, coeffs):
    """y + dt * sum_j coeffs[j] * K[j].

    y:      (b, f)
    dt:     (b,)
    K:      (j, b, f)  -- stacked stage derivatives
    coeffs: (j,)       -- tableau row a[i, :j]
    """
    acc = jnp.tensordot(coeffs.astype(K.dtype), K, axes=1)
    return y + dt[:, None] * acc


def fused_update(y, K, dt, b_sol, b_err):
    """One fused pass producing the solution update and the embedded error.

    y1  = y + dt * (b_sol . K)
    err =     dt * (b_err . K)

    K: (s, b, f); b_sol, b_err: (s,).  Returns (y1, err), both (b, f).
    """
    y1 = y + dt[:, None] * jnp.tensordot(b_sol.astype(K.dtype), K, axes=1)
    err = dt[:, None] * jnp.tensordot(b_err.astype(K.dtype), K, axes=1)
    return y1, err


def broadcast_tolerances(atol, rtol, dtype):
    """Normalize tolerances onto column-broadcastable arrays.

    Accepted shapes -- the ONE tolerance contract shared by the error norm
    (both backends), the Newton convergence scale and the initial-step
    heuristic: scalar (batch-shared), (b,) per-instance, or full (b, f).
    Returns (atol, rtol) ready to broadcast against a (b, f) state.
    """
    atol = jnp.asarray(atol, dtype=dtype)
    rtol = jnp.asarray(rtol, dtype=dtype)
    if atol.ndim == 1:
        atol = atol[:, None]
    if rtol.ndim == 1:
        rtol = rtol[:, None]
    return atol, rtol


def error_norm(err, y0, y1, atol, rtol):
    """Weighted RMS norm, per instance.

    ||err / (atol + rtol * max(|y0|, |y1|))||_rms  over the feature axis.

    err, y0, y1: (b, f);  atol, rtol: scalar or (b,) or (b, f).
    Returns (b,).
    """
    atol, rtol = broadcast_tolerances(atol, rtol, err.dtype)
    scale = atol + rtol * jnp.maximum(jnp.abs(y0), jnp.abs(y1))
    ratio = err / scale
    return jnp.sqrt(jnp.mean(ratio * ratio, axis=-1))


def rms_norm(x, scale):
    """Scaled RMS over the feature axis: ||x / scale||_rms.

    x, scale: (b, f) (scale may broadcast).  Returns (b,).  Used by the
    automatic initial-step-size heuristic; ``error_norm`` is the in-loop
    variant with the accept/reject scale convention.
    """
    ratio = x / scale
    return jnp.sqrt(jnp.mean(ratio * ratio, axis=-1))


def hermite_coeffs(y0, y1, f0, f1, dt):
    """Cubic-Hermite dense-output coefficients in Horner form.

    p(x) = ((c3 * x + c2) * x + c1) * x + c0,  x = (t - t0)/dt in [0, 1].
    Returns (c0, c1, c2, c3), each (b, f).
    """
    hdt = dt[:, None]
    c0 = y0
    c1 = hdt * f0
    c2 = 3.0 * (y1 - y0) - hdt * (2.0 * f0 + f1)
    c3 = 2.0 * (y0 - y1) + hdt * (f0 + f1)
    return c0, c1, c2, c3


def batched_lu_factor(A):
    """Batched partial-pivoted LU factorization: factor ONCE per solver step.

    A: (b, f, f) chord matrices I - dt*gamma*J.

    Returns ``(lu, permutation)``: the packed LU factors (unit lower + upper
    triangle in one (b, f, f) array) and the (b, f) int32 row permutation.
    This is the factor-once half of the fused Newton path -- every subsequent
    ``fused_newton_iter`` launch back-substitutes against these factors
    instead of re-eliminating the same matrix.  The pair is an operator for
    the same backend's ``fused_newton_iter``: the Pallas backend returns it
    already in its substitution kernel's layout, padded, with instances on
    the last (lane) axis, ``(fr, fr, bq)`` factors and an ``(fr, bq)``
    permutation (``pallas_impl.unpack_lu`` reads that back as this one).

    ``lax.linalg.lu`` is the exact factorization ``jnp.linalg.solve``
    performs internally, so the factor + back-substitution composition
    reproduces ``jnp.linalg.solve`` bitwise on this backend.
    """
    lu, _, permutation = lax_linalg.lu(A)
    return lu, permutation


def fused_newton_iter(lu, perm, k, fk, active, scale):
    """One whole chord-Newton iteration against a prefactored LU, as ONE op:
    residual, permutation scatter, the two triangular back-substitutions,
    the masked commit and the scaled-RMS convergence norm.

    lu:     (b, f, f) packed LU factors from ``batched_lu_factor``
    perm:   (b, f) int32 row permutation from ``batched_lu_factor``
    k:      (b, f) current stage iterate
    fk:     (b, f) vf evaluation at the iterate, ``eval_fn(k)``
    active: (b,) bool -- instances still iterating
    scale:  (b, f) error scale atol + rtol*|y| (may broadcast)

    Returns ``(k_new, res_norm)``: ``k - delta`` where active (``k``
    elsewhere) and the (b,) RMS of ``delta / scale``, where the update
    ``delta = M^{-1} (k - fk)`` is solved via the LU factors.  The
    triangular-solve sequence mirrors ``lax.linalg``'s own ``lu_solve``
    lowering (permutation row-gather, unit-lower then upper solve), which is
    what ``jnp.linalg.solve`` runs after factorizing -- so ``delta`` from
    ``batched_lu_factor`` + this op is bitwise-equal to
    ``jnp.linalg.solve(M, k - fk)``.
    """
    g = k - fk
    x = jnp.take_along_axis(g[..., None], perm[..., None], axis=-2)
    x = lax_linalg.triangular_solve(lu, x, left_side=True, lower=True,
                                    unit_diagonal=True)
    x = lax_linalg.triangular_solve(lu, x, left_side=True, lower=False)
    delta = x[..., 0]
    k_new = jnp.where(active[:, None], k - delta, k)
    ratio = delta / scale
    return k_new, jnp.sqrt(jnp.mean(ratio * ratio, axis=-1))


def masked_bisect_refine(coeffs, lo, hi, v_lo, v_mid, active):
    """One masked bisection refinement on the dense-output interpolant.

    The event localizer brackets a sign change of the condition function in
    interpolant coordinates x in [0, 1].  Given the bracket, the condition
    value at its low end and at its midpoint, this op halves the bracket
    (keeping the sign change inside) and evaluates the interpolant at the NEW
    midpoint -- the caller then evaluates the condition there and iterates.

    coeffs: tuple of (b, f) Horner coefficients, low -> high degree
    lo, hi: (b,) current bracket
    v_lo:   (b,) condition value at lo
    v_mid:  (b,) condition value at (lo + hi)/2
    active: (b,) bool -- instances still refining (others keep their bracket)

    Returns ``(lo', hi', v_lo', mid', y_mid')`` with ``mid' = (lo' + hi')/2``
    and ``y_mid'`` the interpolant there (evaluated for every row; inactive
    rows' brackets are frozen).
    """
    mid = 0.5 * (lo + hi)
    # The crossing is in [lo, mid] iff the condition changes sign there
    # (v_mid == 0 counts: the event is at/before the midpoint).
    left = jnp.sign(v_lo) != jnp.sign(v_mid)
    hi_new = jnp.where(active & left, mid, hi)
    lo_new = jnp.where(active & ~left, mid, lo)
    v_lo_new = jnp.where(active & ~left, v_mid, v_lo)
    mid_new = 0.5 * (lo_new + hi_new)
    xe = mid_new[:, None]
    acc = coeffs[-1]
    for c in coeffs[-2::-1]:
        acc = acc * xe + c
    return lo_new, hi_new, v_lo_new, mid_new, acc


def pid_update(
    err_ratio, dt, prev_inv, prev2_inv,
    *, b1, b2, b3, safety, factor_min, factor_max, dt_min, dt_max,
):
    """The Soederlind digital-filter step update shared by ``PIDController``
    and the fused-step kernel.

    This is THE accept/next-dt program: ``PIDController.__call__`` delegates
    here and the fused megakernel re-implements exactly this expression
    sequence, so the fused and unfused paths decide identically (bitwise).

    err_ratio: (b,) weighted RMS error ratio of this step
    dt:        (b,) step size just attempted (signed)
    prev_inv / prev2_inv: (b,) inverse error ratios of the last two accepts
    b1/b2/b3:  Soederlind exponents (already divided by the controller order)

    Returns ``(accept, dt_next, new_inv, new_inv2)``.
    """
    dtype = dt.dtype
    # Guard: err_ratio == 0 (exact solve) -> use factor_max.
    finite = jnp.isfinite(err_ratio)
    safe_ratio = jnp.where(finite & (err_ratio > 0.0), err_ratio, 1.0)
    inv = 1.0 / safe_ratio

    factor = safety * inv**b1 * prev_inv**b2 * prev2_inv**b3
    factor = jnp.where(err_ratio == 0.0, factor_max, factor)
    # Non-finite error estimate: treat as a hard reject, halve the step.
    factor = jnp.where(finite, factor, 0.5)
    factor = jnp.clip(factor, factor_min, factor_max)

    accept = finite & (err_ratio <= 1.0)
    # On rejection never grow the step.
    factor = jnp.where(accept, factor, jnp.minimum(factor, 1.0))

    mag = jnp.clip(jnp.abs(dt) * factor.astype(dtype), dt_min, dt_max)
    dt_next = jnp.sign(dt) * mag

    # Error history advances only on accepted steps (torchode semantics).
    new_inv = jnp.where(accept, inv, prev_inv)
    new_inv2 = jnp.where(accept, prev_inv, prev2_inv)
    return accept, dt_next, new_inv, new_inv2


def poly_eval(y, coeffs):
    """Elementwise polynomial vector field: sum_d coeffs[d] * y**d (Horner).

    ``coeffs`` is a static tuple, low -> high degree; each entry is a scalar
    (feature-shared) or a length-f tuple.  The ONE evaluation program shared
    by ``PolynomialTerm.vf`` and the fused-step megakernel, so the in-kernel
    stage evaluations are bitwise-identical to the unfused vf calls.
    """
    cs = [jnp.asarray(c, y.dtype) for c in coeffs]
    acc = jnp.broadcast_to(cs[-1], y.shape)
    for c in cs[-2::-1]:
        acc = acc * y + c
    return acc


def fused_step(
    y, K, f1, t, t_new, dt_cur, safe_dt, running, prev_inv, prev2_inv,
    atol, rtol, *, b_sol, b_err, ctrl, want_coeffs, ctrl_mode="pid",
    failed=None,
):
    """One fused explicit-RK step attempt AROUND the vf calls: stage-combine,
    WRMS error norm, controller decision, masked commit of (t, y, f)
    against the ``running`` mask, and the dense-output/event interpolation
    coefficient build -- everything between the last stage evaluation and the
    loop-state rebuild, as ONE op.

    y:        (b, f) current state
    K:        (s, b, f) stacked stage derivatives; K[0] is f(t, y) (FSAL cache)
    f1:       (b, f) derivative at (t + dt, y1) (the FSAL last stage, or the
              trailing evaluation for non-FSAL tableaus)
    t:        (b,) current time;  t_new: (b,) time reached if accepted
    dt_cur:   (b,) the standing step proposal (pre-clamp, fed to the controller)
    safe_dt:  (b,) the signed step the stages actually used
    running / prev_inv / prev2_inv: (b,) loop mask + controller history
    b_sol / b_err: static tableau weight tuples
    ctrl:     static ``(b1, b2, b3, safety, factor_min, factor_max, dt_min,
              dt_max)`` from ``PIDController.filter_params`` (``()`` under
              ``ctrl_mode="fixed"``)
    want_coeffs: build the cubic-Hermite coefficients too (dense/events)
    ctrl_mode: ``"pid"`` runs the Soederlind filter; ``"fixed"`` is the
              fixed-step contract (``FixedController``): accept everything
              that is running, keep the standing dt proposal and leave the
              controller history untouched.  The error ratio is still
              computed (it is 0 for fixed-step tableaus, whose b_err is all
              zeros), matching the unfused path bitwise.

    failed: optional (b,) bool -- instances whose implicit stage solve failed
    this attempt (Newton divergence / iteration-cap exhaustion).  Failed
    instances get ``err_ratio = inf`` BEFORE the controller (so an adaptive
    controller shrinks their step) and are excluded from ``accept``
    unconditionally -- essential under ``ctrl_mode="fixed"``, whose
    always-accept contract would otherwise commit a garbage iterate.

    Returns ``(y1, err_ratio, accept, y_out, f_out, t_out, dt_out, new_inv,
    new_inv2, coeffs)`` with ``coeffs = (c0, c1, c2, c3)`` or ``None``.
    """
    y1, err = fused_update(
        y, K, safe_dt, jnp.asarray(b_sol, K.dtype), jnp.asarray(b_err, K.dtype)
    )
    err_ratio = error_norm(err, y, y1, atol, rtol)
    if failed is not None:
        err_ratio = jnp.where(failed, jnp.inf, err_ratio)
    if ctrl_mode == "fixed":
        accept = jnp.ones(dt_cur.shape, dtype=bool)
        dt_next = dt_cur
        new_inv, new_inv2 = prev_inv, prev2_inv
    else:
        b1, b2, b3, safety, factor_min, factor_max, dt_min, dt_max = ctrl
        accept, dt_next, new_inv, new_inv2 = pid_update(
            err_ratio, dt_cur, prev_inv, prev2_inv,
            b1=b1, b2=b2, b3=b3, safety=safety,
            factor_min=factor_min, factor_max=factor_max,
            dt_min=dt_min, dt_max=dt_max,
        )
    accept = accept & running
    if failed is not None:
        accept = accept & ~failed
    acc_f = accept[:, None]
    y_out = jnp.where(acc_f, y1, y)
    f_out = jnp.where(acc_f, f1, K[0])
    t_out = jnp.where(accept, t_new, t)
    dt_out = jnp.where(running, dt_next, dt_cur)
    coeffs = hermite_coeffs(y, y1, K[0], f1, safe_dt) if want_coeffs else None
    return y1, err_ratio, accept, y_out, f_out, t_out, dt_out, new_inv, new_inv2, coeffs


def fused_step_poly(
    y, f0, t, t_new, dt_cur, safe_dt, running, prev_inv, prev2_inv,
    atol, rtol, *, a, c, b_sol, b_err, poly, ctrl, want_coeffs,
    fsal=True, ctrl_mode="pid",
):
    """The full megakernel for closed-form polynomial vector fields: the
    stage evaluations fuse too, so an ENTIRE explicit-RK step attempt is one
    op with zero vf launches.

    ``a``/``c`` are the static tableau arrays (tuples), ``poly`` the static
    coefficient tuple of the elementwise polynomial vf (see ``poly_eval``).
    For FSAL tableaus f1 is the last stage; for non-FSAL ones the trailing
    evaluation f(t + dt, y1) folds in here too (the polynomial vf is
    closed-form, so it costs one more in-kernel Horner pass, not a launch) --
    it happens on every attempt, accepted or rejected, exactly like the
    unfused ``rk_step``.  Everything else as in ``fused_step``.
    """
    del c  # autonomous polynomial dynamics: stage times never enter
    s = len(b_sol)
    ks = [f0]
    for i in range(1, s):
        yi = stage_accum(y, safe_dt, jnp.stack(ks), jnp.asarray(a[i][:i], y.dtype))
        ks.append(poly_eval(yi, poly))
    K = jnp.stack(ks)
    if fsal:
        f1 = K[-1]
    else:
        y1, _ = fused_update(
            y, K, safe_dt, jnp.asarray(b_sol, K.dtype), jnp.asarray(b_err, K.dtype)
        )
        f1 = poly_eval(y1, poly)
    return fused_step(
        y, K, f1, t, t_new, dt_cur, safe_dt, running, prev_inv, prev2_inv,
        atol, rtol, b_sol=b_sol, b_err=b_err, ctrl=ctrl,
        want_coeffs=want_coeffs, ctrl_mode=ctrl_mode,
    )


def fused_event_detect(v_prev, v_new, fired, accept, *, directions):
    """Fused per-event sign test of the event layer: scipy's zero-crossing
    detection for EVERY registered event in one op, plus the masked carry of
    the condition values (only accepted steps advance them).

    v_prev: (b, E) condition values at the current accepted state
    v_new:  (b, E) condition values at the candidate state
    fired:  (b, E) bool -- crossings already recorded (these never re-fire)
    accept: (b,) bool -- this step's accept mask (already masked by running)
    directions: static tuple of per-event crossing directions (0 / +1 / -1)

    Returns ``(newly, v_keep)``: the (b, E) "newly crossed this step" mask
    and the carried (b, E) condition values.
    """
    crossed = []
    for i, d in enumerate(directions):
        v0, v1 = v_prev[:, i], v_new[:, i]
        up = (v0 <= 0.0) & (v1 >= 0.0)
        down = (v0 >= 0.0) & (v1 <= 0.0)
        if d > 0:
            c = up
        elif d < 0:
            c = down
        else:
            c = up | down
        crossed.append(c & ((v0 != 0.0) | (v1 != 0.0)))
    newly = jnp.stack(crossed, axis=1) & ~fired & accept[:, None]
    v_keep = jnp.where(accept[:, None], v_new, v_prev)
    return newly, v_keep


def fused_event_commit(x, y_ev, newly, y_new, t0, dt, fired, ev_t, ev_y, *, terminal):
    """Fused event-record commit: terminal resolution (the earliest terminal
    crossing wins), the first-crossing bookkeeping update and the stop
    outputs of one step's event processing, as one op.

    x:      (b, E) localized crossing positions in interpolant coordinates
    y_ev:   (b, E, f) interpolated states at the crossings
    newly:  (b, E) bool -- crossings detected this step
    y_new:  (b, f) the accepted candidate state (stop fallback)
    t0, dt: (b,) step start times / signed step sizes
    fired / ev_t / ev_y: the recorded-crossing bookkeeping being advanced
    terminal: static tuple of per-event terminal flags

    Returns ``(fired', ev_t', ev_y', stop, t_stop, y_stop, n_new)``.
    """
    b = x.shape[0]
    inf = jnp.asarray(jnp.inf, t0.dtype)
    x_stop = jnp.full((b,), inf, dtype=t0.dtype)
    y_stop = y_new
    stop = jnp.zeros((b,), dtype=bool)
    for i, term in enumerate(terminal):
        if not term:
            continue
        stop = stop | newly[:, i]
        earlier = newly[:, i] & (x[:, i] < x_stop)
        y_stop = jnp.where(earlier[:, None], y_ev[:, i], y_stop)
        x_stop = jnp.where(earlier, x[:, i], x_stop)
    rec = newly & (x <= x_stop[:, None])

    t_ev = t0[:, None] + x * dt[:, None]
    return (
        fired | rec,
        jnp.where(rec, t_ev, ev_t),
        jnp.where(rec[:, :, None], y_ev, ev_y),
        stop,
        t0 + jnp.where(stop, x_stop, 0.0) * dt,
        y_stop,
        rec.sum(axis=1).astype(jnp.int32),
    )


def interp_eval(coeffs, x, mask, out):
    """Masked Horner evaluation of the dense-output polynomial.

    coeffs: tuple of (b, f) arrays, low -> high degree
    x:      (b, n) normalized evaluation positions
    mask:   (b, n) bool -- which (instance, point) cells to write this step
    out:    (b, n, f) existing output buffer

    Returns updated (b, n, f) buffer: where mask, p(x); elsewhere out.
    """
    xe = x[:, :, None]
    acc = jnp.broadcast_to(coeffs[-1][:, None, :], xe.shape[:2] + coeffs[-1].shape[-1:])
    for c in coeffs[-2::-1]:
        acc = acc * xe + c[:, None, :]
    return jnp.where(mask[:, :, None], acc, out)
