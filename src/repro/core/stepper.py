"""Runge-Kutta stepping: the swappable "step method" component hierarchy.

``AbstractStepper`` is the protocol every step method implements -- construct
(``init``/``init_carry``), advance (``step``), interpolate (``interp_coeffs``),
propose a first step (``initial_step_size``) and contribute to the statistics
registry (``init_stats``/``update_stats``).  Two implementations:

``ExplicitRK``
    The tableau + FSAL explicit path (``Stepper`` is kept as a compatibility
    alias).  One ``step`` computes all stage derivatives, the solution update
    and the embedded error estimate through the fused kernels in
    ``repro.kernels.ops``.
``DiagonallyImplicitRK``
    SDIRK/ESDIRK methods for stiff problems (implicit_euler, trbdf2,
    kvaerno3, kvaerno5).  Each implicit stage equation is solved by the
    batched masked-Newton layer in ``core/newton.py`` -- per-instance
    convergence masks, Jacobians from ``ODETerm.vf_jac`` (autodiff default,
    user-overridable) and chord-style Jacobian reuse across stages AND steps
    with a per-instance refresh mask carried in the loop state.

The module-level ``rk_step`` / ``initial_step_size`` functions remain the
underlying primitives; steppers are the objects the drivers compose with a
term and a controller (``AutoDiffAdjoint(ExplicitRK("tsit5"),
pid_controller())`` or ``AutoDiffAdjoint("kvaerno5")``).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from .newton import NewtonConfig, newton_solve
from .static import freeze, frozen_setattr, register_static, value_eq
from .tableau import ButcherTableau, get_tableau
from .terms import ODETerm


class StepResult(NamedTuple):
    y1: jax.Array  # (b, f) candidate next state
    err: jax.Array  # (b, f) embedded error estimate (zeros for fixed-step)
    f1: jax.Array  # (b, f) f(t + dt, y1) -- exact for FSAL/SSAL tableaus
    n_f_evals: Any  # dynamics evaluations in this step (int or () int32)
    carry: Any = ()  # stepper-private cross-step state proposal (e.g. Jacobian)
    solver_failed: jax.Array | None = None  # (b,) bool: nonlinear solve failed
    stats_aux: dict | None = None  # extra per-step stats (n_newton_iters, ...)


def _tableau_arrays(tab: ButcherTableau, dtype):
    """Tableau coefficients as host-side numpy (a, c, b_sol, b_err): they are
    compile-time constants, which lets the Pallas kernels unroll them into the
    VPU instruction stream (no coefficient loads at runtime).  Fixed-step
    tableaus (b_err is None) get zero error weights."""
    a = np.asarray(tab.a, dtype=dtype)
    c = np.asarray(tab.c, dtype=dtype)
    b_sol = np.asarray(tab.b_sol, dtype=dtype)
    b_err = (
        np.asarray(tab.b_err, dtype=dtype)
        if tab.b_err is not None
        else np.zeros((tab.stages,), dtype=dtype)
    )
    return a, c, b_sol, b_err


def rk_step(
    term: ODETerm,
    tab: ButcherTableau,
    t: jax.Array,  # (b,)
    dt: jax.Array,  # (b,)
    y: jax.Array,  # (b, f)
    f0: jax.Array,  # (b, f) derivative at (t, y); FSAL cache
    args: Any,
) -> StepResult:
    s = tab.stages
    a, c, b_sol, b_err = _tableau_arrays(tab, y.dtype)

    ks = [f0]  # stage 0 is always f(t, y) == the FSAL cache
    n_evals = 0
    for i in range(1, s):
        K = jnp.stack(ks)
        yi = ops.stage_accum(y, dt, K, a[i, :i])
        ti = t + c[i] * dt
        ks.append(term.vf(ti, yi, args))
        n_evals += 1

    K = jnp.stack(ks)
    y1, err = ops.fused_update(y, K, dt, b_sol, b_err)

    if tab.fsal:
        f1 = ks[-1]
    else:
        f1 = term.vf(t + dt, y1, args)
        n_evals += 1
    return StepResult(y1=y1, err=err, f1=f1, n_f_evals=n_evals)


def initial_step_size(
    term: ODETerm,
    t0: jax.Array,  # (b,)
    y0: jax.Array,  # (b, f)
    f0: jax.Array,  # (b, f)
    direction: jax.Array,  # (b,) +-1
    order: int,
    atol,
    rtol,
    args: Any = None,
    *,
    dt_min: float = 0.0,
    dt_max: float = float("inf"),
) -> jax.Array:
    """Hairer/Noersett/Wanner automatic initial step selection, vectorized.

    The proposal magnitude is clamped to ``[dt_min, dt_max]`` so an over-eager
    first step can never exceed the controller's step bounds (on smooth
    problems the heuristic happily proposes steps 100x larger than ``h0``).
    """
    atol, rtol = ops.broadcast_tolerances(atol, rtol, y0.dtype)
    scale = atol + jnp.abs(y0) * rtol

    def rms(x):
        return ops.rms_norm(x, scale)

    d0 = rms(y0)
    d1 = rms(f0)
    h0 = jnp.where((d0 < 1e-5) | (d1 < 1e-5), 1e-6, 0.01 * d0 / jnp.maximum(d1, 1e-30))

    y1 = y0 + (h0 * direction)[:, None] * f0
    f1 = term.vf(t0 + h0 * direction, y1, args)
    d2 = rms(f1 - f0) / jnp.maximum(h0, 1e-30)

    dmax = jnp.maximum(d1, d2)
    h1 = jnp.where(
        dmax <= 1e-15,
        jnp.maximum(1e-6, h0 * 1e-3),
        (0.01 / jnp.maximum(dmax, 1e-30)) ** (1.0 / order),
    )
    h = jnp.clip(jnp.minimum(100.0 * h0, h1), dt_min, dt_max)
    return h * direction


class AbstractStepper:
    """The step-method protocol the drivers and ``StepFunction`` compose.

    A stepper owns a tableau, is stateless across *construction* (all
    cross-step state lives in the loop-carried ``carry`` it proposes), and
    contributes named per-instance accumulators to the statistics registry.

    Steppers are *static solver config*: frozen after ``__init__`` (the
    tableau and every knob may be baked into a cached compiled program),
    hashable by value (equal configs key to the same executable) and --
    for the concrete subclasses below -- pytree-registered with zero leaves
    so they cross ``jax.jit``/``vmap``/``shard_map`` boundaries as ordinary
    arguments.  Subclasses must call ``freeze(self)`` at the end of their
    ``__init__``.
    """

    tableau: ButcherTableau

    __setattr__ = frozen_setattr

    @staticmethod
    def coerce(value: "AbstractStepper | str | ButcherTableau | None") -> "AbstractStepper":
        """Normalize the stepper argument accepted by drivers/StepFunction:
        explicit tableaus get an ``ExplicitRK``, implicit ones a
        ``DiagonallyImplicitRK``."""
        if value is None:
            return ExplicitRK()
        if isinstance(value, AbstractStepper):
            return value
        tab = get_tableau(value) if isinstance(value, str) else value
        return DiagonallyImplicitRK(tab) if tab.implicit else ExplicitRK(tab)

    @property
    def order(self) -> int:
        return self.tableau.order

    @property
    def error_order(self) -> int:
        return self.tableau.error_order

    @property
    def is_adaptive(self) -> bool:
        return self.tableau.b_err is not None

    def init(self, term: ODETerm, t0: jax.Array, y0: jax.Array, args: Any) -> jax.Array:
        """Seed the derivative cache: f(t0, y0) (the FSAL seed)."""
        return term.vf(t0, y0, args)

    def init_carry(self, term: ODETerm, t0, y0, f0, args) -> Any:
        """Build the stepper's cross-step carry (lives in ``LoopState``).
        Explicit methods carry nothing; implicit ones carry the Jacobian and
        its per-instance refresh mask."""
        return ()

    def step(
        self,
        term: ODETerm,
        t: jax.Array,
        dt: jax.Array,
        y: jax.Array,
        f0: jax.Array,
        args: Any,
        carry: Any = (),
        scale: jax.Array | None = None,
    ) -> StepResult:
        raise NotImplementedError

    def commit_carry(self, old: Any, new: Any, accept: jax.Array, running: jax.Array) -> Any:
        """Merge the step's proposed carry into the loop state.  Default:
        advance the carry for running instances, freeze it for finished ones
        (the carry is valid for accepted AND rejected attempts -- e.g. a
        Jacobian evaluated at (t, y) stays correct when the step is retried
        with a smaller dt)."""

        def mask(n, o):
            if n.ndim == 0:  # batch-shared scalar leaves advance as proposed
                return n
            r = running.reshape(running.shape + (1,) * (n.ndim - 1))
            return jnp.where(r, n, o)

        return jax.tree_util.tree_map(mask, new, old)

    def interp_coeffs(self, y0, y1, f0, f1, dt):
        """Dense-output interpolant coefficients (cubic Hermite, Horner form)."""
        return ops.hermite_coeffs(y0, y1, f0, f1, dt)

    def initial_step_size(
        self,
        term: ODETerm,
        t0,
        y0,
        f0,
        direction,
        atol,
        rtol,
        args: Any = None,
        *,
        dt_min: float = 0.0,
        dt_max: float = float("inf"),
    ) -> jax.Array:
        return initial_step_size(
            term, t0, y0, f0, direction, self.tableau.order, atol, rtol, args,
            dt_min=dt_min, dt_max=dt_max,
        )

    # --- statistics registry contribution ---
    def init_stats(self, batch: int) -> dict[str, jax.Array]:
        return {"n_f_evals": jnp.zeros((batch,), dtype=jnp.int32)}

    def update_stats(self, stats: dict, ctx) -> dict:
        return {
            **stats,
            "n_f_evals": stats["n_f_evals"] + ctx.step_active * ctx.n_f_evals,
        }

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.tableau.name!r})"


@register_static
@value_eq
class ExplicitRK(AbstractStepper):
    """Tableau + explicit RK step + interpolant; stateless across steps.

    Construct from a method name or an explicit tableau::

        ExplicitRK("tsit5")
        ExplicitRK(my_tableau)

    Contributes ``n_f_evals`` to the solver's statistics registry (the static
    per-step evaluation count, shared across the batch because the dynamics
    run on the full batch while any instance is running -- torchode's
    "overhanging evaluations").
    """

    def __init__(self, method: str | ButcherTableau = "dopri5"):
        self.tableau = get_tableau(method) if isinstance(method, str) else method
        if self.tableau.implicit:
            raise ValueError(
                f"tableau {self.tableau.name!r} has implicit stages; "
                "use DiagonallyImplicitRK"
            )
        freeze(self)

    def step(self, term, t, dt, y, f0, args, carry=(), scale=None):
        return rk_step(term, self.tableau, t, dt, y, f0, args)

    def stage_derivatives(self, term, t, dt, y, f0, args):
        """The stacked stage slopes K (s, b, f) WITHOUT the b_sol/b_err
        combination -- the fused-step fast path hands K to the megakernel,
        which does the combine/norm/controller/commit in one launch.
        Bitwise-identical stage recursion to ``rk_step`` (same ops, same
        order).  Returns ``(K, n_f_evals)``."""
        tab = self.tableau
        a, c, _, _ = _tableau_arrays(tab, y.dtype)
        ks = [f0]
        n_evals = 0
        for i in range(1, tab.stages):
            yi = ops.stage_accum(y, dt, jnp.stack(ks), a[i, :i])
            ks.append(term.vf(t + c[i] * dt, yi, args))
            n_evals += 1
        return jnp.stack(ks), n_evals

    def trailing_derivative(self, term, t, dt, y, K, args):
        """The non-FSAL trailing evaluation f(t + dt, y1) the fused fast path
        feeds to the megakernel as ``f1``.  y1 is rebuilt through the same
        ``fused_update`` program the kernel applies internally (on the ref
        backend XLA CSEs the two), and -- like ``rk_step`` -- the evaluation
        happens on every attempt, accepted or rejected, so ``n_f_evals`` and
        the committed derivative cache stay bitwise-identical to the unfused
        path.  Returns ``(f1, n_f_evals_delta)``."""
        tab = self.tableau
        _, _, b_sol, b_err = _tableau_arrays(tab, y.dtype)
        y1, _ = ops.fused_update(y, K, dt, b_sol, b_err)
        return term.vf(t + dt, y1, args), 1


# Compatibility alias: the pre-hierarchy name of the explicit stepper.
Stepper = ExplicitRK


class DIRKCarry(NamedTuple):
    """Cross-step state of ``DiagonallyImplicitRK``: the chord Jacobian and
    the per-instance mask asking for it to be re-evaluated next step."""

    jac: jax.Array  # (b, f, f) df/dy from a previous step (possibly stale)
    refresh: jax.Array  # (b,) bool


@register_static
@value_eq
class DiagonallyImplicitRK(AbstractStepper):
    """SDIRK/ESDIRK stepper for stiff problems, batched-Newton inside.

    Every implicit stage shares the tableau's single diagonal coefficient
    ``gamma``, so one chord matrix ``M = I - dt*gamma*J`` (per instance)
    serves all stages of a step: it is LU-factored once per step attempt and
    every Newton iteration of every stage back-substitutes against the
    factors, with ``fused=`` or without.  ``J`` comes from
    ``ODETerm.vf_jac`` and is reused across stages *and* steps; an instance
    re-evaluates it only when its ``refresh`` flag is set (Newton failed or
    converged slowly), so well-behaved instances amortize one Jacobian over
    many steps.

    All inner-solver knobs live on ONE object: pass
    ``newton=NewtonConfig(tol=..., max_iters=..., divergence_rate=...,
    slow_iters=...)``.

    Statistics: ``n_f_evals`` (batched Newton evaluations, overhanging),
    ``n_newton_iters`` (per-instance inner iterations while running),
    ``n_jac_evals`` (per-instance Jacobian evaluations), ``n_newton_trips``
    (the batched Newton iterations of every attempt the instance ran: the
    linear-solve launches its lane paid for, converged or not, so it depends
    on the batch's composition) and ``n_newton_rejects`` (attempts rejected
    because the instance's own Newton solve failed).
    """

    def __init__(
        self,
        method: str | ButcherTableau = "kvaerno5",
        *,
        newton: NewtonConfig | None = None,
    ):
        self.tableau = get_tableau(method) if isinstance(method, str) else method
        if not self.tableau.implicit:
            raise ValueError(
                f"tableau {self.tableau.name!r} is explicit; use ExplicitRK"
            )
        self.gamma = self.tableau.diagonal  # validates the constant diagonal
        self.newton = newton if newton is not None else NewtonConfig()
        freeze(self)

    def init_carry(self, term, t0, y0, f0, args) -> DIRKCarry:
        b, f = y0.shape
        return DIRKCarry(
            jac=jnp.zeros((b, f, f), dtype=y0.dtype),
            refresh=jnp.ones((b,), dtype=bool),
        )

    def _stage_sweep(self, term, t, dt, y, f0, args, carry, scale):
        """The shared stage recursion of the unfused and fused DIRK paths:
        per-instance Jacobian refresh, chord-matrix build, ONE
        ``ops.batched_lu_factor`` of ``M`` per step attempt, and one masked
        Newton solve per implicit stage whose every iteration is a single
        ``ops.fused_newton_iter`` launch against the prefactored LU -- the
        O(n^3) elimination is paid once per attempt, each iteration costs
        O(n^2) substitutions.  On the ref backend the LU composition is
        exactly what ``jnp.linalg.solve`` lowers to, so the iterates are
        bitwise those of a dense solve against ``M`` in every iteration.

        Returns ``(K, carry_out, failed, n_static_evals, n_evals, stats_aux)``.
        """
        tab = self.tableau
        dtype = y.dtype
        a, c, _, _ = _tableau_arrays(tab, dtype)
        if not isinstance(carry, DIRKCarry):
            carry = self.init_carry(term, t, y, f0, args)
        if scale is None:
            # Direct-call default: the solver's default tolerances.
            scale = 1e-6 + 1e-3 * jnp.abs(y)

        # --- per-instance Jacobian refresh (skipped entirely when nobody asks) ---
        J = jax.lax.cond(
            jnp.any(carry.refresh),
            lambda: jnp.where(carry.refresh[:, None, None], term.vf_jac(t, y, args), carry.jac),
            lambda: carry.jac,
        )
        n_jac_evals = carry.refresh.astype(jnp.int32)
        eye = jnp.eye(y.shape[1], dtype=dtype)
        M = eye - (dt * self.gamma)[:, None, None] * J
        operator = ops.batched_lu_factor(M)

        ks: list[jax.Array] = []
        failed = jnp.zeros(dt.shape, dtype=bool)
        slow = jnp.zeros(dt.shape, dtype=bool)
        n_newton_iters = jnp.zeros(dt.shape, dtype=jnp.int32)
        n_evals = jnp.zeros((), dtype=jnp.int32)
        n_static_evals = 0
        slow_iters = self.newton.effective_slow_iters
        for i in range(tab.stages):
            ti = t + c[i] * dt
            y_pred = y if i == 0 else ops.stage_accum(y, dt, jnp.stack(ks), a[i, :i])
            if a[i, i] == 0.0:  # explicit stage (the E in ESDIRK)
                if i == 0:
                    ks.append(f0)
                else:
                    ks.append(term.vf(ti, y_pred, args))
                    n_static_evals += 1
            else:
                dtg = (dt * a[i, i])[:, None]

                def eval_fn(k, ti=ti, y_pred=y_pred, dtg=dtg):
                    return term.vf(ti, y_pred + dtg * k, args)

                # Convergence is measured on the stage VALUE increment
                # dt*a_ii*delta_k (state units), not the raw slope update,
                # so the test matches the atol/rtol error scale.
                stage_scale = scale / jnp.maximum(jnp.abs(dtg), jnp.finfo(dtype).tiny)
                pred = ks[-1] if ks else f0  # predictor: the previous stage slope
                res = newton_solve(
                    eval_fn, pred, scale=stage_scale,
                    operator=operator, config=self.newton,
                )
                ks.append(res.k)
                failed = failed | ~res.converged
                slow = slow | (res.n_iters >= slow_iters)
                n_newton_iters = n_newton_iters + res.n_iters
                n_evals = n_evals + res.n_evals

        stats_aux = {
            "n_newton_iters": n_newton_iters,
            "n_jac_evals": n_jac_evals,
            # One batched linear solve per Newton trip, whoever still iterates.
            "n_newton_trips": n_evals,
            "n_newton_rejects": failed.astype(jnp.int32),
        }
        carry_out = DIRKCarry(jac=J, refresh=failed | slow)
        return jnp.stack(ks), carry_out, failed, n_static_evals, n_evals, stats_aux

    def step(self, term, t, dt, y, f0, args, carry=(), scale=None):
        tab = self.tableau
        _, _, b_sol, b_err = _tableau_arrays(tab, y.dtype)
        K, carry_out, failed, n_static_evals, n_evals, stats_aux = self._stage_sweep(
            term, t, dt, y, f0, args, carry, scale
        )
        y1, err = ops.fused_update(y, K, dt, b_sol, b_err)
        if tab.stiffly_accurate and tab.c[-1] == 1.0:
            f1 = K[-1]  # the last stage derivative IS f(t + dt, y1)
        else:
            f1 = term.vf(t + dt, y1, args)
            n_static_evals += 1

        return StepResult(
            y1=y1,
            err=err,
            f1=f1,
            n_f_evals=n_evals + n_static_evals,
            carry=carry_out,
            solver_failed=failed,
            stats_aux=stats_aux,
        )

    def fused_stage_parts(self, term, t, dt, y, f0, args, carry, scale):
        """The DIRK half of the fused fast path: the stage sweep ``step``
        runs too (one ``batched_lu_factor`` per step, one
        ``fused_newton_iter`` launch per Newton iteration), plus the trailing
        derivative -- everything the ``fused_step`` megakernel needs as
        inputs.  The combine/norm/controller/commit happen in-kernel, with
        the per-instance ``solver_failed`` mask threaded through its
        ``failed=`` input so divergence still lands as a controller reject.

        Returns ``(K, f1, n_f_evals, carry, solver_failed, stats_aux)``.
        """
        tab = self.tableau
        _, _, b_sol, b_err = _tableau_arrays(tab, y.dtype)
        K, carry_out, failed, n_static_evals, n_evals, stats_aux = self._stage_sweep(
            term, t, dt, y, f0, args, carry, scale
        )
        if tab.stiffly_accurate and tab.c[-1] == 1.0:
            f1 = K[-1]
        else:
            # Rebuild y1 through the same fused_update program the megakernel
            # applies internally (XLA CSEs the two on the ref backend), then
            # one trailing vf launch -- exactly like ``step``.
            y1, _ = ops.fused_update(y, K, dt, b_sol, b_err)
            f1 = term.vf(t + dt, y1, args)
            n_static_evals += 1
        return K, f1, n_evals + n_static_evals, carry_out, failed, stats_aux

    def commit_carry(self, old, new, accept, running):
        """Advance the Jacobian for running instances.  Two refresh-flag
        refinements: a rejected step that already ran on a FRESH Jacobian
        (old.refresh was set) retries at the same (t, y), where re-evaluating
        would reproduce J bit-identically -- suppress the flag and let the dt
        shrink do the work; and finished instances drop their flag so a frozen
        instance can never keep triggering whole-batch re-evaluation."""
        wasteful = old.refresh & ~accept
        return DIRKCarry(
            jac=jnp.where(running[:, None, None], new.jac, old.jac),
            refresh=new.refresh & ~wasteful & running,
        )

    # --- statistics registry contribution ---
    def init_stats(self, batch: int) -> dict[str, jax.Array]:
        zeros = jnp.zeros((batch,), dtype=jnp.int32)
        return {
            "n_f_evals": zeros,
            "n_newton_iters": zeros,
            "n_jac_evals": zeros,
            "n_newton_trips": zeros,
            "n_newton_rejects": zeros,
        }

    def update_stats(self, stats: dict, ctx) -> dict:
        aux = ctx.aux or {}
        running = ctx.running.astype(jnp.int32)
        out = {
            **stats,
            "n_f_evals": stats["n_f_evals"] + ctx.step_active * ctx.n_f_evals,
        }
        for name in ("n_newton_iters", "n_jac_evals", "n_newton_trips", "n_newton_rejects"):
            if name in aux:
                out[name] = stats[name] + ctx.step_active * running * aux[name]
        return out
