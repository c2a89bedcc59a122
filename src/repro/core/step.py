"""The shared ``init/step/finish`` step function of the batch-parallel solver.

``StepFunction`` composes the three swappable components -- ``ODETerm``
(dynamics), a stepper (``ExplicitRK`` / ``DiagonallyImplicitRK``: tableau +
stage recursion + interpolant) and a controller -- into one adaptive solver
step for the whole batch.  Steppers may thread private cross-step state
(``LoopState.scarry``, e.g. the implicit stepper's reused Jacobian and its
per-instance refresh mask) and report per-instance nonlinear-solver failure,
which the loop turns into an ordinary controller reject by forcing that
instance's error ratio to infinity.  The drivers in
``drivers.py`` iterate it with ``lax.while_loop`` / bounded ``lax.scan``;
``make_solver`` in ``loop.py`` exposes the bare function triple for callers
that build their own loop.

Every instance in the batch carries its own time, step size, controller
history, accept/reject decision, termination status and (when events are
registered) event bookkeeping: sign changes of each event condition are
detected on accepted steps and localized by masked bisection on the step's
dense-output interpolant (``core/events.py``), and a fired terminal event
stops that instance at the interpolated event state with ``Status.EVENT``.  The body is a single
fused XLA program -- termination is an on-device reduction, so there is never
a host<->device synchronization inside the loop (the GPU-sync avoidance
torchode implements by hand in PyTorch).  Instances that finish early keep
being *evaluated* (the dynamics run on the full batch -- torchode's
"overhanging evaluations") but their state is frozen by masking, so results
are unaffected.

Statistics registry
-------------------
``LoopState.stats`` is a dict of named per-instance ``(b,)`` accumulators
instead of hard-coded counter fields.  Each component contributes entries via
an ``init_stats(batch) -> dict`` hook and advances them in
``update_stats(stats, ctx) -> dict``, where ``ctx`` is a ``StepContext``
describing the step just taken.  The stepper records ``n_f_evals``, the
controller ``n_accepted``, the step function itself ``n_steps`` and
``n_initialized``.  Implicit steppers add the Newton layer's counts from
their ``StepContext.aux``: ``n_newton_iters`` and ``n_jac_evals`` per
instance, ``n_newton_trips`` (the batched Newton iterations, one
linear-solve launch each, of every attempt the instance ran -- the launches
its lane paid for, so it depends on the batch's composition like
``n_f_evals``) and ``n_newton_rejects`` (attempts rejected because the
instance's own Newton solve failed).  User code can register additional
contributors through ``extra_stats`` to record any solver-internal metric
(paper Sec. 3's per-instance stats, generalized).
"""

from __future__ import annotations

import enum
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from .controller import (
    ControllerState,
    FixedController,
    _ControllerStats,
    integral_controller,
)
from .events import advance as advance_events
from .events import init_event_state, normalize_events
from .controller import PIDController
from .solution import Solution, Status
from .static import freeze, frozen_setattr, register_config_pytree
from .stepper import AbstractStepper, DiagonallyImplicitRK, ExplicitRK, _tableau_arrays
from .terms import ODETerm, as_term


class FusedFallbackReason(enum.IntEnum):
    """Machine-readable reason the ``fused=True`` fast path disengaged.

    Recorded per instance in ``Solution.stats["fused_fallback_reason"]``
    whenever ``fused=True`` was *requested* (ENGAGED means it actually ran),
    so callers can monitor silently-degraded configurations instead of
    diffing launch counts.  The codes are static config properties -- every
    instance in a batch carries the same value.
    """

    ENGAGED = 0
    # The stepper is not (exactly) ExplicitRK or DiagonallyImplicitRK:
    # subclasses may override the stage recursion the kernel bakes in.
    NOT_EXPLICIT_RK = 1
    # The controller is not (exactly) PIDController or FixedController:
    # the kernel bakes in those two accept/next-dt programs only, and
    # subclasses may override ``__call__``.
    UNSUPPORTED_CONTROLLER = 2
    # The stepper is a DiagonallyImplicitRK SUBCLASS: the fused implicit
    # path bakes in the exact factor-once chord-Newton stage sweep, which a
    # subclass may override.
    UNSUPPORTED_IMPLICIT = 3


class LoopState(NamedTuple):
    t: jax.Array  # (b,) current time
    dt: jax.Array  # (b,) signed step proposal for the next attempt
    y: jax.Array  # (b, f)
    f0: jax.Array  # (b, f) FSAL derivative cache at (t, y)
    scarry: Any  # stepper cross-step carry (() for explicit, Jacobian for DIRK)
    cstate: ControllerState
    running: jax.Array  # (b,) bool
    status: jax.Array  # (b,) int32
    stats: dict[str, jax.Array]  # named (b,) accumulators (statistics registry)
    ys: jax.Array  # (b, n, f) dense output buffer (or (b, 0, f) when unused)
    it: jax.Array  # () int32 global iteration counter
    estate: Any = ()  # per-instance event bookkeeping (EventState, or () without events)


class StepContext(NamedTuple):
    """What a statistics contributor may observe about the step just taken."""

    running: jax.Array  # (b,) bool: running mask *before* this step
    accept: jax.Array  # (b,) bool: accepted this step (masked by running)
    step_active: jax.Array  # () int32: 1 while any instance runs (overhanging evals)
    n_f_evals: Any  # dynamics-evaluation count of this step (int or () int32)
    n_written: jax.Array  # (b,) int32: dense-output points written this step
    err_ratio: jax.Array  # (b,) weighted RMS error ratio of this step
    aux: dict | None = None  # stepper-private extras (e.g. Newton iteration counts)
    n_events: jax.Array | None = None  # (b,) int32: events recorded this step


def _normalize_times(y0, t_eval, t_start, t_end, dtype):
    b = y0.shape[0]
    if t_eval is not None:
        t_eval = jnp.asarray(t_eval, dtype=dtype)
        if t_eval.ndim == 1:
            t_eval = jnp.broadcast_to(t_eval[None, :], (b, t_eval.shape[0]))
        if t_start is None:
            t_start = t_eval[:, 0]
        if t_end is None:
            t_end = t_eval[:, -1]
    if t_start is None or t_end is None:
        raise ValueError("need t_eval or (t_start, t_end)")
    t_start = jnp.broadcast_to(jnp.asarray(t_start, dtype=dtype), (b,))
    t_end = jnp.broadcast_to(jnp.asarray(t_end, dtype=dtype), (b,))
    return t_eval, t_start, t_end


class StepFunction:
    """One adaptive solver step for the whole batch, on flat (b, f) buffers.

    PyTree states are ravelled *before* they reach this class (see
    ``terms.ravel_state`` / the drivers); the hot loop and the Pallas kernels
    only ever see flat arrays.

    Static/dynamic split: a ``StepFunction`` is frozen after construction and
    pytree-registered so ``init``/``step``/``finish`` are pure functions of
    ``(static config, dynamic state)`` -- there is no mutable Python-object
    state in the hot path.  Flattening yields exactly two leaves, ``rtol`` and
    ``atol`` (scalars or per-instance vectors; free to vary without a
    retrace); the term, stepper, controller, event specs and layout flags ride
    in the treedef as hashable aux data, so passing a ``StepFunction`` (or a
    driver holding one) through ``jax.jit`` keys the compilation cache on the
    static config by value.
    """

    __setattr__ = frozen_setattr

    def __init__(
        self,
        term: ODETerm,
        stepper: AbstractStepper | str | None = None,
        controller=None,
        *,
        rtol=1e-3,
        atol=1e-6,
        dense: bool = True,
        dense_window: int = 0,
        events=None,
        event_bisect_iters: int = 30,
        extra_stats: tuple = (),
        fused: bool = False,
    ):
        self.term = as_term(term)
        stepper = self.stepper = AbstractStepper.coerce(stepper)
        if controller is None:
            controller = integral_controller() if stepper.is_adaptive else FixedController()
        self.controller = controller
        self.rtol = rtol
        self.atol = atol
        self.dense = dense
        self.dense_window = dense_window
        self.events = normalize_events(events)
        self.event_bisect_iters = event_bisect_iters
        self.extra_stats = tuple(extra_stats)
        self.fused = bool(fused)
        # The fused megakernel fast path engages for EVERY configuration the
        # kernel's baked-in programs cover: any explicit tableau (FSAL or
        # not, adaptive or fixed -- non-FSAL trailing evaluations fold in) OR
        # any diagonally-implicit tableau (the factor-once chord-Newton sweep
        # runs one ``fused_newton_iter`` launch per iteration and hands the
        # megakernel its ``failed`` mask), driven by exactly PIDController
        # (``ctrl_mode="pid"``) or exactly FixedController
        # (``ctrl_mode="fixed"``).  Exact-type checks, not isinstance:
        # subclasses may override ``__call__``/``step`` with programs the
        # kernel does not bake in.  Everything else falls back to the unfused
        # path transparently -- same results, one launch per op instead of
        # one per step -- and records why in ``fused_fallback_reason``.
        mode, why = None, FusedFallbackReason.ENGAGED
        implicit = False
        if type(stepper) is ExplicitRK:
            pass
        elif type(stepper) is DiagonallyImplicitRK:
            implicit = True
        elif isinstance(stepper, DiagonallyImplicitRK):
            why = FusedFallbackReason.UNSUPPORTED_IMPLICIT
        else:
            why = FusedFallbackReason.NOT_EXPLICIT_RK
        if why is FusedFallbackReason.ENGAGED:
            if type(self.controller) is PIDController:
                mode = "pid"
            elif type(self.controller) is FixedController:
                mode = "fixed"
            else:
                why = FusedFallbackReason.UNSUPPORTED_CONTROLLER
        self._fused_mode = mode if self.fused else None
        self._fused_fallback = int(why)
        self._fused_path = self._fused_mode is not None
        self._fused_implicit = implicit and self._fused_path
        self._rebuild_derived()
        freeze(self)

    def _rebuild_derived(self) -> None:
        """Build the statistics-contributor tuple (also called when a pytree
        unflatten reconstructs the instance: the tuple holds a back-reference
        to ``self``, so it cannot ride in the aux data).

        Registry order: component contributions first, loop bookkeeping last.
        Duck-typed controllers predating the registry (init/__call__ only)
        still get n_accepted recorded -- it was unconditional before and the
        Solution.stats contract promises it."""
        controller_stats = (
            self.controller if hasattr(self.controller, "init_stats") else _ControllerStats()
        )
        object.__setattr__(
            self, "stat_contributors",
            (self.stepper, controller_stats, self, *self.extra_stats),
        )

    # --- the step function's own statistics contribution ---
    def init_stats(self, batch: int) -> dict[str, jax.Array]:
        zeros = jnp.zeros((batch,), dtype=jnp.int32)
        out = {"n_steps": zeros, "n_initialized": zeros}
        if self.events:
            out["n_events"] = zeros
        if self.fused:
            # Why (or that) the requested fast path (dis)engaged -- a static
            # config property, broadcast so it lands in the per-instance
            # stats surface the serving stack already exports.
            out["fused_fallback_reason"] = jnp.full(
                (batch,), self._fused_fallback, dtype=jnp.int32
            )
        if self._fused_path:
            # Counts steps taken through the megakernel; equals n_steps while
            # the fast path is engaged (the observable proof it actually ran).
            out["n_fused_steps"] = zeros
        return out

    def update_stats(self, stats: dict, ctx: StepContext) -> dict:
        out = {
            **stats,
            "n_steps": stats["n_steps"] + ctx.step_active * ctx.running.astype(jnp.int32),
            "n_initialized": stats["n_initialized"] + ctx.n_written,
        }
        if ctx.n_events is not None:
            out["n_events"] = stats["n_events"] + ctx.n_events
        return out

    def _collect_init_stats(self, batch: int) -> dict[str, jax.Array]:
        stats: dict[str, jax.Array] = {}
        for c in self.stat_contributors:
            hook = getattr(c, "init_stats", None)
            if hook is not None:
                for name, acc in hook(batch).items():
                    if name in stats:
                        raise ValueError(f"duplicate statistic {name!r} in registry")
                    stats[name] = acc
        return stats

    def _apply_stat_updates(self, stats: dict, ctx: StepContext) -> dict:
        for c in self.stat_contributors:
            hook = getattr(c, "update_stats", None)
            if hook is not None:
                stats = hook(stats, ctx)
        return stats

    def _scale(self, y: jax.Array) -> jax.Array:
        """The (b, f) error scale atol + rtol*|y| shared by the acceptance
        test and the Newton convergence test.  Tolerances may be scalars,
        per-instance (b,) vectors or full (b, f) arrays."""
        atol, rtol = ops.broadcast_tolerances(self.atol, self.rtol, y.dtype)
        return atol + rtol * jnp.abs(y)

    def init(self, y0, t_eval=None, t_start=None, t_end=None, dt0=None, args=None):
        """Build the initial LoopState.  Returns ``(state, consts)`` where
        ``consts = (t_eval, t_start, t_end, direction)`` is loop-invariant."""
        y0 = jnp.asarray(y0)
        dtype = y0.dtype
        b, feat = y0.shape
        t_eval, t_start, t_end = _normalize_times(y0, t_eval, t_start, t_end, dtype)
        direction = jnp.sign(t_end - t_start)
        direction = jnp.where(direction == 0, jnp.ones_like(direction), direction)

        f0 = self.stepper.init(self.term, t_start, y0, args)
        if dt0 is None:
            # The proposal is clamped to the controller's step bounds so an
            # over-eager heuristic can never violate dt_min/dt_max.
            dt = self.stepper.initial_step_size(
                self.term, t_start, y0, f0, direction, self.atol, self.rtol, args,
                dt_min=getattr(self.controller, "dt_min", 0.0),
                dt_max=getattr(self.controller, "dt_max", float("inf")),
            )
            n_init_evals = 2
        else:
            dt = jnp.broadcast_to(jnp.asarray(dt0, dtype=dtype), (b,)) * direction
            n_init_evals = 1

        if self.dense and t_eval is not None:
            n = t_eval.shape[1]
            ys = jnp.zeros((b, n, feat), dtype=dtype)
            # Pre-write all evaluation points at/before t_start (usually just the
            # first one) with the initial condition.
            pre = direction[:, None] * (t_eval - t_start[:, None]) <= 0.0
            ys = jnp.where(pre[:, :, None], y0[:, None, :], ys)
            n_initialized = pre.sum(axis=1).astype(jnp.int32)
        else:
            ys = jnp.zeros((b, 0, feat), dtype=dtype)
            n_initialized = jnp.zeros((b,), dtype=jnp.int32)

        stats = self._collect_init_stats(b)
        stats["n_f_evals"] = stats["n_f_evals"] + n_init_evals
        stats["n_initialized"] = stats["n_initialized"] + n_initialized

        state = LoopState(
            t=t_start,
            dt=dt,
            y=y0,
            f0=f0,
            scarry=self.stepper.init_carry(self.term, t_start, y0, f0, args),
            cstate=self.controller.init(b, dtype),
            running=jnp.ones((b,), dtype=bool),
            status=jnp.zeros((b,), dtype=jnp.int32),
            stats=stats,
            ys=ys,
            it=jnp.zeros((), dtype=jnp.int32),
            estate=(
                init_event_state(self.events, t_start, y0, args) if self.events else ()
            ),
        )
        return state, (t_eval, t_start, t_end, direction)

    def _propose(self, state: LoopState, consts):
        """The per-instance step proposal -- the shared prologue of the fused
        and unfused paths.  Returns ``(dt_prop, cursor, t_win, W)``; the last
        three are ``(None, None, 0)`` unless windowed dense output is active
        (``t_win is not None`` is the windowed-mode flag downstream).

        Windowed dense output (beyond-torchode optimization): only a static
        window of W eval points at the per-instance cursor is touched per
        step, instead of masking over ALL n points.  The attempt is clamped
        so a step never crosses beyond the window's last point (costs extra
        steps only when the solver could cross >W points at once).  See
        EXPERIMENTS.md SSPerf (solver)."""
        t_eval, t_start, t_end, direction = consts
        if not (self.dense and t_eval is not None and self.dense_window > 0):
            return state.dt, None, None, 0
        n_pts = t_eval.shape[1]
        W = min(self.dense_window, n_pts)
        cursor = jnp.minimum(state.stats["n_initialized"], n_pts - W)  # (b,)
        t_win = jax.vmap(
            lambda te, c: jax.lax.dynamic_slice(te, (c,), (W,))
        )(t_eval, cursor)
        has_beyond = (state.stats["n_initialized"] + W) < n_pts
        lim = jnp.where(has_beyond, t_win[:, -1] - state.t, t_end - state.t)
        clamp = has_beyond & (direction * lim > 0) & (jnp.abs(lim) < jnp.abs(state.dt))
        return jnp.where(clamp, lim, state.dt), cursor, t_win, W

    def _write_dense(self, state, consts, coeffs, accept, t_stop, safe_dt, cursor, t_win, W):
        """Write every eval point passed by this step into the dense-output
        buffer (windowed or full-mask; shared by the fused and unfused
        paths).  Returns ``(ys, n_written)``."""
        t_eval, t_start, t_end, direction = consts
        ys = state.ys
        n_written = jnp.zeros_like(state.running, dtype=jnp.int32)
        if t_win is not None:
            xw = jnp.clip((t_win - state.t[:, None]) / safe_dt[:, None], 0.0, 1.0)
            after_t = direction[:, None] * (t_win - state.t[:, None]) > 0.0
            upto_new = direction[:, None] * (t_win - t_stop[:, None]) <= 0.0
            maskw = accept[:, None] & after_t & upto_new
            feat = ys.shape[-1]
            cur = jax.vmap(
                lambda row, c: jax.lax.dynamic_slice(row, (c, 0), (W, feat))
            )(ys, cursor)
            merged = ops.interp_eval(coeffs, xw, maskw, cur)
            ys = jax.vmap(
                lambda row, m, c: jax.lax.dynamic_update_slice(row, m, (c, 0))
            )(ys, merged, cursor)
            n_written = maskw.sum(axis=1).astype(jnp.int32)
        elif self.dense and t_eval is not None:
            x = (t_eval - state.t[:, None]) / safe_dt[:, None]
            x = jnp.clip(x, 0.0, 1.0)  # masked points stay finite (grad-safe)
            after_t = direction[:, None] * (t_eval - state.t[:, None]) > 0.0
            upto_new = direction[:, None] * (t_eval - t_stop[:, None]) <= 0.0
            mask = accept[:, None] & after_t & upto_new
            ys = ops.interp_eval(coeffs, x, mask, ys)
            n_written = mask.sum(axis=1).astype(jnp.int32)
        return ys, n_written

    def step(self, state: LoopState, consts, args) -> LoopState:
        if self._fused_path:
            return self._step_fused(state, consts, args)
        term, stepper, controller = self.term, self.stepper, self.controller
        k = stepper.error_order
        t_eval, t_start, t_end, direction = consts
        tiny = jnp.asarray(jnp.finfo(state.y.dtype).tiny, state.y.dtype)
        eps = jnp.asarray(jnp.finfo(state.y.dtype).eps, state.y.dtype)

        any_running = jnp.any(state.running)

        dt_prop, cursor, t_win, W = self._propose(state, consts)

        # --- clamp the attempt so the final step lands exactly on t_end ---
        rem = t_end - state.t
        will_finish = jnp.abs(dt_prop) >= jnp.abs(rem)
        dt_used = jnp.where(will_finish, rem, dt_prop)
        safe_dt = jnp.where(jnp.abs(dt_used) > tiny, dt_used, jnp.ones_like(dt_used))

        # --- one RK step for the whole batch ---
        res = stepper.step(
            term, state.t, safe_dt, state.y, state.f0, args,
            carry=state.scarry, scale=self._scale(state.y),
        )
        err_ratio = ops.error_norm(res.err, state.y, res.y1, self.atol, self.rtol)
        if res.solver_failed is not None:
            # Nonlinear-solver divergence flows through the ordinary
            # controller reject path: an infinite error ratio is a hard
            # reject that shrinks that instance's step and retries.
            err_ratio = jnp.where(res.solver_failed, jnp.inf, err_ratio)

        # --- per-instance accept/reject + next step proposal ---
        accept, dt_next, cstate_new = controller(err_ratio, state.dt, state.cstate, k)
        accept = accept & state.running
        if res.solver_failed is not None:
            # A failed nonlinear solve must never be committed, even by an
            # always-accept controller (FixedController): the iterate is
            # garbage.  Under a fixed step this retries until max_steps, a
            # visible failure instead of a silently wrong SUCCESS.
            accept = accept & ~res.solver_failed

        t_new = jnp.where(will_finish, t_end, state.t + dt_used)
        done_now = accept & will_finish

        # step-size floor: instances whose step collapses are stopped
        dt_floor = 8.0 * eps * jnp.maximum(jnp.abs(state.t), jnp.abs(t_end))
        nonfinite_y = ~jnp.all(jnp.isfinite(res.y1), axis=-1)
        stopped = state.running & ~accept & (jnp.abs(dt_next) <= dt_floor)

        # The dense-output interpolant of this step is shared by the eval-point
        # writer and the event localizer.
        dense_now = self.dense and t_eval is not None
        if dense_now or self.events:
            coeffs = stepper.interp_coeffs(state.y, res.y1, state.f0, res.f1, safe_dt)

        # --- events: detect sign changes on accepted steps, localize by
        # masked bisection on the interpolant (zero extra vf evaluations),
        # stop instances whose terminal event fired ---
        if self.events:
            adv = advance_events(
                self.events, state.estate, coeffs, state.t, safe_dt, t_new,
                res.y1, accept, args, self.event_bisect_iters,
            )
            estate, event_stop = adv.estate, adv.stop
            # Dense output and the committed state are truncated at the
            # earliest terminal event time.
            t_stop = jnp.where(event_stop, adv.t_stop, t_new)
        else:
            adv, estate = None, state.estate
            event_stop = jnp.zeros_like(accept)
            t_stop = t_new

        # --- dense output: write every eval point passed by this step ---
        ys, n_written = self._write_dense(
            state, consts, coeffs if (dense_now or self.events) else None,
            accept, t_stop, safe_dt, cursor, t_win, W,
        )

        # --- masked commit ---
        acc_f = accept[:, None]
        y = jnp.where(acc_f, res.y1, state.y)
        f0 = jnp.where(acc_f, res.f1, state.f0)
        t = jnp.where(accept, t_new, state.t)
        dt = jnp.where(state.running, dt_next, state.dt)
        if self.events:
            # An event-stopped instance rests AT the event: its committed
            # state is the interpolated (event_t, event_y), not (t_new, y1).
            y = jnp.where(event_stop[:, None], adv.y_stop, y)
            t = jnp.where(event_stop, t_stop, t)

        running = state.running & ~done_now & ~stopped & ~event_stop
        status = jnp.where(
            event_stop,
            Status.EVENT.value,
            jnp.where(
                done_now,
                Status.SUCCESS.value,
                jnp.where(
                    stopped,
                    jnp.where(nonfinite_y, Status.INFINITE.value, Status.REACHED_DT_MIN.value),
                    state.status,
                ),
            ),
        ).astype(jnp.int32)

        inc = jnp.where(any_running, 1, 0).astype(jnp.int32)
        ctx = StepContext(
            running=state.running,
            accept=accept,
            step_active=inc,
            n_f_evals=res.n_f_evals,
            n_written=n_written,
            err_ratio=err_ratio,
            aux=res.stats_aux,
            n_events=adv.n_new if adv is not None else None,
        )
        stats = self._apply_stat_updates(dict(state.stats), ctx)

        return LoopState(
            t=t,
            dt=dt,
            y=y,
            f0=f0,
            scarry=stepper.commit_carry(state.scarry, res.carry, accept, state.running),
            # Every controller returns its own next state (masking non-advances
            # internally), so the loop threads it uniformly -- no special cases.
            cstate=cstate_new,
            running=running,
            status=status,
            stats=stats,
            ys=ys,
            it=state.it + inc,
            estate=estate,
        )

    def _step_fused(self, state: LoopState, consts, args) -> LoopState:
        """The fused fast path: everything between the stage evaluations and
        the loop-state rebuild -- b_sol/b_err combination, WRMS error norm,
        PI controller accept/next-dt, masked commit of (t, y, f) against the
        ``running`` mask, and the Hermite coefficient build -- is ONE
        kernel-registry op (``ops.fused_step``).  For ``PolynomialTerm``
        dynamics the stage evaluations fuse too (``ops.fused_step_poly``):
        the whole step attempt is a single launch with zero vf dispatches.

        Mirrors ``step`` expression-for-expression (the ref-backend op is
        composed of the same primitives in the same order, so fused and
        unfused solves are bitwise-identical there); only engaged when
        ``_fused_path`` holds (``ExplicitRK`` or ``DiagonallyImplicitRK`` --
        any registered tableau -- driven by ``PIDController`` or
        ``FixedController``).  Non-FSAL tableaus fold their trailing
        evaluation in: the polynomial megakernel runs it as one more
        in-kernel Horner pass, general terms evaluate ``vf`` once between
        the stage sweep and the kernel (exactly like ``rk_step``, on every
        attempt).  Diagonally-implicit steppers run the factor-once
        chord-Newton sweep (``fused_stage_parts``) and thread the
        per-instance ``solver_failed`` mask through the kernel's ``failed=``
        input, which forces an infinite error ratio BEFORE the controller
        and excludes those instances from ``accept`` -- the same
        divergence-to-reject contract as the unfused path, kept in-kernel.
        """
        term, stepper, controller = self.term, self.stepper, self.controller
        t_eval, t_start, t_end, direction = consts
        tiny = jnp.asarray(jnp.finfo(state.y.dtype).tiny, state.y.dtype)
        eps = jnp.asarray(jnp.finfo(state.y.dtype).eps, state.y.dtype)

        any_running = jnp.any(state.running)
        dt_prop, cursor, t_win, W = self._propose(state, consts)

        rem = t_end - state.t
        will_finish = jnp.abs(dt_prop) >= jnp.abs(rem)
        dt_used = jnp.where(will_finish, rem, dt_prop)
        safe_dt = jnp.where(jnp.abs(dt_used) > tiny, dt_used, jnp.ones_like(dt_used))
        t_new = jnp.where(will_finish, t_end, state.t + dt_used)

        dense_now = self.dense and t_eval is not None
        want_coeffs = bool(dense_now or self.events)
        tab = stepper.tableau
        mode = self._fused_mode
        ctrl = controller.filter_params(stepper.error_order)
        # Fixed-step tableaus have no embedded estimate: zero error weights
        # (the in-kernel norm is then 0, exactly like the unfused path).
        _, _, b_sol_w, b_err_w = _tableau_arrays(tab, state.y.dtype)
        common = (
            state.t, t_new, state.dt, safe_dt, state.running,
            state.cstate.prev_inv_ratio, state.cstate.prev2_inv_ratio,
            self.atol, self.rtol,
        )
        poly = getattr(term, "poly_coeffs", ()) if not self._fused_implicit else ()
        scarry_new, solver_failed, stats_aux = state.scarry, None, None
        if self._fused_implicit:
            (K, f1, n_f_evals, carry_prop, solver_failed,
             stats_aux) = stepper.fused_stage_parts(
                term, state.t, safe_dt, state.y, state.f0, args,
                carry=state.scarry, scale=self._scale(state.y),
            )
            out = ops.fused_step(
                state.y, K, f1, *common,
                b_sol=b_sol_w, b_err=b_err_w, ctrl=ctrl,
                want_coeffs=want_coeffs, ctrl_mode=mode, failed=solver_failed,
            )
        elif poly:
            out = ops.fused_step_poly(
                state.y, state.f0, *common,
                a=tab.a, c=tab.c, b_sol=b_sol_w, b_err=b_err_w,
                poly=poly, ctrl=ctrl, want_coeffs=want_coeffs,
                fsal=tab.fsal, ctrl_mode=mode,
            )
            # The in-kernel stage evaluations count exactly like the unfused
            # vf calls they replace (FSAL: the first stage is the cache;
            # non-FSAL: one more for the in-kernel trailing evaluation).
            n_f_evals = tab.stages - 1 + (0 if tab.fsal else 1)
        else:
            K, n_f_evals = stepper.stage_derivatives(
                term, state.t, safe_dt, state.y, state.f0, args
            )
            if tab.fsal:
                f1 = K[-1]
            else:
                # User vector fields cannot fuse: the trailing evaluation is
                # the one launch between the stage sweep and the megakernel.
                f1, extra = stepper.trailing_derivative(
                    term, state.t, safe_dt, state.y, K, args
                )
                n_f_evals += extra
            out = ops.fused_step(
                state.y, K, f1, *common,
                b_sol=b_sol_w, b_err=b_err_w, ctrl=ctrl,
                want_coeffs=want_coeffs, ctrl_mode=mode,
            )
        (y1, err_ratio, accept, y_out, f_out, t_out, dt_out,
         new_inv, new_inv2, coeffs) = out
        cstate_new = ControllerState(new_inv, new_inv2)
        if self._fused_implicit:
            scarry_new = stepper.commit_carry(
                state.scarry, carry_prop, accept, state.running
            )

        done_now = accept & will_finish
        dt_floor = 8.0 * eps * jnp.maximum(jnp.abs(state.t), jnp.abs(t_end))
        nonfinite_y = ~jnp.all(jnp.isfinite(y1), axis=-1)
        # Where ``running`` holds, dt_out IS the controller's dt_next (the
        # kernel commits dt_next under the same mask the unfused path uses).
        stopped = state.running & ~accept & (jnp.abs(dt_out) <= dt_floor)

        if self.events:
            adv = advance_events(
                self.events, state.estate, coeffs, state.t, safe_dt, t_new,
                y1, accept, args, self.event_bisect_iters,
            )
            estate, event_stop = adv.estate, adv.stop
            t_stop = jnp.where(event_stop, adv.t_stop, t_new)
        else:
            adv, estate = None, state.estate
            event_stop = jnp.zeros_like(accept)
            t_stop = t_new

        ys, n_written = self._write_dense(
            state, consts, coeffs, accept, t_stop, safe_dt, cursor, t_win, W
        )

        # --- masked commit: already done in-kernel; events override on top ---
        y, f0, t, dt = y_out, f_out, t_out, dt_out
        if self.events:
            y = jnp.where(event_stop[:, None], adv.y_stop, y)
            t = jnp.where(event_stop, t_stop, t)

        running = state.running & ~done_now & ~stopped & ~event_stop
        status = jnp.where(
            event_stop,
            Status.EVENT.value,
            jnp.where(
                done_now,
                Status.SUCCESS.value,
                jnp.where(
                    stopped,
                    jnp.where(nonfinite_y, Status.INFINITE.value, Status.REACHED_DT_MIN.value),
                    state.status,
                ),
            ),
        ).astype(jnp.int32)

        inc = jnp.where(any_running, 1, 0).astype(jnp.int32)
        ctx = StepContext(
            running=state.running,
            accept=accept,
            step_active=inc,
            n_f_evals=n_f_evals,
            n_written=n_written,
            err_ratio=err_ratio,
            aux=stats_aux,
            n_events=adv.n_new if adv is not None else None,
        )
        stats = self._apply_stat_updates(dict(state.stats), ctx)
        stats["n_fused_steps"] = (
            stats["n_fused_steps"] + inc * state.running.astype(jnp.int32)
        )

        return LoopState(
            t=t,
            dt=dt,
            y=y,
            f0=f0,
            # Explicit steppers carry () across steps; the implicit fast path
            # commits its Jacobian carry exactly like the unfused step.
            scarry=scarry_new,
            cstate=cstate_new,
            running=running,
            status=status,
            stats=stats,
            ys=ys,
            it=state.it + inc,
            estate=estate,
        )

    def finish(self, state: LoopState, consts) -> Solution:
        t_eval, t_start, t_end, direction = consts
        status = jnp.where(
            state.running, Status.REACHED_MAX_STEPS.value, state.status
        ).astype(jnp.int32)
        stats = dict(state.stats)
        extra = {}
        if self.events:
            extra = dict(
                event_t=state.estate.t,
                event_y=state.estate.y,
                event_mask=state.estate.fired,
            )
        if self.dense and t_eval is not None:
            return Solution(ts=t_eval, ys=state.ys, status=status, stats=stats, **extra)
        # Without t_eval, report the per-instance time actually reached:
        # t_end on SUCCESS (the final step lands there exactly), the event
        # time on EVENT, and the last accepted time for early stops
        # (REACHED_DT_MIN / INFINITE / REACHED_MAX_STEPS).
        return Solution(ts=state.t, ys=state.y, status=status, stats=stats, **extra)


# Leaves: the tolerances (dynamic -- per-instance vectors vary freely between
# solves of one compiled program).  Aux: everything else, hashable by value.
register_config_pytree(StepFunction, ("rtol", "atol"), ("stat_contributors",))
