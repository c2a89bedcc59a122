"""Compatibility wrappers over the componentized solver core.

The monolithic while/scan solver that used to live here is decomposed into
``step.py`` (``StepFunction``: the shared ``init/step/finish`` triple,
``LoopState`` with the statistics registry) and ``drivers.py``
(``AutoDiffAdjoint`` / ``ScanAdjoint`` / ``BacksolveAdjoint``).  The
functions below preserve the original one-call API with unchanged signatures;
new code should compose the components directly::

    solver = AutoDiffAdjoint(Stepper("tsit5"), pid_controller())
    sol = solver.solve(f, y0, t_eval, args=args)
"""

from __future__ import annotations

import warnings
from typing import Any

from .drivers import AutoDiffAdjoint, ScanAdjoint
from .solution import Solution

# LoopState keeps its historical import path, but note its counter fields
# (n_steps/n_accepted/...) moved into the ``stats`` registry dict.
from .step import LoopState, StepFunction  # noqa: F401
from .stepper import AbstractStepper
from .terms import as_term


def make_solver(
    f,
    *,
    method: str = "dopri5",
    rtol=1e-3,
    atol=1e-6,
    controller=None,
    max_steps: int = 10_000,
    batched_term: bool = True,
    dense: bool = True,
    dense_window: int = 0,
    events=None,
    event_bisect_iters: int = 30,
    fused: bool = False,
):
    """Build (init_fn, body_fn, finish_fn) shared by the while_loop and scan
    drivers.  Compatibility shim over ``StepFunction``.

    ``max_steps`` is accepted for signature stability only: ``make_solver``
    hands back the bare function triple and the *caller* owns the loop, so the
    caller's loop bound is the only one that exists (compare
    ``AutoDiffAdjoint(..., max_steps=...)``, where the driver owns the loop).
    A non-default value would be silently ignored -- warn instead.
    """
    if max_steps != 10_000:
        warnings.warn(
            "make_solver ignores max_steps: it returns (init, step, finish) and "
            "the iteration bound belongs to the caller's loop. Bound your own "
            "while_loop/scan, or use solve_ivp / AutoDiffAdjoint(max_steps=...) "
            "which own their loop.",
            UserWarning,
            stacklevel=2,
        )
    del max_steps
    step_fn = StepFunction(
        as_term(f, batched=batched_term),
        AbstractStepper.coerce(method),
        controller,
        rtol=rtol,
        atol=atol,
        dense=dense,
        dense_window=dense_window,
        events=events,
        event_bisect_iters=event_bisect_iters,
        fused=fused,
    )
    return step_fn.init, step_fn.step, step_fn.finish


def solve_ivp(
    f,
    y0,
    t_eval=None,
    *,
    t_start=None,
    t_end=None,
    method: str = "dopri5",
    rtol=1e-3,
    atol=1e-6,
    controller=None,
    dt0=None,
    max_steps: int = 10_000,
    args: Any = None,
    batched_term: bool = True,
    dense: bool = True,
    dense_window: int = 0,
    events=None,
    event_bisect_iters: int = 30,
    fused: bool = False,
) -> Solution:
    """Solve a batch of IVPs in parallel with independent per-instance state.

    y0:     (batch, features) initial conditions, or any PyTree whose leaves
            carry the batch as their leading axis (ravelled at the term
            boundary; the vector field then receives per-instance PyTrees)
    t_eval: (n,) shared or (batch, n) per-instance evaluation points, or None to
            track only the final state (fastest; the CNF case in the paper)
    t_start/t_end: scalars or (batch,) vectors; default to t_eval boundaries.
            Integration ranges may differ per instance, including direction.
    method: a tableau name -- explicit ("dopri5", "tsit5", ...) or implicit
            ("kvaerno5", "kvaerno3", "trbdf2", "implicit_euler") for stiff
            problems; implicit names route through ``DiagonallyImplicitRK``.
    rtol/atol: scalars shared by the batch, or per-instance (b,) vectors --
            each instance is then held to its own tolerance by the error norm
            and the step-size controller (torchode's per-instance tolerances).
    events: an ``Event`` (or sequence of them) with per-instance scalar
            conditions ``cond_fn(t, y, args)``; terminal events stop each
            instance independently at its localized crossing time
            (``Status.EVENT``), and the Solution carries per-instance
            ``event_t`` / ``event_y`` / ``event_mask``.
    fused:  opt into the fused step megakernel fast path (one kernel-registry
            op per step attempt around the vf calls, zero vf launches for
            ``polynomial_term`` dynamics).  Engages for every explicit
            tableau (FSAL or not, adaptive or fixed-step) and for
            ``DiagonallyImplicitRK`` (whose factor-once Newton sweep is the
            same either way: ``fused`` only swaps in the megakernel tail)
            under PID-family or fixed controllers, falling back transparently
            otherwise; ``stats["n_fused_steps"]`` reports whether it ran and
            ``stats["fused_fallback_reason"]`` (a ``FusedFallbackReason``
            value) reports why it did not.

    Returns a ``Solution`` with per-instance status and statistics.
    """
    driver = AutoDiffAdjoint(
        AbstractStepper.coerce(method),
        controller,
        rtol=rtol,
        atol=atol,
        max_steps=max_steps,
        dense=dense,
        dense_window=dense_window,
        batched_term=batched_term,
        events=events,
        event_bisect_iters=event_bisect_iters,
        fused=fused,
    )
    return driver.solve(f, y0, t_eval, t_start=t_start, t_end=t_end, dt0=dt0, args=args)


def solve_ivp_scan(
    f,
    y0,
    t_eval=None,
    *,
    t_start=None,
    t_end=None,
    method: str = "dopri5",
    rtol=1e-3,
    atol=1e-6,
    controller=None,
    dt0=None,
    max_steps: int = 256,
    args: Any = None,
    batched_term: bool = True,
    dense: bool = True,
    dense_window: int = 0,
    checkpoint_every: int = 0,
    events=None,
    event_bisect_iters: int = 30,
    fused: bool = False,
) -> Solution:
    """Reverse-mode-differentiable variant: a bounded ``lax.scan`` over
    ``max_steps`` iterations with masked no-op steps after termination
    (discretize-then-optimize).  ``checkpoint_every`` > 0 wraps blocks of steps
    in ``jax.checkpoint`` to trade recompute for memory on long solves.
    """
    driver = ScanAdjoint(
        AbstractStepper.coerce(method),
        controller,
        rtol=rtol,
        atol=atol,
        max_steps=max_steps,
        dense=dense,
        dense_window=dense_window,
        batched_term=batched_term,
        checkpoint_every=checkpoint_every,
        events=events,
        event_bisect_iters=event_bisect_iters,
        fused=fused,
    )
    return driver.solve(f, y0, t_eval, t_start=t_start, t_end=t_end, dt0=dt0, args=args)
