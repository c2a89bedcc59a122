"""Batched masked Newton/chord iteration for the implicit stage equations.

This is the paper's per-instance principle pushed down into the *inner*
nonlinear solve: every ODE instance in the batch iterates its own Newton
sequence and terminates independently through a convergence mask, exactly the
way the outer loop freezes finished instances.  One global ``while_loop``
iteration performs one batched vector-field evaluation and one batched
substitution against a prefactored LU -- instances that already converged (or
failed) stop updating but keep riding along (the inner-loop analogue of
torchode's "overhanging evaluations"), so there is never a host sync or a
per-instance Python loop.

The iteration is a *chord* Newton: the matrix ``M = I - dt*gamma*J`` is built
once per solver step from a (possibly stale, per-instance refreshed) Jacobian
and reused across all stages and iterations.  ``M`` is factored once
(``ops.batched_lu_factor``, partial-pivoted LU) and every iteration runs ONE
fused op, ``ops.fused_newton_iter``: residual, permutation scatter, the two
triangular back-substitutions against the factors, masked commit, and the
scaled-RMS norm in a single launch.  ``DiagonallyImplicitRK`` factors once per
step attempt and shares the factors across its stages.

On the ref backend the LU composition reproduces ``jnp.linalg.solve``
bitwise (it is the same ``lax.linalg.lu`` + triangular-solve sequence
``solve`` lowers to), so each iterate is that of a dense solve against ``M``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from .static import register_static


@register_static
@dataclasses.dataclass(frozen=True)
class NewtonConfig:
    """The inner nonlinear solver's knobs as one hashable static-config
    object: ``DiagonallyImplicitRK`` carries a ``NewtonConfig``, so the knobs
    participate in the stepper's value hash (equal configs -> the same
    compiled program) and cross ``jax.jit`` boundaries as compile-time
    constants, which is what lets the iteration caps unroll into the traced
    ``while_loop`` bound.

    tol
        Convergence threshold for the scaled RMS of the Newton update,
        measured in the step's atol/rtol error units.
    max_iters
        Per-stage iteration cap; exhausting it marks the instance failed.
    divergence_rate
        Growth factor of the update norm between iterations that counts as
        divergence.
    slow_iters
        Iteration count at or above which a *converged* instance is still
        considered slow, scheduling a Jacobian refresh for its next step.
        ``None`` (the default) derives ``max(2, max_iters // 2)``.
    """

    tol: float = 1e-2
    max_iters: int = 8
    divergence_rate: float = 2.0
    slow_iters: int | None = None

    @property
    def effective_slow_iters(self) -> int:
        """The refresh threshold with the ``None`` default resolved."""
        if self.slow_iters is not None:
            return self.slow_iters
        return max(2, self.max_iters // 2)


class NewtonResult(NamedTuple):
    k: jax.Array  # (b, f) solved stage derivative (where converged)
    converged: jax.Array  # (b,) bool: update norm fell below tol
    diverged: jax.Array  # (b,) bool: non-finite residual or growing iterates
    n_iters: jax.Array  # (b,) int32: iterations while this instance was active
    n_evals: jax.Array  # () int32: batched vf evaluations (overhanging count)


class _NewtonState(NamedTuple):
    k: jax.Array
    active: jax.Array
    converged: jax.Array
    diverged: jax.Array
    n_iters: jax.Array
    prev_norm: jax.Array
    it: jax.Array


def newton_solve(
    eval_fn: Callable[[jax.Array], jax.Array],
    k0: jax.Array,  # (b, f) initial iterate (predictor)
    M: jax.Array | None = None,  # (b, f, f) chord matrix I - dt*gamma*J
    scale: jax.Array | None = None,  # (b, f) error scale atol + rtol*|y|
    *,
    operator: tuple[jax.Array, jax.Array] | None = None,
    config: NewtonConfig | None = None,
) -> NewtonResult:
    """Solve ``k = eval_fn(k)`` per instance by masked chord-Newton iteration.

    ``eval_fn`` is the batched stage map ``k -> f(t_i, y_pred + dt*a_ii*k)``;
    the residual is ``g(k) = k - eval_fn(k)`` and each iteration applies
    ``k <- k - M^{-1} g(k)`` where an instance is still active.  Convergence is
    per instance: the scaled RMS of the update falls below ``config.tol``
    (measured in the same atol/rtol units as the step acceptance test, so
    ``tol`` is the fraction of the local error budget the inexact solve may
    consume).  Divergence -- non-finite values or the update norm growing by
    more than ``config.divergence_rate`` between iterations -- deactivates the
    instance with ``diverged`` set; the stepper reports that through the
    controller's reject path rather than poisoning the whole batch.

    The chord operator comes from exactly one of two sources:

    - ``M``: the chord matrix itself, factored once on entry by
      ``ops.batched_lu_factor`` under the active kernel backend.
    - ``operator``: the ``(lu, permutation)`` pair from
      ``ops.batched_lu_factor(M)``, in the kernel backend's own layout
      (build it under the backend that runs the solve), for callers that
      share one factorisation across several solves.

    Either way each iteration is the single fused ``ops.fused_newton_iter``
    launch against the prefactored LU.

    All numeric knobs live on ``config`` (a :class:`NewtonConfig`); ``None``
    means the defaults.
    """
    if (M is None) == (operator is None):
        raise TypeError("newton_solve needs exactly one of M= or operator=")
    if scale is None:
        raise TypeError("newton_solve requires scale")
    if operator is None:
        operator = ops.batched_lu_factor(M)
    lu, perm = operator
    cfg = config if config is not None else NewtonConfig()
    tol, max_iters, divergence_rate = cfg.tol, cfg.max_iters, cfg.divergence_rate
    b = k0.shape[0]
    inf = jnp.asarray(jnp.inf, k0.dtype)

    def cond(s: _NewtonState):
        return jnp.any(s.active) & (s.it < max_iters)

    def body(s: _NewtonState):
        k_new, res_norm = ops.fused_newton_iter(
            lu, perm, s.k, eval_fn(s.k), s.active, scale)
        finite = jnp.isfinite(res_norm)
        conv_now = s.active & finite & (res_norm <= tol)
        div_now = s.active & (~finite | ((s.it > 0) & (res_norm > divergence_rate * s.prev_norm)))
        return _NewtonState(
            k=k_new,
            active=s.active & ~conv_now & ~div_now,
            converged=s.converged | conv_now,
            diverged=s.diverged | div_now,
            n_iters=s.n_iters + s.active.astype(jnp.int32),
            prev_norm=jnp.where(s.active, res_norm, s.prev_norm),
            it=s.it + 1,
        )

    init = _NewtonState(
        k=k0,
        active=jnp.ones((b,), dtype=bool),
        converged=jnp.zeros((b,), dtype=bool),
        diverged=jnp.zeros((b,), dtype=bool),
        n_iters=jnp.zeros((b,), dtype=jnp.int32),
        prev_norm=jnp.full((b,), inf),
        it=jnp.zeros((), dtype=jnp.int32),
    )
    out = jax.lax.while_loop(cond, body, init)
    return NewtonResult(
        k=out.k,
        converged=out.converged,
        diverged=out.diverged | (out.active & ~out.converged),
        n_iters=out.n_iters,
        n_evals=out.it,
    )
