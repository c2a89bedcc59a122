"""Solution container, per-instance status codes and solver statistics."""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, NamedTuple

import jax
import numpy as np


class Grads(NamedTuple):
    """Gradients delivered by a reverse-mode solve program.

    y0:   cotangent of the initial state -- same PyTree structure as the
          ``y0`` that was solved, every leaf with the batch as its leading
          axis (``(b, f)`` for flat states).
    args: cotangent of the dynamics arguments (same structure as ``args``),
          or ``None`` when the solve carried no args.  When the term batches
          its args (``ODETerm.batched_args`` / serving's per-request parameter
          rows), each leaf's leading axis is the batch and row ``i`` is
          request ``i``'s own parameter gradient.
    """

    y0: Any
    args: Any = None


class Status(enum.IntEnum):
    """Per-instance termination status (SUCCESS == 0, as in torchode)."""

    SUCCESS = 0
    REACHED_MAX_STEPS = 1
    INFINITE = 2
    REACHED_DT_MIN = 3
    EVENT = 4  # a terminal event fired; the instance stopped at event_t


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Solution:
    """Result of a batched IVP solve.

    ts:     (b, n) evaluation times (== the t_eval passed in), or (b,) the
            per-instance reached times when t_eval is None (t_end on SUCCESS,
            the event time on EVENT, the last accepted time otherwise)
    ys:     (b, n, f) solution values, or (b, f) final states when t_eval is None.
            For a PyTree initial state, ``ys`` is the same PyTree structure with
            (b, n, ...) / (b, ...) leaves (unravelled at the driver boundary).
            Dense output is truncated at a terminal event: eval points past the
            event time stay at their initial (zero) fill and are excluded from
            ``n_initialized``.
    status: (b,) int32, one of ``Status``
    stats:  the solver's statistics registry: a dict of named per-instance (b,)
            accumulators contributed by each component (stepper: n_f_evals,
            controller: n_accepted, step function: n_steps, n_initialized,
            and n_events when events are registered, plus any user-registered
            contributors).  Implicit steppers add n_newton_iters (the
            instance's own Newton iterations), n_jac_evals, n_newton_trips
            and n_newton_rejects.  n_newton_trips counts the batched Newton
            iterations of every attempt the instance ran, i.e. the
            linear-solve launches its lane paid for while it had converged
            or still iterated; like n_f_evals it depends on the batch's
            composition, and its max over the batch is the solve's number of
            launches.  n_newton_rejects counts the attempts rejected because
            the instance's own Newton solve failed (diverged or hit
            max_iters), whatever the error test said

    When events are registered (all None otherwise; E = number of events):

    event_t:    (b, E) localized first-crossing times (NaN where not fired)
    event_y:    (b, E, f) interpolated states at the crossings (PyTree states
                unravel to (b, E, ...) leaves)
    event_mask: (b, E) bool -- which (instance, event) cells fired

    grads: a ``Grads(y0=..., args=...)`` record when the solution came out of
    a reverse-mode program (``CompiledSolver.solve(cotangent=...)`` / a
    served ``GradRequest``), ``None`` otherwise.  Every grads leaf carries
    the batch as its leading axis, so ``slice_batch`` views carve per-request
    gradients out of a coalesced backward solve exactly like ``ys``.
    """

    ts: jax.Array
    ys: jax.Array
    status: jax.Array
    stats: dict[str, Any]
    event_t: jax.Array | None = None
    event_y: Any = None
    event_mask: jax.Array | None = None
    grads: Any = None

    @property
    def success(self) -> jax.Array:
        """True where integration ended as requested: reached t_end OR was
        stopped by a terminal event (scipy's solve_ivp convention -- an event
        termination is the *intended* outcome, not a failure)."""
        return (self.status == Status.SUCCESS.value) | (self.status == Status.EVENT.value)

    def is_ready(self) -> bool:
        """True when every device buffer has finished computing.

        JAX dispatch is asynchronous: a solve returns immediately with
        futures for its output buffers.  The serving engine launches a batch,
        keeps packing the next one, and uses this probe to harvest completed
        solutions without ever blocking the host on an unfinished program
        (host arrays are trivially ready).
        """
        return all(
            x.is_ready() for x in jax.tree_util.tree_leaves(self)
            if isinstance(x, jax.Array)
        )

    def block_until_ready(self) -> "Solution":
        """Wait for every device buffer; returns self (chains like jax's)."""
        jax.block_until_ready(jax.tree_util.tree_leaves(self))
        return self

    def to_host(self) -> "Solution":
        """Deliver every field as a host NumPy array -- one device->host
        transfer per field (blocking if buffers are still computing).  The
        serving layer calls this exactly once per harvested batch, so the
        per-request ``slice_batch`` views that follow are zero-copy host
        slices instead of b device dispatches per field."""
        return jax.tree_util.tree_map(np.asarray, self)

    def slice_batch(self, index) -> "Solution":
        """View of a subset of instances: every field sliced along the batch
        axis by ``index`` (a ``slice``, int array or index list -- anything
        numpy-style that preserves the leading axis).

        This is the unpacking primitive of the serving layer: a padded bucket
        solve slices back into per-request solutions, and because instances
        never interact (the solver's core batch-invariance contract), a
        sliced view is exactly what solving those instances alone would have
        produced.  Works on PyTree ``ys``/``event_y`` (every leaf carries the
        batch as its leading axis) and slices each stats accumulator.
        """
        take = lambda x: x[index]
        if (isinstance(self.ys, (np.ndarray, jax.Array)) and self.event_t is None
                and self.grads is None):
            # Fast path for flat-state, event-free, forward-only solutions:
            # direct indexing, no tree machinery (the serving unpack hot loop).
            return Solution(
                ts=self.ts[index],
                ys=self.ys[index],
                status=self.status[index],
                stats={k: v[index] for k, v in self.stats.items()},
            )
        maybe = lambda x: None if x is None else jax.tree_util.tree_map(take, x)
        return dataclasses.replace(
            self,
            ts=take(self.ts),
            ys=jax.tree_util.tree_map(take, self.ys),
            status=take(self.status),
            stats={k: jax.tree_util.tree_map(take, v) for k, v in self.stats.items()},
            event_t=maybe(self.event_t),
            event_y=maybe(self.event_y),
            event_mask=maybe(self.event_mask),
            grads=maybe(self.grads),
        )

    def truncate_eval(self, n: int) -> "Solution":
        """Drop evaluation points past the first ``n``: ``ts`` becomes
        ``(b, n)`` and every ``ys`` leaf ``(b, n, ...)``.

        The unpad view for eval-grid padding: the serving layer pads each
        request's ``t_eval`` to a power-of-two length class by repeating the
        final time, and the repeated columns -- pure interpolant re-evaluations,
        never solver state -- are cut off here.  ``stats`` are left untouched
        and so count the padded grid (``n_initialized`` in particular).
        """
        if self.ts.ndim < 2:
            raise ValueError(
                "truncate_eval needs a dense-output solution (ts of shape "
                f"(b, n)); this one tracks only final states (ts {self.ts.shape})"
            )
        return dataclasses.replace(
            self,
            ts=self.ts[:, :n],
            ys=jax.tree_util.tree_map(lambda x: x[:, :n], self.ys),
        )
