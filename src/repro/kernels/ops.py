"""Jitted dispatch layer over the solver hot-spot ops.

Backend selection (env var ``REPRO_KERNEL_BACKEND``):
  - ``ref``       pure-jnp oracle (default on CPU -- XLA:CPU fuses these well)
  - ``pallas``    compiled Pallas TPU kernels (default on TPU)
  - ``interpret`` Pallas kernels in interpret mode (CPU correctness validation)

The solver core (``core/stepper.py`` for the stage math, ``core/step.py`` for
the error norm and dense-output interpolation) only ever imports from this
module, so swapping the backend never touches solver logic.
"""

from __future__ import annotations

import os

import jax

from . import ref

_BACKEND = None
_BACKENDS = ("ref", "pallas", "interpret")


def _check(name: str) -> str:
    if name not in _BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; expected one of {_BACKENDS}")
    return name


def backend() -> str:
    """The active backend.  Resolved from ``REPRO_KERNEL_BACKEND`` on first
    use and validated like ``set_backend``: a misspelt value raises instead
    of latching a backend that every op would then fail to find."""
    global _BACKEND
    if _BACKEND is None:
        choice = os.environ.get("REPRO_KERNEL_BACKEND", "auto")
        if choice == "auto":
            choice = "pallas" if jax.default_backend() == "tpu" else "ref"
        _BACKEND = _check(choice)
    return _BACKEND


def set_backend(name: str) -> None:
    """Override backend (tests use this to exercise interpret mode).  Raises
    ``ValueError`` on unknown names (an ``assert`` would vanish under
    ``python -O`` and silently route every op through a bogus backend)."""
    global _BACKEND
    _BACKEND = _check(name)


def reset_backend() -> None:
    """Drop the cached backend choice so the next dispatch re-reads
    ``REPRO_KERNEL_BACKEND``.

    ``backend()`` latches its choice on the FIRST op dispatch; before this
    hook existed, setting the env var afterwards was silently ignored --
    processes that configure the environment late (notebooks, test fixtures,
    forked workers inheriting a stale parent choice) got whatever backend the
    first dispatch saw.  Note the JAX compilation cache is keyed on the traced
    program, so already-jitted solver programs keep the backend they were
    traced with; re-trace (new shapes/config) to pick up the change.
    """
    global _BACKEND
    _BACKEND = None


def _impl():
    b = backend()
    if b == "ref":
        return ref
    from . import pallas_impl

    return pallas_impl.interpret_impl() if b == "interpret" else pallas_impl.compiled_impl()


# --- op registry -------------------------------------------------------------
# Every hot-spot op dispatches identically: straight to ``ref`` on the ref
# backend (skipping the pallas_impl import entirely), through ``_impl()``
# otherwise.  The registry loop below stamps out one dispatcher per op name --
# adding a backend op means adding its name here and implementing it in
# ``ref.py`` / ``pallas_impl.py``, with no per-op boilerplate.

_OP_NAMES = (
    "stage_accum",
    "fused_update",
    "error_norm",
    "interp_eval",
    "batched_linsolve",
    "batched_lu_factor",
    "fused_newton_iter",
    "masked_newton_update",
    "masked_bisect_refine",
    "fused_step",
    "fused_step_poly",
    "fused_event_detect",
    "fused_event_commit",
)


def _make_dispatcher(name: str):
    ref_fn = getattr(ref, name)

    def dispatch(*args, **kwargs):
        if backend() == "ref":
            return ref_fn(*args, **kwargs)
        return getattr(_impl(), name)(*args, **kwargs)

    dispatch.__name__ = name
    dispatch.__qualname__ = name
    dispatch.__doc__ = ref_fn.__doc__
    return dispatch


for _name in _OP_NAMES:
    globals()[_name] = _make_dispatcher(_name)
del _name


hermite_coeffs = ref.hermite_coeffs  # pure arithmetic; fused into callers by XLA
rms_norm = ref.rms_norm  # init-time only (step-size selection); never in the hot loop
broadcast_tolerances = ref.broadcast_tolerances  # the shared tolerance-shape contract
pid_update = ref.pid_update  # the ONE controller program (PIDController + fused kernels)
poly_eval = ref.poly_eval  # the ONE polynomial-vf program (PolynomialTerm + megakernel)
