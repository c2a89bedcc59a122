"""Empirical convergence-order harness for EVERY registered tableau.

For each tableau the harness runs ONE batched fixed-step solve of the
harmonic oscillator (closed-form solution) with a per-instance step-size
sweep -- the batch axis IS the dt sweep, exercising the per-instance step
independence the solver is built around -- and asserts the slope of
log(error) vs log(dt) is within 0.4 of the tableau's nominal order.

Implicit tableaus additionally run through the fused factor-once chord-Newton
path (``fused=True``), which must preserve the discretization order.

Runs in float64 (via the ``jax.enable_x64`` context, so the
global f32 default of the rest of the suite is untouched): order-5 methods
reach ~1e-11 errors at the small-dt end, far below f32 resolution.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    TABLEAUS,
    DiagonallyImplicitRK,
    FixedController,
    NewtonConfig,
    Status,
    solve_ivp,
)

IMPLICIT = sorted(n for n in TABLEAUS if TABLEAUS[n].implicit)


def oscillator(t, y, args):
    """y'' = -y as a system; exact solution (cos t, -sin t) from (1, 0)."""
    return jnp.stack((y[..., 1], -y[..., 0]), axis=-1)


T_END = 2.0 * np.pi  # one full period: the exact endpoint state is (1, 0)


def measured_order(name: str, fused: bool = False) -> tuple[float, np.ndarray]:
    tab = TABLEAUS[name]
    # The dt sweep must sit inside the method's asymptotic regime: large
    # enough that the leading error term dominates f64 roundoff, small enough
    # that higher-order terms don't steepen the slope (tuned empirically; the
    # 5th-order pairs superconverge above dt ~ 0.3 on smooth problems).
    base = 0.25 if tab.order >= 4 else 0.2
    dts = base * 2.0 ** (-np.arange(4))
    b = len(dts)
    y0 = jnp.tile(jnp.asarray([[1.0, 0.0]], jnp.float64), (b, 1))
    if tab.implicit:
        # Tight Newton tolerance so the inner solve never floors the
        # discretization error the harness is measuring.
        method = DiagonallyImplicitRK(name, newton=NewtonConfig(tol=1e-3, max_iters=20))
    else:
        method = name
    sol = solve_ivp(
        oscillator, y0, None, t_start=0.0, t_end=T_END, method=method,
        controller=FixedController(), dt0=jnp.asarray(dts),
        atol=1e-13, rtol=1e-13, max_steps=2000, fused=fused,
    )
    assert np.all(np.asarray(sol.status) == Status.SUCCESS.value)
    if fused:  # the fast path must actually engage, not silently fall back
        assert np.all(np.asarray(sol.stats["n_fused_steps"])
                      == np.asarray(sol.stats["n_steps"]))
    err = np.abs(np.asarray(sol.ys) - np.array([1.0, 0.0])).max(axis=1)
    slope = np.polyfit(np.log(dts), np.log(np.maximum(err, 1e-16)), 1)[0]
    return float(slope), err


@pytest.mark.parametrize("name", sorted(TABLEAUS))
def test_empirical_order_matches_nominal(name):
    with jax.enable_x64(True):
        order, err = measured_order(name)
    nominal = TABLEAUS[name].order
    assert abs(order - nominal) <= 0.4, (
        f"{name}: measured order {order:.2f} vs nominal {nominal} (errors {err})"
    )


@pytest.mark.parametrize("name", sorted(TABLEAUS))
def test_errors_decrease_monotonically(name):
    """Halving dt must never increase the error anywhere in the sweep."""
    with jax.enable_x64(True):
        _, err = measured_order(name)
    assert np.all(np.diff(err) < 0), f"{name}: errors not monotone: {err}"


@pytest.mark.parametrize("name", IMPLICIT)
def test_fused_implicit_order_matches_nominal(name):
    """The factor-once fused DIRK path preserves the discretization order on
    every implicit tableau (and engages on every step)."""
    with jax.enable_x64(True):
        order, err = measured_order(name, fused=True)
    nominal = TABLEAUS[name].order
    assert abs(order - nominal) <= 0.4, (
        f"{name} (fused): measured order {order:.2f} vs nominal {nominal} "
        f"(errors {err})"
    )
