"""The Newton layer's batch counters: ``n_newton_trips`` (the batched Newton
iterations, one linear-solve launch each, of every attempt an instance ran)
and ``n_newton_rejects`` (attempts rejected because the instance's own
Newton solve failed)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AutoDiffAdjoint, DiagonallyImplicitRK, NewtonConfig, solve_ivp
from repro.kernels import ops


def vdp(t, y, mu):
    x, xd = y[..., 0], y[..., 1]
    return jnp.stack((xd, mu * (1 - x**2) * xd - x), axis=-1)


def linear(t, y, rates):
    return -rates * y


@pytest.fixture(autouse=True)
def ref_backend():
    old = ops.backend()
    ops.set_backend("ref")
    yield
    ops.set_backend(old)


def stats_of(sol):
    return {k: np.asarray(v) for k, v in sol.stats.items()}


def vdp_batch(mu, method="kvaerno5", newton=None, t_end=2.0, max_steps=4000):
    mu = jnp.asarray(mu, jnp.float32)
    y0 = jnp.stack([jnp.asarray([2.0, 0.0]) + 0.1 * i for i in range(mu.shape[0])])
    stepper = DiagonallyImplicitRK(method, newton=newton)
    driver = AutoDiffAdjoint(stepper, rtol=1e-5, atol=1e-6, max_steps=max_steps)
    return driver.solve(vdp, y0, None, t_start=0.0, t_end=t_end, args=mu)


@pytest.mark.parametrize("method", ["kvaerno3", "kvaerno5", "trbdf2"])
def test_one_lane_pays_only_its_own_iterations(method):
    s = stats_of(vdp_batch([100.0], method))
    assert s["n_newton_iters"][0] > 0
    np.testing.assert_array_equal(s["n_newton_trips"], s["n_newton_iters"])


def test_longest_lane_counts_every_launch():
    # Four decades of stiffness: lanes converge at different iterations and
    # finish at different loop trips.  kvaerno5 has no static evaluation,
    # so every batched evaluation after the two of the first step size is a
    # Newton trip, and the longest-running lane rides every one of them.
    s = stats_of(vdp_batch([1.0, 10.0, 100.0, 1000.0]))
    assert s["n_newton_trips"].max() == s["n_f_evals"][0] - 2
    assert np.all(s["n_newton_trips"] >= s["n_newton_iters"])
    assert np.any(s["n_newton_trips"] > s["n_newton_iters"])


def test_starved_newton_rejects_attempts():
    s = stats_of(vdp_batch([1000.0, 1000.0], newton=NewtonConfig(max_iters=1),
                           t_end=1.0, max_steps=400))
    rejects = s["n_newton_rejects"]
    assert np.all(rejects > 0)
    assert np.all(rejects <= s["n_steps"] - s["n_accepted"])


def test_linear_problem_never_fails_newton():
    rates = jnp.asarray([[1.0, 10.0], [100.0, 1000.0], [1e4, 1.0]], jnp.float32)
    y0 = jnp.ones((3, 2), jnp.float32)
    sol = solve_ivp(linear, y0, None, t_start=0.0, t_end=5.0, args=rates,
                    method="kvaerno5", rtol=1e-5, atol=1e-7)
    s = stats_of(sol)
    assert np.all(s["n_newton_iters"] > 0)
    np.testing.assert_array_equal(s["n_newton_rejects"], 0)

