"""The implicit (SDIRK) stepper hierarchy + batched masked-Newton subsystem.

Covers: non-stiff correctness of every implicit tableau, the stiff acceptance
criteria (Robertson + Van der Pol mu=1000 vs float64 BDF references, step-count
ratio vs dopri5), the ``vf_jac`` hook, per-instance Newton masking/statistics,
Jacobian reuse, and the divergence -> controller-reject path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AbstractStepper,
    AutoDiffAdjoint,
    BacksolveAdjoint,
    DiagonallyImplicitRK,
    ExplicitRK,
    NewtonConfig,
    ODETerm,
    Status,
    Stepper,
    newton_solve,
    solve_ivp,
)

IMPLICIT_METHODS = ["implicit_euler", "trbdf2", "kvaerno3", "kvaerno5"]


def exp_decay(t, y, args):
    return -y


def vdp(t, y, mu):
    x, xd = y[..., 0], y[..., 1]
    return jnp.stack((xd, mu * (1 - x**2) * xd - x), axis=-1)


def robertson(t, y, args):
    y1, y2, _ = y[..., 0], y[..., 1], y[..., 2]
    r1 = -0.04 * y1 + 1e4 * y[..., 1] * y[..., 2]
    r3 = 3e7 * y2 * y2
    return jnp.stack((r1, -r1 - r3, r3), axis=-1)


def scipy_reference(f, y0, t_end):
    scipy_integrate = pytest.importorskip("scipy.integrate")
    out = []
    for row in np.asarray(y0):
        sol = scipy_integrate.solve_ivp(
            f, (0.0, t_end), row, method="BDF", rtol=1e-10, atol=1e-13
        )
        assert sol.success
        out.append(sol.y[:, -1])
    return np.stack(out)


class TestHierarchy:
    def test_coerce_dispatches_on_tableau(self):
        assert isinstance(AbstractStepper.coerce("dopri5"), ExplicitRK)
        assert isinstance(AbstractStepper.coerce("kvaerno5"), DiagonallyImplicitRK)
        assert isinstance(AbstractStepper.coerce(None), ExplicitRK)
        s = DiagonallyImplicitRK("trbdf2")
        assert AbstractStepper.coerce(s) is s

    def test_stepper_alias_is_explicit(self):
        assert Stepper is ExplicitRK
        assert isinstance(Stepper("tsit5"), AbstractStepper)

    def test_explicit_rejects_implicit_tableau(self):
        with pytest.raises(ValueError, match="implicit"):
            ExplicitRK("kvaerno5")
        with pytest.raises(ValueError, match="explicit"):
            DiagonallyImplicitRK("dopri5")

    @pytest.mark.parametrize("method", IMPLICIT_METHODS)
    def test_tableau_consistency(self, method):
        from repro.core import get_tableau

        tab = get_tableau(method)
        assert tab.implicit
        assert tab.stiffly_accurate
        assert tab.diagonal > 0
        np.testing.assert_allclose(tab.a.sum(axis=1), tab.c, atol=1e-12)
        np.testing.assert_allclose(tab.b_sol.sum(), 1.0, atol=1e-12)


class TestNonStiffCorrectness:
    @pytest.mark.parametrize("method", ["trbdf2", "kvaerno3", "kvaerno5"])
    def test_exp_decay(self, method):
        sol = solve_ivp(exp_decay, jnp.ones((3, 2)), None, t_start=0.0, t_end=1.0,
                        method=method, atol=1e-7, rtol=1e-6, max_steps=2000)
        assert np.all(np.asarray(sol.status) == Status.SUCCESS.value)
        np.testing.assert_allclose(np.asarray(sol.ys), np.exp(-1.0), rtol=1e-4)

    def test_implicit_euler_fixed_step(self):
        sol = solve_ivp(exp_decay, jnp.ones((2, 1)), None, t_start=0.0, t_end=1.0,
                        method="implicit_euler", dt0=1e-3, max_steps=1100)
        assert np.all(np.asarray(sol.status) == Status.SUCCESS.value)
        # backward Euler is first order: error ~ dt
        np.testing.assert_allclose(np.asarray(sol.ys), np.exp(-1.0), rtol=2e-3)

    def test_dense_output(self):
        t_eval = jnp.linspace(0.0, 2.0, 17)
        sol = solve_ivp(exp_decay, jnp.ones((2, 3)), t_eval, method="kvaerno5",
                        atol=1e-7, rtol=1e-6)
        exp = np.broadcast_to(np.exp(-np.asarray(sol.ts))[..., None], sol.ys.shape)
        np.testing.assert_allclose(np.asarray(sol.ys), exp, rtol=1e-4, atol=1e-5)

    def test_component_api_driver(self):
        solver = AutoDiffAdjoint(DiagonallyImplicitRK("kvaerno3"), rtol=1e-6, atol=1e-7)
        sol = solver.solve(exp_decay, jnp.ones((2, 2)), None, t_start=0.0, t_end=1.0)
        np.testing.assert_allclose(np.asarray(sol.ys), np.exp(-1.0), rtol=1e-4)


class TestStiffAcceptance:
    """The PR's acceptance criteria: accuracy vs float64 references and the
    >= 10x accepted-step advantage over dopri5 at matched tolerances."""

    def test_vdp_mu1000(self):
        mu = 1000.0
        y0 = jnp.array([[2.0, 0.0], [1.5, 0.5]])
        ref = scipy_reference(
            lambda t, y: [y[1], mu * (1 - y[0] ** 2) * y[1] - y[0]], y0, 20.0
        )
        kw = dict(t_start=0.0, t_end=20.0, args=mu, atol=1e-6, rtol=1e-5)
        imp = solve_ivp(vdp, y0, None, method="kvaerno5", max_steps=20_000, **kw)
        assert np.all(np.asarray(imp.status) == Status.SUCCESS.value)
        rel = np.abs(np.asarray(imp.ys) - ref) / (1e-8 + np.abs(ref))
        assert rel.max() < 1e-4

        exp = solve_ivp(vdp, y0, None, method="dopri5", max_steps=100_000, **kw)
        assert np.all(np.asarray(exp.status) == Status.SUCCESS.value)
        ratio = np.asarray(exp.stats["n_accepted"]) / np.asarray(imp.stats["n_accepted"])
        assert ratio.min() >= 10.0

        # per-instance Newton statistics are populated
        n_newton = np.asarray(imp.stats["n_newton_iters"])
        assert n_newton.shape == (2,) and np.all(n_newton > 0)
        assert np.all(np.asarray(imp.stats["n_jac_evals"]) > 0)

    def test_robertson(self):
        y0 = jnp.array([[1.0, 0.0, 0.0]])
        ref = scipy_reference(
            lambda t, y: [
                -0.04 * y[0] + 1e4 * y[1] * y[2],
                0.04 * y[0] - 1e4 * y[1] * y[2] - 3e7 * y[1] ** 2,
                3e7 * y[1] ** 2,
            ],
            y0,
            100.0,
        )
        kw = dict(t_start=0.0, t_end=100.0, atol=1e-10, rtol=1e-5)
        imp = solve_ivp(robertson, y0, None, method="kvaerno5", max_steps=20_000, **kw)
        assert np.all(np.asarray(imp.status) == Status.SUCCESS.value)
        # relative accuracy incl. the ~1e-5-sized intermediate species
        rel = np.abs(np.asarray(imp.ys) - ref) / (1e-7 + np.abs(ref))
        assert rel.max() < 1e-4

        # dopri5 at the same tolerance grinds at the stability limit: cap its
        # budget and compare accepted steps (it does not even finish by 10x
        # the implicit count).
        imp_acc = int(np.asarray(imp.stats["n_accepted"])[0])
        exp = solve_ivp(robertson, y0, None, method="dopri5",
                        max_steps=min(40 * imp_acc, 20_000), **kw)
        exp_acc = int(np.asarray(exp.stats["n_accepted"])[0])
        assert exp_acc >= 10 * imp_acc  # even a capped run shows the gap


class TestNewtonSubsystem:
    def test_newton_solve_linear_exact(self):
        """For an affine map one Newton step with the exact Jacobian lands."""
        b, f = 4, 3
        rng = np.random.default_rng(0)
        W = jnp.asarray(0.3 * rng.standard_normal((f, f)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal((f,)), jnp.float32)

        def eval_fn(k):
            return k @ W.T + bias

        M = jnp.broadcast_to(jnp.eye(f) - W, (b, f, f))
        res = newton_solve(eval_fn, jnp.zeros((b, f)), M, jnp.ones((b, f)),
                           config=NewtonConfig(tol=1e-5, max_iters=5))
        assert np.all(np.asarray(res.converged))
        fixed = np.asarray(res.k)
        np.testing.assert_allclose(fixed, np.asarray(eval_fn(res.k)), atol=1e-4)
        # converged on the second iterate (first lands, second certifies)
        assert np.all(np.asarray(res.n_iters) <= 2)

    def test_newton_divergence_flagged(self):
        def eval_fn(k):
            return 1e6 * k**2 + 100.0

        M = jnp.broadcast_to(jnp.eye(2), (3, 2, 2))
        res = newton_solve(eval_fn, jnp.ones((3, 2)), M, jnp.ones((3, 2)),
                           config=NewtonConfig(tol=1e-3, max_iters=6))
        assert np.all(np.asarray(res.diverged))
        assert not np.any(np.asarray(res.converged))

    def test_per_instance_masking(self):
        """Two very different instances in one batch (oscillatory mu=1 vs
        stiff mu=1000): each runs its own step sizes AND its own Newton
        iteration counts -- the convergence masks keep the inner solves
        independent per instance."""
        mu = jnp.array([1.0, 1000.0])
        y0 = jnp.array([[2.0, 0.0], [2.0, 0.0]])
        sol = solve_ivp(vdp, y0, None, t_start=0.0, t_end=10.0, args=mu,
                        method="kvaerno5", atol=1e-6, rtol=1e-5, max_steps=20_000)
        assert np.all(np.asarray(sol.status) == Status.SUCCESS.value)
        n_newton = np.asarray(sol.stats["n_newton_iters"])
        n_steps = np.asarray(sol.stats["n_steps"])
        assert np.all(n_newton > 0)
        assert n_newton[0] != n_newton[1]  # per-instance, not batch-shared
        assert n_steps[0] != n_steps[1]

    def test_jacobian_reuse(self):
        """On a smooth problem the chord Jacobian is reused across many
        steps: far fewer Jacobian evaluations than accepted steps."""
        sol = solve_ivp(vdp, jnp.array([[2.0, 0.0]]), None, t_start=0.0, t_end=20.0,
                        args=1000.0, method="kvaerno5", atol=1e-6, rtol=1e-5,
                        max_steps=20_000)
        n_jac = int(np.asarray(sol.stats["n_jac_evals"])[0])
        n_steps = int(np.asarray(sol.stats["n_steps"])[0])
        assert 0 < n_jac < n_steps

    def test_fixed_step_newton_failure_is_not_success(self):
        """A failed nonlinear solve must never be committed, even by the
        always-accept FixedController: a fixed-step implicit solve whose
        Newton iteration cannot converge ends in REACHED_MAX_STEPS, not a
        silently wrong SUCCESS (regression)."""
        # One starved Newton iteration at a hopeless tolerance can never
        # certify convergence on a nonlinear problem.
        stepper = DiagonallyImplicitRK(
            "implicit_euler", newton=NewtonConfig(tol=1e-12, max_iters=1))
        solver = AutoDiffAdjoint(stepper, max_steps=50)
        sol = solver.solve(lambda t, y, a: -(y**3), jnp.full((2, 1), 2.0), None,
                           t_start=0.0, t_end=1.0, dt0=0.25)
        assert np.all(np.asarray(sol.status) == Status.REACHED_MAX_STEPS.value)
        assert np.all(np.asarray(sol.stats["n_accepted"]) == 0)
        # the state was never polluted by a garbage iterate
        np.testing.assert_allclose(np.asarray(sol.ys), 2.0)

    def test_backsolve_adjoint_keeps_newton_knobs(self):
        """make_adjoint_solve must thread the stepper object itself (not just
        its tableau), so Newton configuration survives into the forward and
        backward solves (regression)."""
        from repro.core.adjoint import make_adjoint_solve

        # Starved Newton at an impossible tolerance fails every step: if the
        # knobs survive, the forward solve visibly fails to advance.
        starved = DiagonallyImplicitRK(
            "kvaerno3", newton=NewtonConfig(tol=1e-14, max_iters=1))
        solve = make_adjoint_solve(lambda t, y, p: -(y**3), method=starved,
                                   max_steps=30)
        y_starved = np.asarray(solve(jnp.full((1, 1), 2.0), 0.0, 1.0, None))
        np.testing.assert_allclose(y_starved, 2.0)  # no step ever accepted

        healthy = DiagonallyImplicitRK("kvaerno3")
        solve_ok = make_adjoint_solve(lambda t, y, p: -(y**3), method=healthy,
                                      max_steps=200, rtol=1e-6, atol=1e-8)
        y_ok = np.asarray(solve_ok(jnp.full((1, 1), 2.0), 0.0, 1.0, None))
        # y' = -y^3, y(0)=2  ->  y(1) = 2/3
        np.testing.assert_allclose(y_ok, 2.0 / 3.0, rtol=1e-4)

    def test_divergence_rejects_and_recovers(self):
        """A starved Newton budget fails on the large steps the controller
        proposes along the stiff slow manifold; each failure is reported
        through the ordinary controller reject path (visible as rejected
        steps) and the solver still finishes correctly on retried steps."""
        stepper = DiagonallyImplicitRK("kvaerno5", newton=NewtonConfig(max_iters=2))
        solver = AutoDiffAdjoint(stepper, rtol=1e-5, atol=1e-6, max_steps=20_000)
        sol = solver.solve(vdp, jnp.array([[2.0, 0.0]]), None,
                           t_start=0.0, t_end=20.0, args=1000.0)
        assert np.all(np.asarray(sol.status) == Status.SUCCESS.value)
        n_steps = np.asarray(sol.stats["n_steps"])
        n_accepted = np.asarray(sol.stats["n_accepted"])
        assert np.all(n_steps > n_accepted)  # rejects happened


class TestNewtonConfigAPI:
    """The consolidated ``NewtonConfig`` surface: ``newton=`` is the one
    configuration path and ``newton_solve`` is config-first."""

    def test_default_slow_iters_derives_from_budget(self):
        cfg = NewtonConfig(max_iters=8)
        assert cfg.effective_slow_iters == 4
        assert NewtonConfig(max_iters=2).effective_slow_iters == 2
        assert NewtonConfig(max_iters=8, slow_iters=6).effective_slow_iters == 6

    def test_newton_solve_rejects_loose_kwargs(self):
        M = jnp.broadcast_to(jnp.eye(2), (1, 2, 2))
        with pytest.raises(TypeError):
            newton_solve(lambda k: 0.5 * k, jnp.ones((1, 2)), M,
                         jnp.ones((1, 2)), tol=1e-5)
        with pytest.raises(TypeError):
            newton_solve(lambda k: 0.5 * k, jnp.ones((1, 2)), M,
                         jnp.ones((1, 2)), max_iters=5)

    def test_newton_solve_needs_exactly_one_matrix_path(self):
        M = jnp.broadcast_to(jnp.eye(2), (1, 2, 2))
        from repro.kernels import ops

        op = ops.batched_lu_factor(M)
        with pytest.raises(TypeError, match="exactly one"):
            newton_solve(lambda k: 0.5 * k, jnp.ones((1, 2)), M,
                         jnp.ones((1, 2)), operator=op)
        with pytest.raises(TypeError, match="exactly one"):
            newton_solve(lambda k: 0.5 * k, jnp.ones((1, 2)),
                         scale=jnp.ones((1, 2)))

    def test_operator_path_matches_matrix_path(self):
        """Config-first newton_solve: the prefactored-operator path converges
        to the same fixed point as the dense-matrix path."""
        from repro.kernels import ops

        b, f = 4, 3
        rng = np.random.default_rng(7)
        W = jnp.asarray(0.3 * rng.standard_normal((f, f)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal((f,)), jnp.float32)

        def eval_fn(k):
            return k @ W.T + bias

        M = jnp.broadcast_to(jnp.eye(f) - W, (b, f, f))
        cfg = NewtonConfig(tol=1e-5, max_iters=5)
        res_m = newton_solve(eval_fn, jnp.zeros((b, f)), M, jnp.ones((b, f)),
                             config=cfg)
        res_op = newton_solve(eval_fn, jnp.zeros((b, f)),
                              operator=ops.batched_lu_factor(M),
                              scale=jnp.ones((b, f)), config=cfg)
        assert np.all(np.asarray(res_op.converged))
        # M= is factored by the same batched_lu_factor on entry: bitwise
        np.testing.assert_array_equal(np.asarray(res_m.k), np.asarray(res_op.k))
        np.testing.assert_array_equal(np.asarray(res_m.n_iters),
                                      np.asarray(res_op.n_iters))


class TestVfJacHook:
    def test_custom_jacobian_matches_autodiff(self):
        A = jnp.asarray([[-1.0, 2.0], [0.0, -3.0]])

        def f(t, y, args):
            return y @ A.T

        term_auto = ODETerm(f)
        term_custom = ODETerm(f, f_jac=lambda t, y, args: jnp.broadcast_to(A, (y.shape[0], 2, 2)))
        t = jnp.zeros((3,))
        y = jnp.asarray(np.random.default_rng(0).standard_normal((3, 2)), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(term_auto.vf_jac(t, y, None)),
            np.asarray(term_custom.vf_jac(t, y, None)),
            atol=1e-6,
        )

    def test_custom_jacobian_drives_solver(self):
        A = jnp.asarray([[-2.0, 1.0], [1.0, -2.0]])
        term = ODETerm(lambda t, y, args: y @ A.T,
                       f_jac=lambda t, y, args: jnp.broadcast_to(A, (y.shape[0], 2, 2)))
        sol = solve_ivp(term, jnp.ones((2, 2)), None, t_start=0.0, t_end=1.0,
                        method="kvaerno5", atol=1e-7, rtol=1e-6)
        expm = np.asarray(jax.scipy.linalg.expm(np.asarray(A)))
        np.testing.assert_allclose(np.asarray(sol.ys), np.ones((2, 2)) @ expm.T, rtol=1e-4)

    def test_wrong_jacobian_costs_iterations(self):
        """The hook is really used: a zero Jacobian degrades the chord solve
        to fixed-point iteration, which needs more inner iterations."""
        def f(t, y, args):
            return -5.0 * y

        good = ODETerm(f)
        bad = ODETerm(f, f_jac=lambda t, y, args: jnp.zeros((y.shape[0], 2, 2)))
        kw = dict(t_start=0.0, t_end=1.0, method="kvaerno5", atol=1e-7, rtol=1e-6)
        sol_good = solve_ivp(good, jnp.ones((1, 2)), None, **kw)
        sol_bad = solve_ivp(bad, jnp.ones((1, 2)), None, **kw)
        assert np.all(np.asarray(sol_bad.status) == Status.SUCCESS.value)
        assert (np.asarray(sol_bad.stats["n_newton_iters"])[0]
                > np.asarray(sol_good.stats["n_newton_iters"])[0])

    def test_unbatched_term_jacobian(self):
        term = ODETerm(lambda t, y, args: -(y**3), batched=False)
        t = jnp.zeros((2,))
        y = jnp.asarray([[1.0, 2.0], [0.5, 1.5]])
        J = np.asarray(term.vf_jac(t, y, None))
        expect = np.stack([np.diag(-3.0 * np.asarray(row) ** 2) for row in y])
        np.testing.assert_allclose(J, expect, rtol=1e-5)


class TestBacksolveWithImplicit:
    @pytest.mark.reverse_diff
    def test_backsolve_adjoint_gradient(self):
        """BacksolveAdjoint wraps the solve in custom_vjp, so implicit
        steppers (with their inner while_loop) are reverse-differentiable."""
        driver = BacksolveAdjoint(DiagonallyImplicitRK("kvaerno3"), rtol=1e-7, atol=1e-8)

        def loss(a):
            y1 = driver.solve(lambda t, y, a_: a_ * y, jnp.ones((2, 2)),
                              t_start=0.0, t_end=1.0, args=a)
            return jnp.sum(y1)

        a0 = -1.5
        g = jax.grad(loss)(a0)
        # d/da sum(4 * exp(a)) = 4 * exp(a)
        np.testing.assert_allclose(float(g), 4.0 * np.exp(a0), rtol=1e-3)
