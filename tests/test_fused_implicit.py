"""The fused diagonally-implicit step: factor-once chord Newton through the
kernel registry.

The contract under test mirrors the explicit fused path (PR 6/9): on the ref
backend a fused DIRK solve is BITWISE-identical to the unfused solver on every
implicit tableau -- including steps that reject on Newton failure and refresh
the chord Jacobian -- because ``batched_lu_factor`` + ``fused_newton_iter``
compose the very jnp primitives ``jnp.linalg.solve`` lowers to, in the same
order.  On top of that: engagement accounting, the FixedController
failure-is-not-success path, ref/interpret parity for the two new ops, and
the unfused step running the same factor-once sweep as the fused one
(bitwise on the interpret backend too).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AutoDiffAdjoint,
    DiagonallyImplicitRK,
    FixedController,
    NewtonConfig,
    ODETerm,
    Status,
    solve_ivp,
)
from repro.core.stepper import _tableau_arrays
from repro.core.tableau import TABLEAUS
from repro.kernels import ops, pallas_impl as pi, ref

IMPLICIT = sorted(n for n in TABLEAUS if TABLEAUS[n].implicit)


def vdp(t, y, mu):
    x, xd = y[..., 0], y[..., 1]
    return jnp.stack((xd, mu * (1 - x**2) * xd - x), axis=-1)


def robertson(t, y, args):
    y1, y2, y3 = y[..., 0], y[..., 1], y[..., 2]
    return jnp.stack(
        (
            -0.04 * y1 + 1e4 * y2 * y3,
            0.04 * y1 - 1e4 * y2 * y3 - 3e7 * y2**2,
            3e7 * y2**2,
        ),
        axis=-1,
    )


def allen_cahn(t, y, lam):
    up = jnp.concatenate([y[..., 1:], jnp.zeros_like(y[..., :1])], axis=-1)
    dn = jnp.concatenate([jnp.zeros_like(y[..., :1]), y[..., :-1]], axis=-1)
    return lam * (up - 2.0 * y + dn) + y - y**3


@pytest.fixture
def ref_backend():
    old = ops.backend()
    ops.set_backend("ref")
    yield
    ops.set_backend(old)


def _assert_bitwise(a, c):
    """Whole-Solution equality plus proof the fused path actually ran."""
    np.testing.assert_array_equal(np.asarray(a.ys), np.asarray(c.ys))
    np.testing.assert_array_equal(np.asarray(a.ts), np.asarray(c.ts))
    np.testing.assert_array_equal(np.asarray(a.status), np.asarray(c.status))
    for key in ("n_steps", "n_accepted", "n_f_evals", "n_newton_iters",
                "n_jac_evals", "n_newton_trips", "n_newton_rejects"):
        np.testing.assert_array_equal(
            np.asarray(a.stats[key]), np.asarray(c.stats[key]), err_msg=key)
    np.testing.assert_array_equal(np.asarray(c.stats["n_fused_steps"]),
                                  np.asarray(c.stats["n_steps"]))
    assert "n_fused_steps" not in a.stats
    assert not np.asarray(c.stats["fused_fallback_reason"]).any()


class TestFusedImplicitBitwise:
    """ref-backend fused DIRK solves are indistinguishable from unfused."""

    @pytest.mark.parametrize("method", IMPLICIT)
    @pytest.mark.parametrize("dense", [False, True])
    def test_vdp_mixed_stiffness(self, ref_backend, method, dense):
        # One batch spanning four decades of stiffness: the mu=1 instance
        # accepts nearly every step while mu=1000 rejects and refreshes its
        # chord Jacobian on its own schedule.
        mu = jnp.asarray([1.0, 10.0, 100.0, 1000.0], jnp.float32)
        y0 = jnp.tile(jnp.asarray([[2.0, 0.0]], jnp.float32), (4, 1))
        te = jnp.linspace(0.0, 1.0, 5) if dense else None
        kw = dict(t_start=0.0, t_end=1.0, args=mu,
                  method=DiagonallyImplicitRK(method),
                  rtol=1e-4, atol=1e-6, max_steps=8000, dense=dense)
        a = solve_ivp(vdp, y0, te, fused=False, **kw)
        c = solve_ivp(vdp, y0, te, fused=True, **kw)
        _assert_bitwise(a, c)
        if method != "implicit_euler":
            # 1st-order implicit_euler grinds to max_steps under PID at this
            # tolerance (identically on both paths -- the equality above is
            # the contract); the higher-order tableaus must actually finish.
            assert np.all(np.asarray(a.status) == Status.SUCCESS.value)

    @pytest.mark.parametrize("method", ["trbdf2", "kvaerno5"])
    def test_robertson(self, ref_backend, method):
        y0 = jnp.tile(jnp.asarray([[1.0, 0.0, 0.0]], jnp.float32), (3, 1))
        kw = dict(t_start=0.0, t_end=100.0,
                  method=DiagonallyImplicitRK(method),
                  rtol=1e-4, atol=1e-8, max_steps=8000)
        a = solve_ivp(robertson, y0, None, fused=False, **kw)
        c = solve_ivp(robertson, y0, None, fused=True, **kw)
        _assert_bitwise(a, c)
        assert np.all(np.asarray(a.status) == Status.SUCCESS.value)

    def test_newton_reject_path(self, ref_backend):
        # A starved Newton budget forces solver-failure rejects (n_steps >
        # n_accepted): the failed -> inf-ratio -> controller-reject route must
        # agree bitwise between the fused kernel and the unfused solver.
        stepper = DiagonallyImplicitRK("kvaerno5", newton=NewtonConfig(max_iters=2))
        kw = dict(rtol=1e-5, atol=1e-6, max_steps=20_000)
        sk = dict(t_start=0.0, t_end=20.0, args=1000.0)
        y0 = jnp.asarray([[2.0, 0.0]], jnp.float32)
        a = AutoDiffAdjoint(stepper, fused=False, **kw).solve(vdp, y0, None, **sk)
        c = AutoDiffAdjoint(stepper, fused=True, **kw).solve(vdp, y0, None, **sk)
        _assert_bitwise(a, c)
        assert np.all(np.asarray(a.stats["n_steps"])
                      > np.asarray(a.stats["n_accepted"]))

    def test_fixed_controller_failure_is_not_success(self, ref_backend):
        # The fused kernel's ctrl_mode="fixed" switch would happily accept
        # everything; the solver-failure column must veto the commit exactly
        # like the unfused path (regression contract of PR 9's fixed mode).
        stepper = DiagonallyImplicitRK(
            "implicit_euler", newton=NewtonConfig(tol=1e-12, max_iters=1))
        kw = dict(max_steps=50, controller=FixedController())
        f = lambda t, y, a: -(y**3)
        y0 = jnp.full((2, 1), 2.0, jnp.float32)
        a = AutoDiffAdjoint(stepper, fused=False, **kw).solve(
            f, y0, None, t_start=0.0, t_end=1.0, dt0=0.25)
        c = AutoDiffAdjoint(stepper, fused=True, **kw).solve(
            f, y0, None, t_start=0.0, t_end=1.0, dt0=0.25)
        _assert_bitwise(a, c)
        assert np.all(np.asarray(c.status) == Status.REACHED_MAX_STEPS.value)
        assert np.all(np.asarray(c.stats["n_accepted"]) == 0)
        np.testing.assert_allclose(np.asarray(c.ys), 2.0)

    def test_fixed_controller_bitwise(self, ref_backend):
        stepper = DiagonallyImplicitRK("trbdf2")
        kw = dict(max_steps=200, controller=FixedController())
        y0 = jnp.asarray([[2.0, 0.0]], jnp.float32)
        a = AutoDiffAdjoint(stepper, fused=False, **kw).solve(
            vdp, y0, None, t_start=0.0, t_end=1.0, dt0=0.05, args=5.0)
        c = AutoDiffAdjoint(stepper, fused=True, **kw).solve(
            vdp, y0, None, t_start=0.0, t_end=1.0, dt0=0.05, args=5.0)
        _assert_bitwise(a, c)


class TestUnfusedStepFactorsOnce:
    """The unfused DIRK step runs the same factor-once stage sweep as the
    fused path, so on the interpret backend -- where a Gauss-Jordan solve
    per iteration and LU substitution round differently -- the two agree
    bitwise: same K, same carry, same counters."""

    @pytest.mark.parametrize("method", IMPLICIT)
    def test_step_matches_fused_stage_parts_interpret(self, method):
        # A 16-point Allen-Cahn semidiscretisation: large enough a chord
        # matrix that the two linear solvers would round apart.
        stepper = DiagonallyImplicitRK(method)
        term = ODETerm(allen_cahn)
        n = 16
        mu = jnp.float32((n + 1) ** 2)
        x = jnp.linspace(0.0, 1.0, n + 2, dtype=jnp.float32)[1:-1]
        amp = jnp.asarray([0.5, 1.0, 1.5, 2.0], jnp.float32)
        y = amp[:, None] * jnp.sin(jnp.pi * x)[None, :] + 0.1 * x[None, :]
        t = jnp.zeros((4,), jnp.float32)
        dt = jnp.asarray([0.05, 0.02, 0.01, 0.005], jnp.float32)
        scale = 1e-7 + 1e-4 * jnp.abs(y)
        _, _, b_sol, b_err = _tableau_arrays(stepper.tableau, y.dtype)

        def unfused(y, f0, carry):
            return stepper.step(term, t, dt, y, f0, mu, carry, scale)

        def fused(y, f0, carry):
            return stepper.fused_stage_parts(term, t, dt, y, f0, mu, carry, scale)

        def combine(y, K):
            return ops.fused_update(y, K, dt, b_sol, b_err)

        old = ops.backend()
        try:
            ops.set_backend("interpret")
            f0 = term.vf(t, y, mu)
            carry = stepper.init_carry(term, t, y, f0, mu)
            # Two steps: a fresh Jacobian, then the first step's carry.
            for _ in range(2):
                a = jax.jit(unfused)(y, f0, carry)
                K, f1, n_evals, carry_c, failed, aux = jax.jit(fused)(y, f0, carry)
                y1, err = jax.jit(combine)(y, K)
                for name, u, c in (("y1", a.y1, y1), ("err", a.err, err),
                                   ("f1", a.f1, f1), ("n_f_evals", a.n_f_evals, n_evals),
                                   ("failed", a.solver_failed, failed),
                                   ("jac", a.carry.jac, carry_c.jac),
                                   ("refresh", a.carry.refresh, carry_c.refresh)):
                    np.testing.assert_array_equal(np.asarray(u), np.asarray(c),
                                                  err_msg=name)
                assert set(a.stats_aux) == set(aux)
                for key in aux:
                    np.testing.assert_array_equal(np.asarray(a.stats_aux[key]),
                                                  np.asarray(aux[key]), err_msg=key)
                assert int(a.n_f_evals) > 0
                y, f0, carry = a.y1, a.f1, a.carry
        finally:
            ops.set_backend(old)


def _masked_linsolve_update(A, k, fk, active, scale):
    """One chord-Newton iteration written out: ``delta = A^{-1} (k - fk)``,
    committed where active, and the (b,) RMS of ``delta / scale``."""
    delta = jnp.linalg.solve(A, (k - fk)[..., None])[..., 0]
    ratio = delta / scale
    return (jnp.where(active[:, None], k - delta, k),
            jnp.sqrt(jnp.mean(ratio * ratio, axis=-1)))


class TestNewOpsParity:
    """ref vs pallas-interpret agreement for the two new registry ops."""

    SHAPES = [(1, 1), (3, 5), (8, 128), (17, 300), (2, 129), (9, 64)]

    @staticmethod
    def _chordlike(rng, b, f):
        # diagonally-dominant like M = I - dt*gamma*J on a sane step
        A = rng.normal(size=(b, f, f)).astype(np.float32)
        A += (3.0 + np.abs(A).sum(axis=-1).max(axis=-1))[:, None, None] * np.eye(f)
        return jnp.asarray(A)

    @pytest.mark.parametrize("b,f", SHAPES)
    def test_lu_factor_and_iter_match_ref(self, b, f):
        rng = np.random.default_rng(b * 131 + f)
        A = self._chordlike(rng, b, f)
        k = jnp.asarray(rng.normal(size=(b, f)).astype(np.float32))
        fk = jnp.asarray(rng.normal(size=(b, f)).astype(np.float32))
        active = jnp.asarray(rng.integers(0, 2, size=(b,)).astype(bool))
        scale = jnp.asarray(rng.uniform(0.5, 2.0, size=(b, f)).astype(np.float32))

        lu_r, p_r = ref.batched_lu_factor(A)
        lu_i, p_i = pi.batched_lu_factor(A, interpret=True)
        np.testing.assert_array_equal(np.asarray(p_r),
                                      np.asarray(pi.unpack_lu(lu_i, p_i, b, f)[1]))
        k_r, n_r = ref.fused_newton_iter(lu_r, p_r, k, fk, active, scale)
        k_i, n_i = pi.fused_newton_iter(lu_i, p_i, k, fk, active, scale,
                                        interpret=True)
        tol = dict(rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(k_r), np.asarray(k_i), **tol)
        np.testing.assert_allclose(np.asarray(n_r), np.asarray(n_i), **tol)
        # inactive rows commit nothing, in both backends
        frozen = ~np.asarray(active)
        np.testing.assert_array_equal(np.asarray(k_i)[frozen],
                                      np.asarray(k)[frozen])

    def test_ref_iter_is_masked_linsolve_update(self):
        # The ref fused iteration IS a dense solve against the same matrix
        # plus the masked commit and the scaled RMS, bitwise -- the
        # factor-once parity anchor.
        rng = np.random.default_rng(3)
        b, f = 6, 7
        A = self._chordlike(rng, b, f)
        k = jnp.asarray(rng.normal(size=(b, f)).astype(np.float32))
        fk = jnp.asarray(rng.normal(size=(b, f)).astype(np.float32))
        active = jnp.asarray([True, True, False, True, False, True])
        scale = jnp.asarray(rng.uniform(0.5, 2.0, size=(b, f)).astype(np.float32))

        k_a, n_a = _masked_linsolve_update(A, k, fk, active, scale)
        k_b, n_b = ref.fused_newton_iter(*ref.batched_lu_factor(A), k, fk,
                                         active, scale)
        np.testing.assert_array_equal(np.asarray(k_a), np.asarray(k_b))
        np.testing.assert_array_equal(np.asarray(n_a), np.asarray(n_b))

    def test_all_inactive_batch_is_identity(self):
        rng = np.random.default_rng(11)
        b, f = 4, 9
        A = self._chordlike(rng, b, f)
        k = jnp.asarray(rng.normal(size=(b, f)).astype(np.float32))
        fk = jnp.asarray(rng.normal(size=(b, f)).astype(np.float32))
        active = jnp.zeros((b,), bool)
        scale = jnp.ones((b, f), jnp.float32)
        for impl, extra in ((ref, {}), (pi, {"interpret": True})):
            lu, p = impl.batched_lu_factor(A, **extra)
            k_new, _ = impl.fused_newton_iter(lu, p, k, fk, active, scale, **extra)
            np.testing.assert_array_equal(np.asarray(k_new), np.asarray(k))

    def test_masked_parity_property(self):
        """Hypothesis sweep: parity between the fused iteration and a dense
        solve plus masked commit holds under arbitrary active masks,
        including all-inactive and all-active batches."""
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=25, deadline=None)
        @given(
            b=st.integers(1, 9),
            f=st.integers(1, 24),
            seed=st.integers(0, 2**16),
            mask=st.sampled_from(["none", "all", "random"]),
        )
        def prop(b, f, seed, mask):
            rng = np.random.default_rng(seed)
            A = self._chordlike(rng, b, f)
            k = jnp.asarray(rng.normal(size=(b, f)).astype(np.float32))
            fk = jnp.asarray(rng.normal(size=(b, f)).astype(np.float32))
            active = jnp.asarray(
                np.zeros(b, bool) if mask == "none"
                else np.ones(b, bool) if mask == "all"
                else rng.integers(0, 2, size=b).astype(bool))
            scale = jnp.asarray(rng.uniform(0.5, 2.0, (b, f)).astype(np.float32))
            k_a, n_a = _masked_linsolve_update(A, k, fk, active, scale)
            k_b, n_b = ref.fused_newton_iter(*ref.batched_lu_factor(A), k, fk,
                                             active, scale)
            np.testing.assert_array_equal(np.asarray(k_a), np.asarray(k_b))
            np.testing.assert_array_equal(np.asarray(n_a), np.asarray(n_b))

        prop()


class TestFusedImplicitInterpret:
    """End-to-end fused DIRK solve through the pallas interpret backend."""

    @pytest.mark.parametrize("method", ["trbdf2", "kvaerno5"])
    def test_interpret_solve_matches_ref(self, method):
        mu = jnp.asarray([1.0, 100.0], jnp.float32)
        y0 = jnp.tile(jnp.asarray([[2.0, 0.0]], jnp.float32), (2, 1))
        kw = dict(t_start=0.0, t_end=1.0, args=mu,
                  method=DiagonallyImplicitRK(method),
                  rtol=1e-4, atol=1e-6, max_steps=4000, fused=True)
        old = ops.backend()
        try:
            ops.set_backend("ref")
            a = solve_ivp(vdp, y0, None, **kw)
            ops.set_backend("interpret")
            c = solve_ivp(vdp, y0, None, **kw)
        finally:
            ops.set_backend(old)
        assert np.all(np.asarray(c.status) == Status.SUCCESS.value)
        np.testing.assert_array_equal(np.asarray(c.stats["n_fused_steps"]),
                                      np.asarray(c.stats["n_steps"]))
        np.testing.assert_allclose(np.asarray(a.ys), np.asarray(c.ys),
                                   rtol=5e-3, atol=1e-4)
