"""Newton-subsystem kernel validation: Pallas (interpret mode) vs the
pure-jnp oracles -- runs without optional deps (no hypothesis), so the
implicit solver's kernel contract is always checked."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import pallas_impl as pi, ref

SHAPES = [(1, 1), (3, 5), (8, 128), (17, 300), (2, 1025), (9, 64)]


class TestBatchedLinsolve:
    """Newton linear-solve kernel vs the jnp.linalg.solve oracle.  Matrices
    are I - dt*gamma*J-shaped (diagonally dominant), the regime the kernel is
    specified for; agreement there is to 1e-6 in f32."""

    @pytest.mark.parametrize("b,f", [(1, 1), (2, 3), (3, 8), (8, 128), (5, 37), (17, 130)])
    def test_matches_ref(self, b, f):
        rng = np.random.default_rng(b * f)
        A = jnp.asarray(
            np.eye(f) + (0.25 / np.sqrt(f)) * rng.standard_normal((b, f, f)), jnp.float32
        )
        rhs = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        r = ref.batched_linsolve(A, rhs)
        p = pi.batched_linsolve(A, rhs, interpret=True)
        np.testing.assert_allclose(r, p, rtol=1e-4, atol=1e-5)

    def test_oracle_tight(self):
        """Well-conditioned small systems: interpret == ref to 1e-6."""
        rng = np.random.default_rng(7)
        b, f = 4, 6
        A = jnp.asarray(np.eye(f) + 0.1 * rng.standard_normal((b, f, f)), jnp.float32)
        rhs = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        r = ref.batched_linsolve(A, rhs)
        p = pi.batched_linsolve(A, rhs, interpret=True)
        np.testing.assert_allclose(r, p, rtol=1e-6, atol=1e-6)

    def test_residual_is_small(self):
        """The kernel's solution satisfies A @ x = rhs directly."""
        rng = np.random.default_rng(3)
        b, f = 3, 20
        A = jnp.asarray(np.eye(f) + 0.1 * rng.standard_normal((b, f, f)), jnp.float32)
        rhs = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        x = pi.batched_linsolve(A, rhs, interpret=True)
        res = jnp.einsum("bij,bj->bi", A, x) - rhs
        np.testing.assert_allclose(np.asarray(res), 0.0, atol=2e-6)

    def test_pivoting_handles_zero_diagonal(self):
        """A matrix needing row swaps (zero on the diagonal) still solves."""
        A = jnp.asarray([[[0.0, 1.0], [1.0, 0.0]]], jnp.float32)
        rhs = jnp.asarray([[2.0, 3.0]], jnp.float32)
        x = pi.batched_linsolve(A, rhs, interpret=True)
        np.testing.assert_allclose(np.asarray(x), [[3.0, 2.0]], atol=1e-6)


class TestErrorNormToleranceShapes:
    """The Pallas error_norm accepts the same tolerance shapes as the ref
    oracle: scalar, per-instance (b,), and full (b, f) (regression)."""

    @pytest.mark.parametrize("shape", ["scalar", "b", "bf"])
    def test_matches_ref(self, shape):
        rng = np.random.default_rng(11)
        b, f = 5, 37
        err, y0, y1 = [jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(3)]
        if shape == "scalar":
            atol, rtol = 1e-6, 1e-3
        elif shape == "b":
            atol = jnp.asarray(rng.uniform(1e-8, 1e-4, (b,)), jnp.float32)
            rtol = jnp.asarray(rng.uniform(1e-6, 1e-2, (b,)), jnp.float32)
        else:
            atol = jnp.asarray(rng.uniform(1e-8, 1e-4, (b, f)), jnp.float32)
            rtol = jnp.asarray(rng.uniform(1e-6, 1e-2, (b, f)), jnp.float32)
        r = ref.error_norm(err, y0, y1, atol, rtol)
        p = pi.error_norm(err, y0, y1, atol, rtol, interpret=True)
        np.testing.assert_allclose(r, p, rtol=1e-4, atol=1e-6)


class TestMaskedNewtonUpdate:
    @pytest.mark.parametrize("b,f", SHAPES)
    def test_matches_ref(self, b, f):
        rng = np.random.default_rng(b + 3 * f)
        k, d = [jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(2)]
        active = jnp.asarray(rng.uniform(size=(b,)) > 0.4)
        scale = jnp.asarray(np.abs(rng.standard_normal((b, f))) + 0.3, jnp.float32)
        rk, rn = ref.masked_newton_update(k, d, active, scale)
        pk, pn = pi.masked_newton_update(k, d, active, scale, interpret=True)
        np.testing.assert_allclose(rk, pk, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(rn, pn, rtol=1e-6, atol=1e-6)

    def test_inactive_rows_frozen(self):
        k = jnp.ones((3, 4))
        d = jnp.full((3, 4), 0.5)
        active = jnp.asarray([True, False, True])
        pk, pn = pi.masked_newton_update(k, d, active, jnp.ones((3, 4)), interpret=True)
        np.testing.assert_allclose(np.asarray(pk[1]), 1.0)
        np.testing.assert_allclose(np.asarray(pk[0]), 0.5)
        # the norm is reported for every row (callers mask by active)
        np.testing.assert_allclose(np.asarray(pn), 0.5, rtol=1e-6)


def _chord(rng, b, f):
    """I - dt*gamma*J-shaped matrices, the regime the factor-once ops see."""
    return jnp.asarray(
        np.eye(f) + (0.25 / np.sqrt(f)) * rng.standard_normal((b, f, f)), jnp.float32
    )


class TestBatchedLuFactor:
    """Factor-once LU kernel vs the lax.linalg.lu oracle."""

    @pytest.mark.parametrize("b,f", SHAPES)
    def test_matches_ref(self, b, f):
        rng = np.random.default_rng(5 * b + f)
        A = _chord(rng, b, f)
        r_lu, r_p = ref.batched_lu_factor(A)
        p_lu, p_p = pi.batched_lu_factor(A, interpret=True)
        # identical pivot choices (same max-magnitude, first-match rule) ...
        np.testing.assert_array_equal(np.asarray(r_p), np.asarray(p_p))
        # ... and matching factors up to f32 elimination rounding
        np.testing.assert_allclose(r_lu, p_lu, rtol=1e-4, atol=1e-5)

    def test_factors_reconstruct_matrix(self):
        """P @ A == L @ U for the packed kernel output."""
        rng = np.random.default_rng(2)
        b, f = 3, 12
        A = _chord(rng, b, f)
        lu, perm = pi.batched_lu_factor(A, interpret=True)
        lu = np.asarray(lu)
        L = np.tril(lu, -1) + np.eye(f)
        U = np.triu(lu)
        PA = np.take_along_axis(np.asarray(A), np.asarray(perm)[:, :, None], axis=1)
        np.testing.assert_allclose(L @ U, PA, rtol=1e-5, atol=1e-5)

    def test_pivoting_handles_zero_diagonal(self):
        A = jnp.asarray([[[0.0, 1.0], [1.0, 0.0]]], jnp.float32)
        lu, perm = pi.batched_lu_factor(A, interpret=True)
        np.testing.assert_array_equal(np.asarray(perm), [[1, 0]])


class TestFusedNewtonIter:
    """The one-launch Newton iteration vs the ref composition."""

    @pytest.mark.parametrize("b,f", SHAPES)
    def test_matches_ref(self, b, f):
        rng = np.random.default_rng(7 * b + f)
        A = _chord(rng, b, f)
        k, fk = [jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(2)]
        active = jnp.asarray(rng.uniform(size=(b,)) > 0.4)
        scale = jnp.asarray(np.abs(rng.standard_normal((b, f))) + 0.3, jnp.float32)
        r_lu, r_p = ref.batched_lu_factor(A)
        rk, rn = ref.fused_newton_iter(r_lu, r_p, k, fk, active, scale)
        p_lu, p_p = pi.batched_lu_factor(A, interpret=True)
        pk, pn = pi.fused_newton_iter(p_lu, p_p, k, fk, active, scale, interpret=True)
        np.testing.assert_allclose(rk, pk, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(rn, pn, rtol=2e-4, atol=2e-4)

    def test_solves_the_chord_system(self):
        """The committed update satisfies M @ delta = k - f(k) directly."""
        rng = np.random.default_rng(13)
        b, f = 4, 24
        A = _chord(rng, b, f)
        k, fk = [jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(2)]
        active = jnp.ones((b,), bool)
        lu, perm = pi.batched_lu_factor(A, interpret=True)
        k_new, _ = pi.fused_newton_iter(lu, perm, k, fk, active,
                                        jnp.ones((b, f)), interpret=True)
        delta = np.asarray(k) - np.asarray(k_new)
        res = np.einsum("bij,bj->bi", np.asarray(A), delta) - np.asarray(k - fk)
        np.testing.assert_allclose(res, 0.0, atol=5e-6)

    def test_inactive_rows_frozen(self):
        rng = np.random.default_rng(17)
        b, f = 3, 4
        A = _chord(rng, b, f)
        k, fk = [jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(2)]
        active = jnp.asarray([True, False, True])
        lu, perm = pi.batched_lu_factor(A, interpret=True)
        k_new, _ = pi.fused_newton_iter(lu, perm, k, fk, active,
                                        jnp.ones((b, f)), interpret=True)
        np.testing.assert_array_equal(np.asarray(k_new)[1], np.asarray(k)[1])
        assert not np.array_equal(np.asarray(k_new)[0], np.asarray(k)[0])


class TestLargeSystemsWithRowSwaps:
    """n=128 chord matrices whose largest entry in every column sits off the
    diagonal, so every elimination step swaps rows: factor and iteration
    match the ref ops and solve the system."""

    def test_factor_and_newton_iter_at_n128(self):
        rng = np.random.default_rng(128)
        b, f = 3, 128
        # A cyclic shift makes row (j + 1) the dominant one in column j.
        shift = np.roll(np.eye(f), 1, axis=0)
        A = jnp.asarray(4.0 * shift + 0.1 * rng.standard_normal((b, f, f)), jnp.float32)
        r_lu, r_p = ref.batched_lu_factor(A)
        p_lu, p_p = pi.batched_lu_factor(A, interpret=True)
        assert (np.asarray(p_p) != np.arange(f)).any(axis=1).all()
        np.testing.assert_array_equal(np.asarray(r_p), np.asarray(p_p))
        np.testing.assert_allclose(r_lu, p_lu, rtol=1e-4, atol=1e-4)
        k, fk = [jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(2)]
        active = jnp.asarray([True, False, True])
        scale = jnp.ones((b, f), jnp.float32)
        rk, rn = ref.fused_newton_iter(r_lu, r_p, k, fk, active, scale)
        pk, pn = pi.fused_newton_iter(p_lu, p_p, k, fk, active, scale, interpret=True)
        np.testing.assert_allclose(rk, pk, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(rn, pn, rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(pk)[1], np.asarray(k)[1])
        delta = np.asarray(k) - np.asarray(pk)
        res = np.einsum("bij,bj->bi", np.asarray(A), delta) - np.asarray(k - fk)
        np.testing.assert_allclose(res[[0, 2]], 0.0, atol=1e-4)
