"""Newton-subsystem kernel validation: Pallas (interpret mode) vs the
pure-jnp oracles -- runs without optional deps (no hypothesis), so the
implicit solver's kernel contract is always checked."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NewtonConfig, newton_solve
from repro.kernels import ops, pallas_impl as pi, ref

SHAPES = [(1, 1), (3, 5), (8, 128), (17, 300), (2, 1025), (9, 64)]


def _lu_solve(A, rhs):
    """x with A @ x = rhs through the Pallas factor-once ops: one
    ``fused_newton_iter`` from k = 0 with fk = rhs commits
    k - A^{-1}(k - fk) = A^{-1} rhs."""
    b, f = rhs.shape
    lu, perm = pi.batched_lu_factor(A, interpret=True)
    x, _ = pi.fused_newton_iter(lu, perm, jnp.zeros_like(rhs), rhs, jnp.ones((b,), bool),
                                jnp.ones((b, f), rhs.dtype), interpret=True)
    return x


class TestBatchedLinsolve:
    """The Newton linear solve (LU factor + one substitution) vs the
    jnp.linalg.solve oracle.  Matrices are I - dt*gamma*J-shaped (diagonally
    dominant), the regime the kernels are specified for; agreement there is
    to 1e-6 in f32."""

    @pytest.mark.parametrize("b,f", [(1, 1), (2, 3), (3, 8), (8, 128), (5, 37), (17, 130)])
    def test_matches_ref(self, b, f):
        rng = np.random.default_rng(b * f)
        A = jnp.asarray(
            np.eye(f) + (0.25 / np.sqrt(f)) * rng.standard_normal((b, f, f)), jnp.float32
        )
        rhs = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        r = jnp.linalg.solve(A, rhs[..., None])[..., 0]
        p = _lu_solve(A, rhs)
        np.testing.assert_allclose(r, p, rtol=1e-4, atol=1e-5)

    def test_oracle_tight(self):
        """Well-conditioned small systems: interpret == ref to 1e-6."""
        rng = np.random.default_rng(7)
        b, f = 4, 6
        A = jnp.asarray(np.eye(f) + 0.1 * rng.standard_normal((b, f, f)), jnp.float32)
        rhs = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        r = jnp.linalg.solve(A, rhs[..., None])[..., 0]
        p = _lu_solve(A, rhs)
        np.testing.assert_allclose(r, p, rtol=1e-6, atol=1e-6)

    def test_residual_is_small(self):
        """The kernels' solution satisfies A @ x = rhs directly."""
        rng = np.random.default_rng(3)
        b, f = 3, 20
        A = jnp.asarray(np.eye(f) + 0.1 * rng.standard_normal((b, f, f)), jnp.float32)
        rhs = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        x = _lu_solve(A, rhs)
        res = jnp.einsum("bij,bj->bi", A, x) - rhs
        np.testing.assert_allclose(np.asarray(res), 0.0, atol=2e-6)

    def test_pivoting_handles_zero_diagonal(self):
        """A matrix needing row swaps (zero on the diagonal) still solves."""
        A = jnp.asarray([[[0.0, 1.0], [1.0, 0.0]]], jnp.float32)
        rhs = jnp.asarray([[2.0, 3.0]], jnp.float32)
        x = _lu_solve(A, rhs)
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
        p_lu, p_p = pi.unpack_lu(*pi.batched_lu_factor(A, interpret=True), b, f)
        # identical pivot choices (same max-magnitude, first-match rule) ...
        np.testing.assert_array_equal(np.asarray(r_p), np.asarray(p_p))
        # ... and matching factors up to f32 elimination rounding
        np.testing.assert_allclose(r_lu, p_lu, rtol=1e-4, atol=1e-5)

    def test_factors_reconstruct_matrix(self):
        """P @ A == L @ U for the packed kernel output."""
        rng = np.random.default_rng(2)
        b, f = 3, 12
        A = _chord(rng, b, f)
        lu, perm = pi.unpack_lu(*pi.batched_lu_factor(A, interpret=True), b, f)
        lu = np.asarray(lu)
        L = np.tril(lu, -1) + np.eye(f)
        U = np.triu(lu)
        PA = np.take_along_axis(np.asarray(A), np.asarray(perm)[:, :, None], axis=1)
        np.testing.assert_allclose(L @ U, PA, rtol=1e-5, atol=1e-5)

    def test_pivoting_handles_zero_diagonal(self):
        A = jnp.asarray([[[0.0, 1.0], [1.0, 0.0]]], jnp.float32)
        lu, perm = pi.unpack_lu(*pi.batched_lu_factor(A, interpret=True), 1, 2)
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
        p_op = pi.batched_lu_factor(A, interpret=True)
        p_lu, p_p = pi.unpack_lu(*p_op, b, f)
        assert (np.asarray(p_p) != np.arange(f)).any(axis=1).all()
        np.testing.assert_array_equal(np.asarray(r_p), np.asarray(p_p))
        np.testing.assert_allclose(r_lu, p_lu, rtol=1e-4, atol=1e-4)
        k, fk = [jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(2)]
        active = jnp.asarray([True, False, True])
        scale = jnp.ones((b, f), jnp.float32)
        rk, rn = ref.fused_newton_iter(r_lu, r_p, k, fk, active, scale)
        pk, pn = pi.fused_newton_iter(*p_op, k, fk, active, scale, interpret=True)
        np.testing.assert_allclose(rk, pk, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(rn, pn, rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(pk)[1], np.asarray(k)[1])
        delta = np.asarray(k) - np.asarray(pk)
        res = np.einsum("bij,bj->bi", np.asarray(A), delta) - np.asarray(k - fk)
        np.testing.assert_allclose(res[[0, 2]], 0.0, atol=1e-4)


def _column_substitution(lu, perm, k, fk, active, scale):
    """One chord-Newton iteration as a plain column-oriented substitution
    on ``lax.linalg.lu``'s layout, in the kernel's operation order: gather,
    ``x[i > j] -= L[i, j] * x[j]`` for j ascending, then ``x[j] /= U[j, j]``
    and ``x[i < j] -= U[i, j] * x[j]`` for j descending."""
    b, f = k.shape
    x = jnp.take_along_axis(k - fk, perm, axis=1)
    cols = jnp.arange(f)[None, :]

    def fwd(j, x):
        return jnp.where(cols > j, x - lu[:, :, j] * x[:, j][:, None], x)

    def bwd(t, x):
        j = f - 1 - t
        xj = x[:, j][:, None] / lu[:, j, j][:, None]
        return jnp.where(cols == j, xj, jnp.where(cols < j, x - lu[:, :, j] * xj, x))

    x = jax.lax.fori_loop(0, f, bwd, jax.lax.fori_loop(0, f, fwd, x))
    r = x / scale
    return jnp.where(active[:, None], k - x, k), jnp.sqrt(jnp.sum(r * r, axis=1) / f)


def _newton_case(b, f, seed):
    rng = np.random.default_rng(seed)
    A = _chord(rng, b, f)
    k, fk = [jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(2)]
    active = jnp.asarray(rng.uniform(size=(b,)) > 0.4)
    scale = jnp.asarray(np.abs(rng.standard_normal((b, f))) + 0.3, jnp.float32)
    return A, k, fk, active, scale


# Both lane-block widths (a full-extent block of the 8-padded batch below
# 128, 128-instance blocks from there on) and identity-padded instances.
LANE_SHAPES = [(1, 1), (5, 3), (8, 128), (127, 17), (128, 128), (129, 3), (256, 128)]


class TestInstancesOnLanes:
    """The substitution kernel with instances on lanes computes each element
    exactly as a column-oriented substitution does; only the norm's sum
    runs in another order."""

    @pytest.mark.parametrize("b,f", LANE_SHAPES)
    def test_bitwise_column_substitution(self, b, f):
        A, k, fk, active, scale = _newton_case(b, f, 11 * b + f)
        op = pi.batched_lu_factor(A, interpret=True)
        pk, pn = pi.fused_newton_iter(*op, k, fk, active, scale, interpret=True)
        lu, perm = pi.unpack_lu(*op, b, f)
        rk, rn = jax.jit(_column_substitution)(lu, perm, k, fk, active, scale)
        np.testing.assert_array_equal(np.asarray(pk), np.asarray(rk))
        np.testing.assert_array_max_ulp(np.asarray(pn), np.asarray(rn), maxulp=1)

    @pytest.mark.parametrize("b,f", LANE_SHAPES)
    def test_operator_layout(self, b, f):
        """Factor column c of an instance block is one (fr, L) slab with
        instances on lanes; padded instances and rows carry the identity."""
        A = _chord(np.random.default_rng(b + f), b, f)
        lut, perm = pi.batched_lu_factor(A, interpret=True)
        fr, bp = -(-f // 8) * 8, -(-b // 8) * 8
        bq = bp if bp < 128 else -(-bp // 128) * 128
        assert lut.shape == (fr, fr, bq) and perm.shape == (fr, bq)
        lu, p = pi.unpack_lu(lut, perm, b, f)
        np.testing.assert_array_equal(np.asarray(lut)[:f, :f, :b],
                                      np.transpose(np.asarray(lu), (2, 1, 0)))
        np.testing.assert_array_equal(np.asarray(perm)[:f, :b], np.asarray(p).T)
        eye = np.eye(fr, dtype=np.float32)
        for i in range(b, bq):
            np.testing.assert_array_equal(np.asarray(lut)[:, :, i], eye)
            np.testing.assert_array_equal(np.asarray(perm)[:, i], np.arange(fr))
        np.testing.assert_array_equal(np.asarray(lut)[f:, f:, :b],
                                      np.broadcast_to(eye[f:, f:, None], (fr - f, fr - f, b)))

    @pytest.mark.parametrize("b,f,budget", [(9, 64, 16 << 10), (130, 17, 40 << 10),
                                            (256, 128, 2 << 20)])
    def test_column_chunks_match_one_block(self, monkeypatch, b, f, budget):
        """Factors streamed in column chunks (forward in order, backward in
        reverse) give the one-block result bitwise."""
        A, k, fk, active, scale = _newton_case(b, f, 3 * b + f)
        op = pi.batched_lu_factor(A, interpret=True)
        whole = pi.fused_newton_iter(*op, k, fk, active, scale, interpret=True)
        monkeypatch.setattr(pi, "_FACTOR_BLOCK_BYTES", budget)
        _, R, bq = op[0].shape
        assert pi._factor_chunks(R, min(bq, 128), 4)[1] > 1
        chunked = pi.fused_newton_iter(*op, k, fk, active, scale, interpret=True)
        for w, c in zip(whole, chunked):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(c))


@pytest.fixture
def interpret_backend():
    old = ops.backend()
    ops.set_backend("interpret")
    yield
    ops.set_backend(old)


class TestNewtonSolveFactorsM:
    """``newton_solve(M=...)`` factors M on entry and iterates through the
    Pallas substitution kernel: on an affine stage map ``k -> W k + c`` with
    ``M = I - W`` the first iteration lands on the fixed point and the
    second certifies it."""

    @pytest.mark.parametrize("b,f", [(1, 1), (3, 5), (5, 37), (17, 130), (128, 8), (129, 3)])
    def test_affine_map_converges_in_two(self, interpret_backend, b, f):
        rng = np.random.default_rng(19 * b + f)
        W = (0.25 / np.sqrt(f)) * rng.standard_normal((b, f, f))
        c = rng.standard_normal((b, f))
        Wj, cj = jnp.asarray(W, jnp.float32), jnp.asarray(c, jnp.float32)

        def eval_fn(k):
            return jnp.einsum("bij,bj->bi", Wj, k) + cj

        M = jnp.eye(f, dtype=jnp.float32) - Wj
        res = newton_solve(eval_fn, jnp.zeros((b, f), jnp.float32), M,
                           jnp.ones((b, f), jnp.float32),
                           config=NewtonConfig(tol=1e-5, max_iters=5))
        assert np.all(np.asarray(res.converged))
        assert np.all(np.asarray(res.n_iters) <= 2)
        exact = np.linalg.solve(np.eye(f) - W, c[..., None])[..., 0]
        np.testing.assert_allclose(np.asarray(res.k), exact, rtol=1e-5, atol=1e-5)
