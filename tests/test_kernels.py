"""Per-kernel allclose validation: Pallas (interpret mode) vs the pure-jnp
oracles in kernels/ref.py, with shape/dtype sweeps and hypothesis properties."""

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, pallas_impl as pi, ref


def rng_arrays(seed, *shapes, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(s), dtype) for s in shapes]


SHAPES = [(1, 1), (3, 5), (8, 128), (17, 300), (2, 1025), (9, 64)]
STAGES = [2, 4, 7]


class TestFusedUpdate:
    @pytest.mark.parametrize("b,f", SHAPES)
    @pytest.mark.parametrize("s", STAGES)
    def test_matches_ref(self, b, f, s):
        y, K = rng_arrays(b * f + s, (b, f), (s, b, f))
        dt = jnp.abs(rng_arrays(1, (b,))[0]) + 0.01
        b_sol = np.random.default_rng(s).standard_normal(s)
        b_err = np.random.default_rng(s + 1).standard_normal(s)
        r_y, r_e = ref.fused_update(y, K, dt, jnp.asarray(b_sol, jnp.float32),
                                    jnp.asarray(b_err, jnp.float32))
        p_y, p_e = pi.fused_update(y, K, dt, b_sol, b_err, interpret=True)
        np.testing.assert_allclose(r_y, p_y, rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(r_e, p_e, rtol=3e-5, atol=3e-5)

    def test_zero_coefficients_skipped(self):
        y, K = rng_arrays(0, (4, 16), (7, 4, 16))
        dt = jnp.ones((4,))
        b_sol = np.array([1.0, 0, 0, 0, 0, 0, 0])
        b_err = np.zeros(7)
        p_y, p_e = pi.fused_update(y, K, dt, b_sol, b_err, interpret=True)
        np.testing.assert_allclose(p_y, y + K[0], rtol=1e-6)
        np.testing.assert_allclose(p_e, 0.0, atol=1e-7)


class TestStageAccum:
    @pytest.mark.parametrize("b,f", SHAPES)
    def test_matches_ref(self, b, f):
        s = 4
        y, K = rng_arrays(b + f, (b, f), (s, b, f))
        dt = jnp.abs(rng_arrays(2, (b,))[0]) + 0.01
        coeffs = np.random.default_rng(7).standard_normal(s)
        r = ref.stage_accum(y, dt, K, jnp.asarray(coeffs, jnp.float32))
        p = pi.stage_accum(y, dt, K, coeffs, interpret=True)
        np.testing.assert_allclose(r, p, rtol=3e-5, atol=3e-5)


class TestErrorNorm:
    @pytest.mark.parametrize("b,f", SHAPES)
    def test_matches_ref(self, b, f):
        err, y0, y1 = rng_arrays(b * 31 + f, (b, f), (b, f), (b, f))
        r = ref.error_norm(err, y0, y1, 1e-6, 1e-3)
        p = pi.error_norm(err, y0, y1, 1e-6, 1e-3, interpret=True)
        np.testing.assert_allclose(r, p, rtol=1e-4, atol=1e-6)

    def test_per_instance_tolerances(self):
        err, y0, y1 = rng_arrays(3, (4, 37), (4, 37), (4, 37))
        atol = jnp.asarray([1e-8, 1e-6, 1e-4, 1e-2])
        rtol = jnp.asarray([1e-6, 1e-5, 1e-3, 1e-2])
        r = ref.error_norm(err, y0, y1, atol, rtol)
        p = pi.error_norm(err, y0, y1, atol, rtol, interpret=True)
        np.testing.assert_allclose(r, p, rtol=1e-4)

    def test_zero_atol_feature_padding(self):
        """padding must stay exact even with atol == 0 (regression)."""
        err, y0, y1 = rng_arrays(5, (2, 130), (2, 130), (2, 130))
        r = ref.error_norm(err, y0, y1, 0.0, 1e-3)
        p = pi.error_norm(err, y0, y1, 0.0, 1e-3, interpret=True)
        np.testing.assert_allclose(r, p, rtol=1e-4)


class TestInterp:
    @pytest.mark.parametrize("b,n,f", [(1, 1, 1), (3, 7, 5), (8, 128, 128), (5, 200, 2),
                                       (4, 9, 31), (4, 9, 32)])
    def test_matches_ref(self, b, n, f):
        rng = np.random.default_rng(b * n + f)
        coeffs = tuple(jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(4))
        x = jnp.asarray(rng.uniform(0, 1, (b, n)), jnp.float32)
        mask = jnp.asarray(rng.uniform(size=(b, n)) > 0.5)
        out = jnp.asarray(rng.standard_normal((b, n, f)), jnp.float32)
        r = ref.interp_eval(coeffs, x, mask, out)
        p = pi.interp_eval(coeffs, x, mask, out, interpret=True)
        np.testing.assert_allclose(r, p, rtol=3e-5, atol=3e-5)

    @pytest.mark.parametrize("b,n,f", [(9, 200, 2), (3, 8, 130)])
    def test_all_false_rows_keep_the_buffer(self, b, n, f):
        """Rows whose mask is all-false pass the buffer through untouched,
        bitwise, whatever the flattened (point, feature) tiling."""
        rng = np.random.default_rng(b + n + f)
        coeffs = tuple(jnp.asarray(rng.standard_normal((b, f)), jnp.float32) for _ in range(4))
        x = jnp.asarray(rng.uniform(0, 1, (b, n)), jnp.float32)
        mask = rng.uniform(size=(b, n)) > 0.5
        mask[::2] = False
        out = jnp.asarray(rng.standard_normal((b, n, f)), jnp.float32)
        p = np.asarray(pi.interp_eval(coeffs, x, jnp.asarray(mask), out, interpret=True))
        np.testing.assert_array_equal(p[::2], np.asarray(out)[::2])
        r = ref.interp_eval(coeffs, x, jnp.asarray(mask), out)
        np.testing.assert_allclose(r, p, rtol=3e-5, atol=3e-5)

    def test_horner_is_a_polynomial(self):
        """ref oracle itself: interp at x equals direct polynomial eval."""
        b, n, f = 2, 9, 3
        rng = np.random.default_rng(0)
        cs = [rng.standard_normal((b, f)).astype(np.float32) for _ in range(4)]
        x = rng.uniform(0, 1, (b, n)).astype(np.float32)
        mask = np.ones((b, n), bool)
        out = np.zeros((b, n, f), np.float32)
        r = np.asarray(ref.interp_eval(tuple(map(jnp.asarray, cs)), jnp.asarray(x),
                                       jnp.asarray(mask), jnp.asarray(out)))
        direct = sum(c[:, None, :] * (x[:, :, None] ** k) for k, c in enumerate(cs))
        np.testing.assert_allclose(r, direct, rtol=1e-4, atol=1e-5)


class TestProperties:
    @settings(max_examples=25, deadline=None)
    @given(b=st.integers(1, 12), f=st.integers(1, 200), s=st.integers(1, 7),
           seed=st.integers(0, 2**30))
    def test_fused_update_property(self, b, f, s, seed):
        rng = np.random.default_rng(seed)
        y = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        K = jnp.asarray(rng.standard_normal((s, b, f)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 2, (b,)), jnp.float32)
        b_sol = rng.standard_normal(s)
        b_err = rng.standard_normal(s)
        r = ref.fused_update(y, K, dt, jnp.asarray(b_sol, jnp.float32),
                             jnp.asarray(b_err, jnp.float32))
        p = pi.fused_update(y, K, dt, b_sol, b_err, interpret=True)
        np.testing.assert_allclose(r[0], p[0], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(r[1], p[1], rtol=1e-4, atol=1e-4)

    @settings(max_examples=25, deadline=None)
    @given(b=st.integers(1, 8), f=st.integers(1, 300), seed=st.integers(0, 2**30))
    def test_error_norm_property(self, b, f, seed):
        rng = np.random.default_rng(seed)
        err = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        y0 = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        y1 = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        r = ref.error_norm(err, y0, y1, 1e-6, 1e-3)
        p = pi.error_norm(err, y0, y1, 1e-6, 1e-3, interpret=True)
        np.testing.assert_allclose(r, p, rtol=2e-4, atol=1e-6)

    @settings(max_examples=15, deadline=None)
    @given(b=st.integers(1, 10), f=st.integers(129, 400), s=st.integers(2, 5),
           seed=st.integers(0, 2**30))
    def test_fused_step_tiled_reduction_property(self, b, f, s, seed):
        """Mixed accept/reject batches through the feature-tiled two-pass WRMS
        reduction (f > 128 engages it) agree with the single-pass ref op."""
        rng = np.random.default_rng(seed)
        y = jnp.asarray(rng.uniform(0.5, 1.5, (b, f)), jnp.float32)
        K = jnp.asarray(rng.standard_normal((s, b, f)), jnp.float32)
        t = jnp.asarray(rng.uniform(0.0, 1.0, (b,)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.05, 0.2, (b,)), jnp.float32)
        running = jnp.asarray(rng.uniform(size=b) > 0.25)
        pi1 = jnp.asarray(rng.uniform(0.5, 2.0, (b,)), jnp.float32)
        pi2 = jnp.asarray(rng.uniform(0.5, 2.0, (b,)), jnp.float32)
        kw = dict(b_sol=tuple(rng.standard_normal(s).tolist()),
                  b_err=tuple((0.1 * rng.standard_normal(s)).tolist()),
                  ctrl=(0.14, -0.08, 0.02, 0.9, 0.2, 10.0, 0.0, float("inf")),
                  want_coeffs=False)
        # Calibrate atol off a probe ratio so accept/reject actually mixes.
        probe = np.asarray(ref.fused_step(y, K, K[-1], t, t + dt, dt, dt,
                                          running, pi1, pi2, 0.05, 1e-3, **kw)[1])
        atol = float(0.05 * np.median(probe)) if probe.any() else 0.05
        args = (y, K, K[-1], t, t + dt, dt, dt, running, pi1, pi2, atol, 1e-3)
        r = ref.fused_step(*args, **kw)
        p = pi.fused_step(*args, interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(r[1]), np.asarray(p[1]),
                                   rtol=1e-4, atol=1e-6)
        # Decisions may differ only on the knife edge of ratio == 1; committed
        # outputs are compared where the decisions agree.
        clear = np.abs(np.asarray(r[1]) - 1.0) > 1e-3
        np.testing.assert_array_equal(np.asarray(r[2])[clear],
                                      np.asarray(p[2])[clear])
        agree = np.asarray(r[2]) == np.asarray(p[2])
        for i in (0, 3, 4, 5, 6, 7, 8):
            np.testing.assert_allclose(np.asarray(r[i])[agree],
                                       np.asarray(p[i])[agree],
                                       rtol=2e-4, atol=1e-5)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**30))
    def test_error_norm_scale_invariance(self, seed):
        """rtol-only norm is invariant to rescaling (err, y) jointly."""
        rng = np.random.default_rng(seed)
        err = jnp.asarray(rng.standard_normal((3, 40)), jnp.float32)
        y0 = jnp.asarray(rng.standard_normal((3, 40)) + 2.0, jnp.float32)
        r1 = ref.error_norm(err, y0, y0, 0.0, 1e-3)
        r2 = ref.error_norm(err * 10, y0 * 10, y0 * 10, 0.0, 1e-3)
        np.testing.assert_allclose(r1, r2, rtol=1e-4)


class TestFusedEventOps:
    """The event layer's kernelized sign test and commit vs the ref oracle."""

    def _detect_inputs(self, seed, b, E):
        rng = np.random.default_rng(seed)
        v_prev = jnp.asarray(rng.standard_normal((b, E)), jnp.float32)
        v_new = jnp.asarray(rng.standard_normal((b, E)), jnp.float32)
        fired = jnp.asarray(rng.uniform(size=(b, E)) > 0.7)
        accept = jnp.asarray(rng.uniform(size=b) > 0.3)
        return rng, v_prev, v_new, fired, accept

    @pytest.mark.parametrize("b,E", [(1, 1), (6, 3), (17, 2)])
    @pytest.mark.parametrize("direction", [-1.0, 0.0, 1.0])
    def test_detect_matches_ref(self, b, E, direction):
        _, v_prev, v_new, fired, accept = self._detect_inputs(b * E, b, E)
        directions = tuple(direction if i % 2 == 0 else 0.0 for i in range(E))
        r = ref.fused_event_detect(v_prev, v_new, fired, accept,
                                   directions=directions)
        p = pi.fused_event_detect(v_prev, v_new, fired, accept,
                                  directions=directions, interpret=True)
        np.testing.assert_array_equal(np.asarray(r[0]), np.asarray(p[0]))
        np.testing.assert_array_equal(np.asarray(r[1]), np.asarray(p[1]))

    @pytest.mark.parametrize("b,E,f", [(1, 1, 4), (6, 3, 40), (5, 2, 300)])
    def test_commit_matches_ref(self, b, E, f):
        # f=300 exercises the feature-tiled grid with its idempotent
        # per-tile rewrites of the E-column outputs.
        rng, v_prev, v_new, fired, accept = self._detect_inputs(b + E + f, b, E)
        newly, _ = ref.fused_event_detect(v_prev, v_new, fired, accept,
                                          directions=(0.0,) * E)
        x = jnp.asarray(rng.uniform(0.0, 1.0, (b, E)), jnp.float32)
        y_ev = jnp.asarray(rng.standard_normal((b, E, f)), jnp.float32)
        y_new = jnp.asarray(rng.standard_normal((b, f)), jnp.float32)
        t0 = jnp.asarray(rng.uniform(0.0, 1.0, b), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.05, 0.2, b), jnp.float32)
        ev_t = jnp.full((b, E), jnp.nan, jnp.float32)
        ev_y = jnp.zeros((b, E, f), jnp.float32)
        terminal = tuple(bool(i % 2 == 0) for i in range(E))
        args = (x, y_ev, newly, y_new, t0, dt, fired, ev_t, ev_y)
        r = ref.fused_event_commit(*args, terminal=terminal)
        p = pi.fused_event_commit(*args, terminal=terminal, interpret=True)
        for name, rr, pp in zip(
            ("fired", "ev_t", "ev_y", "stop", "t_stop", "y_stop", "n_new"), r, p
        ):
            np.testing.assert_array_equal(np.asarray(rr), np.asarray(pp),
                                          err_msg=name)


    def test_three_events_mixed_directions(self):
        """E=3 with one rising, one falling and one two-sided event, every
        sign pattern present: detect and commit match the ref op bitwise."""
        b, E, f = 19, 3, 5
        rng, _, _, fired, accept = self._detect_inputs(99, b, E)
        signs = np.array([-1.0, 0.0, 1.0], np.float32)
        v_prev = jnp.asarray(np.stack([np.roll(signs, i) for i in range(b)])[:, :E]
                             * rng.uniform(0.5, 2.0, (b, E)), jnp.float32)
        v_new = jnp.asarray(np.stack([np.roll(signs, i // 3) for i in range(b)])[:, :E]
                            * rng.uniform(0.5, 2.0, (b, E)), jnp.float32)
        directions = (1.0, -1.0, 0.0)
        r = ref.fused_event_detect(v_prev, v_new, fired, accept, directions=directions)
        p = pi.fused_event_detect(v_prev, v_new, fired, accept, directions=directions,
                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(r[0]), np.asarray(p[0]))
        np.testing.assert_array_equal(np.asarray(r[1]), np.asarray(p[1]))
        newly = r[0]
        assert np.asarray(newly).any(axis=0).all()  # every event fires somewhere
        args = (
            jnp.asarray(rng.uniform(0.0, 1.0, (b, E)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, E, f)), jnp.float32),
            newly,
            jnp.asarray(rng.standard_normal((b, f)), jnp.float32),
            jnp.asarray(rng.uniform(0.0, 1.0, b), jnp.float32),
            jnp.asarray(rng.uniform(0.05, 0.2, b), jnp.float32),
            fired,
            jnp.full((b, E), jnp.nan, jnp.float32),
            jnp.zeros((b, E, f), jnp.float32),
        )
        terminal = (True, False, True)
        r = ref.fused_event_commit(*args, terminal=terminal)
        p = pi.fused_event_commit(*args, terminal=terminal, interpret=True)
        for name, rr, pp in zip(
            ("fired", "ev_t", "ev_y", "stop", "t_stop", "y_stop", "n_new"), r, p
        ):
            np.testing.assert_array_equal(np.asarray(rr), np.asarray(pp), err_msg=name)


class TestBackendDispatch:
    def test_solver_runs_on_interpret_backend(self):
        from repro.core import solve_ivp

        old = ops.backend()
        ops.set_backend("interpret")
        try:
            sol = solve_ivp(lambda t, y, a: -y, jnp.ones((2, 3)),
                            jnp.linspace(0, 1, 5), atol=1e-6, rtol=1e-6)
            exp = np.broadcast_to(np.exp(-np.asarray(sol.ts))[..., None], sol.ys.shape)
            np.testing.assert_allclose(np.asarray(sol.ys), exp, rtol=1e-4, atol=1e-5)
        finally:
            ops.set_backend(old)
