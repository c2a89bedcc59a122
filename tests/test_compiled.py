"""The static/dynamic split and the zero-retrace compiled front end.

Covers: component hashability/frozenness (the static half of the contract),
trace counting through ``CompiledSolver`` (exactly one trace for repeated
same-shape solves; retrace on shape/dtype/static-config change), buffer
donation, bitwise agreement with the uncompiled drivers, ``sharded_solve``
consistency, the ``make_solver`` max_steps warning and the kernel-backend
error path.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import (
    AutoDiffAdjoint,
    BacksolveAdjoint,
    CompiledSolver,
    DiagonallyImplicitRK,
    Event,
    ExplicitRK,
    FixedController,
    NewtonConfig,
    ODETerm,
    ScanAdjoint,
    Status,
    StepFunction,
    Stepper,
    get_tableau,
    make_solver,
    pid_controller,
    sharded_solve,
)


def decay(t, y, args):
    return -y if args is None else -y * args


class TraceCounter:
    """A vector field that counts how many times it is *traced* (any call
    during tracing increments; a cached/compiled dispatch calls it zero
    times, so a stable count across solves proves zero retraces)."""

    def __init__(self):
        self.calls = 0

    def __call__(self, t, y, args):
        self.calls += 1
        return -y * args


# ---------------------------------------------------------------------------
# Static config: hashability, value equality, frozenness, pytree round-trips.


class TestStaticConfig:
    def test_components_hash_by_value(self):
        assert ExplicitRK("tsit5") == ExplicitRK("tsit5")
        assert hash(ExplicitRK("tsit5")) == hash(ExplicitRK("tsit5"))
        assert ExplicitRK("tsit5") != ExplicitRK("dopri5")
        assert DiagonallyImplicitRK("kvaerno3") == DiagonallyImplicitRK("kvaerno3")
        assert DiagonallyImplicitRK(
            "kvaerno3", newton=NewtonConfig(tol=1e-5)
        ) != DiagonallyImplicitRK("kvaerno3")
        assert get_tableau("dopri5") == get_tableau("dopri5")
        assert hash(get_tableau("dopri5")) != hash(get_tableau("tsit5"))
        assert pid_controller() == pid_controller()
        assert FixedController() == FixedController()
        assert hash(ODETerm(decay)) == hash(ODETerm(decay))
        assert hash(Event(decay)) == hash(Event(decay))

    def test_components_frozen(self):
        for obj in (ExplicitRK("tsit5"), DiagonallyImplicitRK("kvaerno3"),
                    AutoDiffAdjoint(Stepper("dopri5")),
                    StepFunction(decay), CompiledSolver()):
            with pytest.raises(AttributeError):
                obj.anything = 1
        tab = get_tableau("dopri5")
        with pytest.raises(ValueError):
            tab.a[0, 0] = 99.0  # coefficient arrays are read-only

    def test_driver_is_pytree_with_tolerance_leaves(self):
        drv = AutoDiffAdjoint(Stepper("tsit5"), pid_controller(),
                              rtol=jnp.full((4,), 1e-5), atol=1e-8)
        leaves, treedef = jax.tree_util.tree_flatten(drv)
        assert len(leaves) == 2  # rtol, atol -- everything else is static aux
        hash(treedef)  # aux data must be hashable
        # value-equal configs produce equal treedefs (same compiled program)
        other = jax.tree_util.tree_flatten(
            AutoDiffAdjoint(Stepper("tsit5"), pid_controller(),
                            rtol=jnp.ones((4,)), atol=0.1)
        )[1]
        assert treedef == other
        # round-trip reconstructs a working driver
        drv2 = jax.tree_util.tree_unflatten(treedef, leaves)
        sol = drv2.solve(decay, jnp.ones((4, 2)), jnp.linspace(0, 1, 5), args=1.0)
        assert np.all(np.asarray(sol.status) == Status.SUCCESS.value)

    def test_driver_as_jit_argument(self):
        """A driver crosses jax.jit as an ordinary argument: tolerances are
        dynamic (no retrace), static config keys the cache."""
        t_eval = jnp.linspace(0.0, 1.0, 5)

        @jax.jit
        def run(drv, y0):
            return drv.solve(decay, y0, t_eval, args=1.0).ys

        y0 = jnp.ones((4, 2))
        a = run(AutoDiffAdjoint(Stepper("tsit5"), rtol=1e-3), y0)
        b = run(AutoDiffAdjoint(Stepper("tsit5"), rtol=1e-7), y0)
        assert a.shape == b.shape == (4, 5, 2)

    def test_backsolve_adjoint_final_state_only(self):
        """BacksolveAdjoint compiles since the gradient-serving PR (its
        custom-VJP solve wraps into a synthesized final-state Solution), but
        it tracks only the final state: eval grids / dt0 must be refused
        with a real message, not crash in the stepper-coercion path."""
        solver = CompiledSolver(BacksolveAdjoint(Stepper("dopri5"),
                                                 rtol=1e-7, atol=1e-9),
                                donate=False)
        y0 = jnp.ones((2, 3))
        with pytest.raises(TypeError, match="final state"):
            solver.solve(decay, y0, jnp.linspace(0.0, 1.0, 4), args=1.0)
        with pytest.raises(TypeError, match="final state"):
            solver.solve(decay, y0, None, t_start=0.0, t_end=1.0, args=1.0,
                         dt0=0.01)
        sol = solver.solve(decay, y0, None, t_start=0.0, t_end=1.0, args=1.0)
        np.testing.assert_allclose(np.asarray(sol.ys),
                                   np.exp(-1.0) * np.ones((2, 3)), atol=1e-5)
        assert np.all(np.asarray(sol.status) == Status.SUCCESS.value)

    def test_stepfunction_pytree_roundtrip(self):
        sf = StepFunction(decay, "dopri5", events=Event(lambda t, y, a: y[0] - 0.5))
        leaves, treedef = jax.tree_util.tree_flatten(sf)
        sf2 = jax.tree_util.tree_unflatten(treedef, leaves)
        state, consts = sf2.init(jnp.ones((3, 2)), jnp.linspace(0, 1, 4))
        state = sf2.step(state, consts, 1.0)
        assert state.it == 1
        # the rebuilt statistics registry still points at the new instance
        assert sf2 in sf2.stat_contributors


# ---------------------------------------------------------------------------
# Trace counting: the zero-retrace contract.


class TestZeroRetrace:
    def test_exactly_one_trace_for_repeated_same_shape_solves(self):
        vf = TraceCounter()
        solver = CompiledSolver(AutoDiffAdjoint(Stepper("dopri5")), donate=False)
        t_eval = jnp.linspace(0.0, 1.0, 6)
        sols = [solver.solve(vf, jnp.full((8, 3), 1.0), t_eval, args=1.0)]
        after_first = vf.calls
        assert after_first > 0
        for i in range(5):
            sols.append(
                solver.solve(vf, jnp.full((8, 3), 0.5 + i), t_eval, args=0.5 + i)
            )
        assert vf.calls == after_first, "same-shape solve retraced the program"
        assert solver.cache_info().misses == 1
        assert solver.cache_info().hits == 5
        # and the numbers are real
        np.testing.assert_allclose(
            np.asarray(sols[1].ys[:, -1]), np.exp(-0.5) * 0.5, rtol=1e-4
        )

    def test_retrace_on_shape_dtype_and_static_change(self):
        vf = TraceCounter()
        solver = CompiledSolver(AutoDiffAdjoint(Stepper("dopri5")), donate=False)
        t_eval = jnp.linspace(0.0, 1.0, 6)
        args = jnp.asarray(1.0)
        solver.solve(vf, jnp.ones((8, 3)), t_eval, args=args)
        base = vf.calls

        # batch-shape change -> new program
        solver.solve(vf, jnp.ones((4, 3)), t_eval, args=args)
        after_shape = vf.calls
        assert after_shape > base
        # dtype change of a dynamic arg -> new program
        solver.solve(vf, jnp.ones((4, 3)), t_eval, args=jnp.asarray(1, jnp.int32))
        after_dtype = vf.calls
        assert after_dtype > after_shape
        # t_eval length change -> new program
        solver.solve(vf, jnp.ones((4, 3)), jnp.linspace(0.0, 1.0, 9), args=args)
        after_teval = vf.calls
        assert after_teval > after_dtype
        # static-config change (different tableau) -> new program
        CompiledSolver(AutoDiffAdjoint(Stepper("tsit5")), donate=False).solve(
            vf, jnp.ones((4, 3)), t_eval, args=args
        )
        assert vf.calls > after_teval
        # ...but returning to an already-seen point stays cached
        final = vf.calls
        solver.solve(vf, jnp.ones((8, 3)), t_eval, args=args)
        solver.solve(vf, jnp.ones((4, 3)), t_eval, args=args)
        assert vf.calls == final

    def test_tolerances_are_dynamic(self):
        """Per-call rtol/atol overrides reuse the same executable."""
        vf = TraceCounter()
        solver = CompiledSolver(
            AutoDiffAdjoint(Stepper("dopri5"), rtol=jnp.asarray(1e-3),
                            atol=jnp.asarray(1e-6)),
            donate=False,
        )
        t_eval = jnp.linspace(0.0, 1.0, 6)
        loose = solver.solve(vf, jnp.ones((4, 2)), t_eval, args=1.0)
        base = vf.calls
        tight = solver.solve(vf, jnp.ones((4, 2)), t_eval, args=1.0,
                             rtol=jnp.asarray(1e-9), atol=jnp.asarray(1e-12))
        assert vf.calls == base, "tolerance change must not retrace"
        assert np.all(np.asarray(tight.stats["n_steps"])
                      >= np.asarray(loose.stats["n_steps"]))

    def test_aot_compile_handle(self):
        """compile() builds the executable ahead of the first request; solve
        with matching shapes dispatches to it without tracing again."""
        vf = TraceCounter()
        solver = CompiledSolver(AutoDiffAdjoint(Stepper("dopri5")), donate=False)
        spec = jax.ShapeDtypeStruct((8, 3), jnp.float32)
        sspec = jax.ShapeDtypeStruct((), jnp.float32)
        handle = solver.compile(vf, spec, None, t_start=sspec, t_end=sspec, args=sspec)
        traced = vf.calls
        assert traced > 0
        # strong-f32 scalars: they must key identically to the compile() specs
        t0, t1, a = (jnp.zeros((), jnp.float32), jnp.ones((), jnp.float32),
                     jnp.ones((), jnp.float32))
        out = handle(jnp.ones((8, 3)), None, t_start=t0, t_end=t1, args=a)
        assert out.ys.shape == (8, 3)
        sol = solver.solve(vf, jnp.ones((8, 3)), None, t_start=t0, t_end=t1, args=a)
        assert vf.calls == traced, "AOT-compiled point must not trace again"
        np.testing.assert_array_equal(np.asarray(out.ys), np.asarray(sol.ys))


class TestDonation:
    def test_final_state_solve_donates_y0(self):
        """donate='auto' consumes the y0 buffer in the final-state regime:
        the input is aliased into an output (visible in the HLO) and the
        caller's array is actually deleted -- fewer live buffers, and reuse
        raises instead of silently reading freed memory."""
        solver = CompiledSolver(AutoDiffAdjoint(Stepper("dopri5")))
        handle = solver.compile(
            decay,
            jax.ShapeDtypeStruct((8, 3), jnp.float32),
            None,
            t_start=jax.ShapeDtypeStruct((), jnp.float32),
            t_end=jax.ShapeDtypeStruct((), jnp.float32),
            args=jax.ShapeDtypeStruct((), jnp.float32),
        )
        assert "input_output_alias" in handle.as_text()

        y0 = jnp.ones((8, 3))
        sol = solver.solve(decay, y0, None, t_start=jnp.asarray(0.0),
                           t_end=jnp.asarray(1.0), args=jnp.asarray(1.0))
        jax.block_until_ready(sol.ys)
        assert y0.is_deleted(), "y0 was not donated"
        with pytest.raises(Exception):
            np.asarray(y0 + 1.0)

    def test_dense_solve_does_not_donate_and_does_not_warn(self):
        """With t_eval no output matches y0's shape, so 'auto' keeps the
        buffer alive (and XLA's 'donated buffers were not usable' warning
        never fires)."""
        solver = CompiledSolver(AutoDiffAdjoint(Stepper("dopri5")))
        y0 = jnp.ones((8, 3))
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*donated buffers.*")
            sol = solver.solve(decay, y0, jnp.linspace(0.0, 1.0, 5), args=1.0)
            jax.block_until_ready(sol.ys)
        assert not y0.is_deleted()
        np.asarray(y0 + 1.0)  # still usable

    def test_new_shape_tol_override_after_aot_compile(self):
        """A per-instance tolerance override on an AOT-compiled point cannot
        go through the strict-aval executable; it must fall back to jit and
        compile the variant, not raise."""
        solver = CompiledSolver(AutoDiffAdjoint(Stepper("dopri5"),
                                                rtol=jnp.asarray(1e-3),
                                                atol=jnp.asarray(1e-6)),
                                donate=False)
        spec = jax.ShapeDtypeStruct((4, 2), jnp.float32)
        sspec = jax.ShapeDtypeStruct((), jnp.float32)
        solver.compile(decay, spec, None, t_start=sspec, t_end=sspec, args=sspec)
        t0, t1, a = (jnp.zeros((), jnp.float32), jnp.ones((), jnp.float32),
                     jnp.ones((), jnp.float32))
        sol = solver.solve(decay, jnp.ones((4, 2)), None, t_start=t0, t_end=t1,
                           args=a, rtol=jnp.full((4,), 1e-7))
        assert np.all(np.asarray(sol.status) == Status.SUCCESS.value)

    def test_donate_false_keeps_buffers(self):
        solver = CompiledSolver(AutoDiffAdjoint(Stepper("dopri5")), donate=False)
        y0 = jnp.ones((8, 3))
        solver.solve(decay, y0, None, t_start=0.0, t_end=1.0, args=1.0)
        assert not y0.is_deleted()


# ---------------------------------------------------------------------------
# Numerical identity with the uncompiled path.


def _mixed_configs():
    ground = Event(lambda t, y, args: y[0] - 0.2, terminal=True, direction=-1.0)
    return [
        ("dopri5-explicit", AutoDiffAdjoint(Stepper("dopri5")), None),
        ("tsit5-mixed-tol", AutoDiffAdjoint(
            Stepper("tsit5"), rtol=jnp.full((6,), 1e-3).at[::2].set(1e-7)), None),
        ("kvaerno3-implicit", AutoDiffAdjoint(DiagonallyImplicitRK("kvaerno3")), None),
        ("dopri5-events", AutoDiffAdjoint(Stepper("dopri5"), events=ground), None),
        ("kvaerno3-events", AutoDiffAdjoint(
            DiagonallyImplicitRK("kvaerno3"), events=ground), None),
    ]


class TestCompiledMatchesUncompiled:
    """``CompiledSolver`` must be the *same program*, not a numerical cousin.

    The reference is the jit of the uncompiled ``AutoDiffAdjoint.solve`` --
    identical jaxpr, so results must be bitwise identical.  (Fully eager
    op-by-op execution is NOT a bitwise reference on any backend: XLA fuses
    and reassociates differently when the whole program compiles as one unit,
    which shifts f32 roundings at the 1e-7 level; eager agreement is asserted
    to tolerance instead.)
    """

    @pytest.mark.parametrize("name,driver,_", _mixed_configs())
    def test_bitwise_vs_jit_and_close_vs_eager(self, name, driver, _):
        vf = ODETerm(decay)
        t_eval = jnp.linspace(0.0, 1.2, 7)
        y0 = jnp.linspace(0.3, 1.5, 12).reshape(6, 2)
        args = jnp.asarray(1.7)

        compiled = CompiledSolver(driver, donate=False)
        got = compiled.solve(vf, y0, t_eval, args=args)

        ref_fn = jax.jit(lambda y, a: driver.solve(vf, y, t_eval, args=a))
        ref = ref_fn(y0, args)
        np.testing.assert_array_equal(np.asarray(got.ys), np.asarray(ref.ys))
        np.testing.assert_array_equal(np.asarray(got.status), np.asarray(ref.status))
        for k in ref.stats:
            np.testing.assert_array_equal(
                np.asarray(got.stats[k]), np.asarray(ref.stats[k]), err_msg=k
            )
        if ref.event_t is not None:
            np.testing.assert_array_equal(
                np.asarray(got.event_t), np.asarray(ref.event_t)
            )

        # Eager sanity check only: op-by-op XLA rounds differently, which can
        # flip accept/reject decisions sitting on the error-ratio boundary, so
        # trajectories agree to solver-tolerance scale, not machine eps.
        eager = driver.solve(vf, y0, t_eval, args=args)
        np.testing.assert_allclose(
            np.asarray(got.ys), np.asarray(eager.ys), rtol=5e-3, atol=1e-5
        )

    def test_vmap_over_parameters(self):
        """The solve program is vmap-compatible: mapping over a dynamics
        parameter batches the whole adaptive loop one level up."""
        driver = AutoDiffAdjoint(Stepper("dopri5"), rtol=1e-7, atol=1e-9)
        t_eval = jnp.linspace(0.0, 1.0, 5)
        y0 = jnp.ones((4, 2))
        rates = jnp.linspace(0.5, 2.0, 3)
        ys = jax.jit(jax.vmap(lambda a: driver.solve(decay, y0, t_eval, args=a).ys))(
            rates
        )
        assert ys.shape == (3, 4, 5, 2)
        for i in range(3):
            direct = driver.solve(decay, y0, t_eval, args=rates[i])
            np.testing.assert_allclose(
                np.asarray(ys[i]), np.asarray(direct.ys), rtol=1e-5, atol=1e-7
            )

    def test_scan_driver_through_compiled(self):
        driver = ScanAdjoint(Stepper("bosh3"), max_steps=64)
        compiled = CompiledSolver(driver, donate=False)
        t_eval = jnp.linspace(0.0, 1.0, 5)
        y0 = jnp.ones((4, 2))
        got = compiled.solve(decay, y0, t_eval, args=1.0)
        ref = jax.jit(lambda y: driver.solve(decay, y, t_eval, args=1.0))(y0)
        np.testing.assert_array_equal(np.asarray(got.ys), np.asarray(ref.ys))


class TestCompiledPropertyHypothesis:
    """Property form of the bitwise guarantee, randomized over solver config
    x batch shape x tolerance mix (runs when hypothesis is installed)."""

    def test_property(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        configs = _mixed_configs()

        @settings(max_examples=8, deadline=None)
        @given(
            idx=st.integers(0, len(configs) - 1),
            batch=st.integers(1, 6),
            feat=st.integers(1, 3),
            seed=st.integers(0, 2**16),
        )
        def check(idx, batch, feat, seed):
            _, driver, _ = configs[idx]
            if getattr(driver, "rtol", None) is not None and hasattr(driver.rtol, "shape") \
                    and getattr(driver.rtol, "ndim", 0) == 1:
                driver = AutoDiffAdjoint(driver.stepper)  # (b,)-tol config needs b=6
            key = jax.random.PRNGKey(seed)
            y0 = 0.2 + jax.random.uniform(key, (batch, feat))
            t_eval = jnp.linspace(0.0, 1.0, 4)
            args = jnp.asarray(1.3)
            got = CompiledSolver(driver, donate=False).solve(decay, y0, t_eval, args=args)
            ref = jax.jit(lambda y, a: driver.solve(decay, y, t_eval, args=a))(y0, args)
            np.testing.assert_array_equal(np.asarray(got.ys), np.asarray(ref.ys))
            np.testing.assert_array_equal(np.asarray(got.status), np.asarray(ref.status))

        check()


# ---------------------------------------------------------------------------
# Multi-device sharding.


class TestShardedSolve:
    """Runs on however many devices exist: 1 in the plain tier-1 suite (the
    shard_map plumbing is still exercised), 4 in the CI smoke leg via
    XLA_FLAGS=--xla_force_host_platform_device_count=4."""

    def _mesh(self):
        return Mesh(np.array(jax.devices()), ("data",))

    def test_matches_single_device_exactly_mixed_tolerances(self):
        mesh = self._mesh()
        b = 8 * mesh.shape["data"]
        y0 = jnp.linspace(-1.5, 1.5, 2 * b).reshape(b, 2)
        t_eval = jnp.linspace(0.0, 1.0, 5)
        rtol = jnp.where(jnp.arange(b) % 3 == 0, 1e-7, 1e-3)
        args = jnp.asarray(3.0)

        def vdp(t, y, mu):
            x, xd = y[..., 0], y[..., 1]
            return jnp.stack((xd, mu * (1 - x**2) * xd - x), axis=-1)

        sol = sharded_solve(mesh, vdp, y0, t_eval, rtol=rtol, atol=1e-8, args=args)
        driver = AutoDiffAdjoint(Stepper("dopri5"), rtol=rtol, atol=1e-8)
        ref = jax.jit(lambda y, a: driver.solve(vdp, y, t_eval, args=a))(y0, args)
        np.testing.assert_array_equal(np.asarray(sol.ys), np.asarray(ref.ys))
        np.testing.assert_array_equal(np.asarray(sol.ts), np.asarray(ref.ts))
        np.testing.assert_array_equal(np.asarray(sol.status), np.asarray(ref.status))
        for k in ("n_steps", "n_accepted", "n_initialized"):
            np.testing.assert_array_equal(
                np.asarray(sol.stats[k]), np.asarray(ref.stats[k]), err_msg=k
            )

    def test_implicit_stepper_sharded(self):
        mesh = self._mesh()
        b = 4 * mesh.shape["data"]
        y0 = jnp.ones((b, 3))
        args = jnp.asarray(40.0)
        sol = sharded_solve(mesh, decay, y0, None, t_start=0.0, t_end=0.5,
                            method="kvaerno3", args=args)
        driver = AutoDiffAdjoint(DiagonallyImplicitRK("kvaerno3"))
        ref = jax.jit(
            lambda y, a: driver.solve(decay, y, None, t_start=0.0, t_end=0.5, args=a)
        )(y0, args)
        # The implicit stepper's batched linear algebra compiles to batch-size
        # dependent fusions, so cross-shard agreement is to tolerance (the
        # explicit path above is held to bitwise equality).
        np.testing.assert_allclose(np.asarray(sol.ys), np.asarray(ref.ys),
                                   rtol=1e-3, atol=1e-12)
        np.testing.assert_array_equal(np.asarray(sol.status), np.asarray(ref.status))

    def test_solver_kwarg_conflict_raises(self):
        """Options next to an explicit solver= would be silently ignored --
        refuse them instead."""
        mesh = self._mesh()
        drv = AutoDiffAdjoint(Stepper("dopri5"))
        with pytest.raises(TypeError, match="to the driver given"):
            sharded_solve(mesh, decay, jnp.ones((4, 2)), None, t_start=0.0,
                          t_end=1.0, solver=drv, rtol=1e-9)

    def test_ragged_batch_pads_per_shard(self):
        """Regression: batches that do not divide the mesh used to raise --
        now they pad (replicating instance 0, the serving layer's trick) and
        the sliced-back results match the unsharded solve exactly."""
        mesh = self._mesh()
        n_dev = mesh.shape["data"]
        for b in sorted({1, n_dev + 1, 2 * n_dev - 1, 3 * n_dev + 2}):
            y0 = jnp.linspace(-1.0, 1.0, 2 * b).reshape(b, 2)
            rtol = jnp.where(jnp.arange(b) % 2 == 0, 1e-6, 1e-3)
            sol = sharded_solve(mesh, decay, y0, None, t_start=0.0,
                                t_end=1.0, rtol=rtol, args=1.0)
            driver = AutoDiffAdjoint(Stepper("dopri5"), rtol=rtol)
            ref = jax.jit(
                lambda y, a: driver.solve(decay, y, None, t_start=0.0,
                                          t_end=1.0, args=a)
            )(y0, jnp.asarray(1.0))
            assert sol.ys.shape == (b, 2), "padding must be sliced off"
            np.testing.assert_array_equal(np.asarray(sol.ys),
                                          np.asarray(ref.ys))
            np.testing.assert_array_equal(np.asarray(sol.status),
                                          np.asarray(ref.status))
            np.testing.assert_array_equal(np.asarray(sol.stats["n_steps"]),
                                          np.asarray(ref.stats["n_steps"]))

    def test_ragged_batch_dense_output(self):
        mesh = self._mesh()
        b = mesh.shape["data"] + 1
        y0 = jnp.linspace(0.5, 1.5, 3 * b).reshape(b, 3)
        t_eval = jnp.linspace(0.0, 1.0, 4)
        sol = sharded_solve(mesh, decay, y0, t_eval, args=1.0)
        driver = AutoDiffAdjoint(Stepper("dopri5"))
        ref = jax.jit(
            lambda y, a: driver.solve(decay, y, t_eval, args=a)
        )(y0, jnp.asarray(1.0))
        assert sol.ys.shape == (b, 4, 3)
        np.testing.assert_array_equal(np.asarray(sol.ys), np.asarray(ref.ys))
        np.testing.assert_array_equal(np.asarray(sol.ts), np.asarray(ref.ts))


# ---------------------------------------------------------------------------
# Satellites: make_solver max_steps warning, backend error path.


class TestMakeSolverMaxSteps:
    def test_non_default_max_steps_warns(self):
        with pytest.warns(UserWarning, match="iteration bound belongs to the caller"):
            fns = make_solver(decay, max_steps=500)
        assert len(fns) == 3  # still returns the triple

    def test_default_is_silent(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            init_fn, step_fn, finish_fn = make_solver(decay)
        state, consts = init_fn(jnp.ones((3, 2)), jnp.linspace(0, 1, 4))
        state = step_fn(state, consts, 1.0)
        sol = finish_fn(state, consts)
        assert sol.ys.shape == (3, 4, 2)


class TestBackendErrors:
    def test_set_backend_unknown_raises_valueerror(self):
        from repro.kernels import ops

        old = ops.backend()
        try:
            with pytest.raises(ValueError, match="unknown kernel backend"):
                ops.set_backend("cuda")
            assert ops.backend() == old  # a rejected name must not stick
        finally:
            ops.set_backend(old)

    def test_interpret_mode_switch_roundtrip(self):
        from repro.kernels import ops

        old = ops.backend()
        try:
            ops.set_backend("interpret")
            assert ops.backend() == "interpret"
            y = jnp.ones((2, 3))
            K = jnp.ones((2, 2, 3))
            out = ops.stage_accum(y, jnp.full((2,), 0.1), K, np.array([0.5, 0.5]))
            assert out.shape == (2, 3)
        finally:
            ops.set_backend(old)
        assert ops.backend() == old

    def test_misspelt_env_backend_raises(self, monkeypatch):
        """A typo in REPRO_KERNEL_BACKEND raises on first use instead of
        latching a backend no op can dispatch to."""
        from repro.kernels import ops

        old = ops.backend()
        try:
            monkeypatch.setenv("REPRO_KERNEL_BACKEND", "palas")
            ops.reset_backend()
            with pytest.raises(ValueError, match="unknown kernel backend 'palas'"):
                ops.backend()
        finally:
            ops.set_backend(old)


class TestBackendInCacheKeys:
    """A program is traced for the kernel backend active at trace time, so
    switching backends in one process must select a different program."""

    def test_compiled_solver_misses_once_per_backend(self):
        from repro.kernels import ops

        solver = CompiledSolver("dopri5")
        y0 = jnp.ones((3, 2))
        old = ops.backend()
        try:
            keys = []
            for name in ("ref", "interpret"):
                ops.set_backend(name)
                keys.append(solver.cache_key(decay, y0, t_start=0.0, t_end=0.5))
                solver.solve(decay, jnp.ones((3, 2)), t_start=0.0, t_end=0.5)
        finally:
            ops.set_backend(old)
        assert keys[0] != keys[1]
        assert solver.cache_info().misses == 2

    def test_sharded_solve_misses_once_per_backend(self):
        from repro.core import compiled
        from repro.kernels import ops

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        before = compiled._SHARDED_CACHE.misses
        old = ops.backend()
        try:
            for name in ("ref", "interpret"):
                ops.set_backend(name)
                sharded_solve(mesh, decay, jnp.ones((2, 2)), t_start=0.0, t_end=0.5)
        finally:
            ops.set_backend(old)
        assert compiled._SHARDED_CACHE.misses - before == 2
