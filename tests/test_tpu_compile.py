"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The Pallas interpreter accepts programs that the TPU compiler (Mosaic)
refuses: bool reshapes, ``dynamic_slice`` on values, lane concatenation of
bool columns.  These tests compile every kernel-registry op at real widths,
and whole ``solve_ivp`` programs on the ``pallas`` backend, for one chip of a
``v5e:2x2`` topology that libtpu describes without a device.  Each compile
must contain a Mosaic kernel (``tpu_custom_call``).

Only one process at a time may load libtpu, so the topology is described
inside a module-scoped fixture of this one file, never at import.  Nothing
here runs: results are checked by the interpret-mode parity suites.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import vdp
from benchmarks.stiff_bench import allen_cahn
from repro.core import Event, solve_ivp
from repro.kernels import ops, pallas_impl

B = 4096  # batch: large enough for a multi-tile grid, small enough to compile fast
N_EVAL = 200  # paper Table 3's evaluation grid
F_WIDTHS = (2, 128, 1024)  # one lane tile, exactly one tile, the feature-tiled schedule
N_WIDTHS = (8, 32, 128)  # chord-matrix sizes of the implicit steppers
E_COUNTS = (1, 3)

DOPRI5_B_SOL = (35 / 384, 0.0, 500 / 1113, 125 / 192, -2187 / 6784, 11 / 84, 0.0)
DOPRI5_B_ERR = (71 / 57600, 0.0, -71 / 16695, 71 / 1920, -17253 / 339200, 22 / 525, -1 / 40)
CTRL = (0.7 / 5, -0.4 / 5, 0.0, 0.9, 0.2, 10.0, 0.0, float("inf"))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any libtpu failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A compile for a described chip cannot be read back without one: keep
    # these compiles out of the persistent cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def pallas_backend():
    old = ops.backend()
    ops.set_backend("pallas")
    yield
    ops.set_backend(old)


def _compile(fn, specs, sharding):
    placed = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), specs
    )
    compiled = jax.jit(fn).lower(*placed).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _bool(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bool_)


def _op_case(name, w):
    """(fn, arg specs) of one registry op at width ``w`` (f, n or E)."""
    impl = pallas_impl.compiled_impl()
    op = getattr(impl, name)
    s = len(DOPRI5_B_SOL)
    col = _f32(B)
    if name == "stage_accum":
        coeffs = np.asarray(DOPRI5_B_SOL[:4])
        return (lambda y, dt, K: op(y, dt, K, coeffs)), (_f32(B, w), col, _f32(4, B, w))
    if name == "fused_update":
        b_sol, b_err = np.asarray(DOPRI5_B_SOL), np.asarray(DOPRI5_B_ERR)
        return (lambda y, K, dt: op(y, K, dt, b_sol, b_err)), (_f32(B, w), _f32(s, B, w), col)
    if name == "error_norm":
        return (lambda e, y0, y1: op(e, y0, y1, 1e-6, 1e-3)), (_f32(B, w),) * 3
    if name == "interp_eval":
        return (
            lambda c0, c1, c2, c3, x, m, out: op((c0, c1, c2, c3), x, m, out),
            (_f32(B, w),) * 4 + (_f32(B, N_EVAL), _bool(B, N_EVAL), _f32(B, N_EVAL, w)),
        )
    if name == "masked_newton_update":
        return op, (_f32(B, w), _f32(B, w), _bool(B), _f32(B, w))
    if name == "masked_bisect_refine":
        return (
            lambda c0, c1, c2, c3, lo, hi, vl, vm, a: op((c0, c1, c2, c3), lo, hi, vl, vm, a),
            (_f32(B, w),) * 4 + (col,) * 4 + (_bool(B),),
        )
    if name == "fused_step":
        return (
            lambda y, K, f1, *cols: op(
                y, K, f1, *cols, 1e-6, 1e-3, b_sol=DOPRI5_B_SOL, b_err=DOPRI5_B_ERR,
                ctrl=CTRL, want_coeffs=True,
            ),
            (_f32(B, w), _f32(s, B, w), _f32(B, w), col, col, col, col, _bool(B), col, col),
        )
    if name == "fused_step_poly":
        from repro.core.tableau import get_tableau

        tab = get_tableau("dopri5")
        a = tuple(tuple(float(v) for v in r) for r in np.asarray(tab.a))
        return (
            lambda y, f0, *cols: op(
                y, f0, *cols, 1e-6, 1e-3, a=a, c=None, b_sol=DOPRI5_B_SOL,
                b_err=DOPRI5_B_ERR, poly=(0.0, -0.5), ctrl=CTRL, want_coeffs=True,
            ),
            (_f32(B, w), _f32(B, w), col, col, col, col, _bool(B), col, col),
        )
    if name == "batched_linsolve":
        return op, (_f32(B, w, w), _f32(B, w))
    if name == "batched_lu_factor":
        return op, (_f32(B, w, w),)
    if name == "fused_newton_iter":
        perm = jax.ShapeDtypeStruct((B, w), jnp.int32)
        return op, (_f32(B, w, w), perm, _f32(B, w), _f32(B, w), _bool(B), _f32(B, w))
    if name == "fused_event_detect":
        directions = tuple((-1.0, 0.0, 1.0)[i % 3] for i in range(w))
        return (
            lambda vp, vn, fired, acc: op(vp, vn, fired, acc, directions=directions),
            (_f32(B, w), _f32(B, w), _bool(B, w), _bool(B)),
        )
    if name == "fused_event_commit":
        terminal = tuple(i % 2 == 0 for i in range(w))
        f = 128
        return (
            lambda *args: op(*args, terminal=terminal),
            (_f32(B, w), _f32(B, w, f), _bool(B, w), _f32(B, f), col, col,
             _bool(B, w), _f32(B, w), _f32(B, w, f)),
        )
    raise KeyError(name)


_F_OPS = ("stage_accum", "fused_update", "error_norm", "interp_eval",
          "masked_newton_update", "masked_bisect_refine", "fused_step", "fused_step_poly")
_N_OPS = ("batched_linsolve", "batched_lu_factor", "fused_newton_iter")
_E_OPS = ("fused_event_detect", "fused_event_commit")
OP_CASES = (
    [(n, w) for n in _F_OPS for w in F_WIDTHS]
    + [(n, w) for n in _N_OPS for w in N_WIDTHS]
    + [(n, w) for n in _E_OPS for w in E_COUNTS]
)


def test_every_registry_op_has_a_case():
    assert {n for n, _ in OP_CASES} == set(ops._OP_NAMES)


@pytest.mark.parametrize("name,width", OP_CASES, ids=[f"{n}-{w}" for n, w in OP_CASES])
def test_registry_op_compiles_for_v5e(one_chip, name, width):
    fn, specs = _op_case(name, width)
    _compile(fn, specs, one_chip)


def test_lu_kernels_fit_vmem_at_n128(one_chip):
    """The LU kernels keep a (BB, n, n) tile resident; at n = 128 the whole
    factor + iteration program must fit the chip's memory."""
    for name in ("batched_lu_factor", "fused_newton_iter"):
        fn, specs = _op_case(name, 128)
        mem = _compile(fn, specs, one_chip).memory_analysis()
        assert mem.temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("fused", [False, True])
def test_dopri5_dense_solve_compiles_for_v5e(one_chip, pallas_backend, fused):
    """Paper Table 3: VdP, dopri5, 200 evaluation points."""
    t_eval = jnp.linspace(0.0, 6.0, N_EVAL)

    def solve(y0):
        return solve_ivp(vdp, y0, t_eval, method="dopri5", rtol=1e-5, atol=1e-5,
                         args=2.0, max_steps=2000, fused=fused)

    _compile(solve, (_f32(B, 2),), one_chip)


@pytest.mark.parametrize("fused", [False, True])
def test_kvaerno5_solve_compiles_for_v5e(one_chip, pallas_backend, fused):
    def solve(y0):
        return solve_ivp(allen_cahn, y0, None, t_start=0.0, t_end=1.0,
                         method="kvaerno5", rtol=1e-4, atol=1e-7,
                         args=float(129**2), max_steps=4000, fused=fused)

    _compile(solve, (_f32(512, 128),), one_chip)


def test_two_event_solve_compiles_for_v5e(one_chip, pallas_backend):
    events = [
        Event(lambda t, y, args: y[..., 0], terminal=True, direction=-1),
        Event(lambda t, y, args: y[..., 0] - 0.5, terminal=False, direction=0),
    ]

    def solve(y0):
        return solve_ivp(lambda t, y, g: jnp.stack((y[..., 1], -g + 0 * y[..., 1]), -1),
                         y0, None, t_start=0.0, t_end=3.0, method="dopri5",
                         args=9.81, events=events, fused=True)

    _compile(solve, (_f32(B, 2),), one_chip)
