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

import base64
import json
import os
import re

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


def _lower(fn, specs, sharding):
    placed = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), specs
    )
    return jax.jit(fn).lower(*placed)


def _compile(fn, specs, sharding):
    compiled = _lower(fn, specs, sharding).compile()
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
    if name == "batched_lu_factor":
        return op, (_f32(B, w, w),)
    if name == "fused_newton_iter":
        lut, perm = jax.eval_shape(impl.batched_lu_factor, _f32(B, w, w))
        return op, (lut, perm, _f32(B, w), _f32(B, w), _bool(B), _f32(B, w))
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
          "masked_bisect_refine", "fused_step", "fused_step_poly")
_N_OPS = ("batched_lu_factor", "fused_newton_iter")
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


def _factor_op_specs(b, n):
    """Shapes of ``batched_lu_factor`` and ``fused_newton_iter`` at batch b."""
    lut, perm = jax.eval_shape(pallas_impl.batched_lu_factor, _f32(b, n, n))
    return {
        "batched_lu_factor": (_f32(b, n, n),),
        "fused_newton_iter": (lut, perm, _f32(b, n), _f32(b, n), _bool(b), _f32(b, n)),
    }


FACTOR_CASES = [(name, b, n) for name in ("batched_lu_factor", "fused_newton_iter")
                for b in (8, 100, 256) for n in N_WIDTHS]


@pytest.mark.parametrize("name,b,n", FACTOR_CASES,
                         ids=[f"{name}-b{b}-n{n}" for name, b, n in FACTOR_CASES])
def test_factor_ops_compile_at_both_lane_blocks(one_chip, name, b, n):
    """A batch below one lane tile is one full-extent instance block (8, or
    104 lanes for a batch of 100, the size of a served bucket); from 128
    on, instances go in 128-lane blocks (256: two of them)."""
    _compile(getattr(pallas_impl, name), _factor_op_specs(b, n)[name], one_chip)


def test_lu_kernels_fit_vmem_at_n128(one_chip):
    """The LU kernel keeps a (BB, n, n) tile resident, the substitution
    kernel one (n, n, 128) block of factors (8 MiB at n = 128): that is its
    whole factor set, so both phases read the one block.  At n = 128 the
    whole factor + iteration program must fit the chip's memory."""
    assert pallas_impl._factor_chunks(128, 128, 4) == (128, 1)
    for name, specs in _factor_op_specs(256, 128).items():
        mem = _compile(getattr(pallas_impl, name), specs, one_chip).memory_analysis()
        assert mem.temp_size_in_bytes < 4 << 30


def test_newton_iter_streams_column_chunks_at_n256(one_chip):
    """Past one block the factors stream in column chunks (forward in
    order, backward in reverse): n = 256 takes four."""
    assert pallas_impl._factor_chunks(256, 128, 4) == (64, 4)
    _compile(pallas_impl.fused_newton_iter, _factor_op_specs(256, 256)["fused_newton_iter"],
             one_chip)


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
    """Fused or not, a DIRK solve factors the chord matrix once per step
    attempt and substitutes in every Newton iteration."""
    def solve(y0):
        return solve_ivp(allen_cahn, y0, None, t_start=0.0, t_end=1.0,
                         method="kvaerno5", rtol=1e-4, atol=1e-7,
                         args=float(129**2), max_steps=4000, fused=fused)

    lowered = _lower(solve, (_f32(512, 128),), one_chip)
    assert "tpu_custom_call" in lowered.compile().as_text()
    # Pallas names each kernel after its function.
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    assert {"_lu_factor_kernel", "_newton_iter_kernel"} <= kernels


def _computations(hlo_text):
    """The instruction lines of each computation of compiled HLO text."""
    comps = []
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comps.append([])
        elif comps and line.startswith("  "):
            comps[-1].append(line.strip().removeprefix("ROOT "))
    return comps


def _runs_kernel(line, kernel):
    """Whether a compiled ``tpu_custom_call`` line runs the named kernel."""
    at = line.find("backend_config=")
    if 'custom_call_target="tpu_custom_call"' not in line or at < 0:
        return False
    config, _ = json.JSONDecoder().raw_decode(line, at + len("backend_config="))
    return kernel.encode() in base64.b64decode(config["custom_call_config"]["body"])


@pytest.mark.parametrize("f", [3, 128])
def test_newton_loop_writes_no_factor_sized_array(one_chip, pallas_backend, f):
    """The factors are laid out once per factorisation: the Newton loop body
    reads them from its carry and writes nothing of their size, at a width
    the 8-row tile pads (3, Robertson) and at one it does not (128)."""
    def solve(y0):
        return solve_ivp(allen_cahn, y0, None, t_start=0.0, t_end=1.0,
                         method="kvaerno5", rtol=1e-4, atol=1e-7,
                         args=float((f + 1) ** 2), max_steps=4000)

    hlo = _compile(solve, (_f32(512, f),), one_chip).as_text()
    bodies = [lines for lines in _computations(hlo)
              if any(_runs_kernel(ln, "_newton_iter_kernel") for ln in lines)]
    assert bodies
    for lines in bodies:
        kernel = next(ln for ln in lines if _runs_kernel(ln, "_newton_iter_kernel"))
        # The kernel's first operand is the factors, as it reads them.
        factor = re.search(r"operand_layout_constraints=\{(\w+\[[\d,]+\])", kernel)[1] + "{"
        written = [ln for ln in lines
                   if ln.split(" = ", 1)[-1].startswith(factor)
                   and not re.search(r"\} (parameter|get-tuple-element|bitcast)\(", ln)]
        assert not written, written


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
