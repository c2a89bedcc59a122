"""Stiff suite: explicit vs implicit steppers on classic stiff problems.

Two problems where every instance of the batch is stiff -- the regime the
explicit-only solver could not touch (any stiff instance grinds at its
stability limit, the exact within-batch pathology the paper measures):

  robertson   the 3-species chemical kinetics IVP (rates spanning 9 orders
              of magnitude), t in [0, 100]
  vdp1000     Van der Pol with mu = 1000, t in [0, 20] (relaxation phase)

For each problem we run ``kvaerno5`` (SDIRK + batched masked Newton) and
``dopri5`` at the same tolerance and report wall time, accepted steps, Newton
iterations and Jacobian evaluations.  The explicit method gets a generous but
bounded step budget; when it hits the cap the step ratio reported is a lower
bound.

A third section times the diagonally-implicit step, fused and unfused, on
the ``interpret`` kernel backend -- the launch-count proxy tier, same as
``step_bench`` -- on a stiff Allen-Cahn method-of-lines problem.  Both paths
factor the chord matrix once per step attempt and substitute per Newton
iteration; ``fused=True`` differs only by the ``fused_step`` megakernel tail,
so the speedup row is informational and has no floor.

``REPRO_STIFF_SMOKE=1`` shrinks batch/horizons/budgets for CI smoke runs.
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import solve_ivp
from repro.kernels import ops

from .common import timed, vdp


def robertson(t, y, args):
    y1, y2, y3 = y[..., 0], y[..., 1], y[..., 2]
    r1 = -0.04 * y1 + 1e4 * y2 * y3
    r3 = 3e7 * y2 * y2
    return jnp.stack((r1, -r1 - r3, r3), axis=-1)


def allen_cahn(t, y, args):
    """Stiff 1D Allen-Cahn semidiscretization (Dirichlet): lam*Lap(y) + y - y^3."""
    lam = args
    up = jnp.concatenate([y[..., 1:], jnp.zeros_like(y[..., :1])], axis=-1)
    dn = jnp.concatenate([jnp.zeros_like(y[..., :1]), y[..., :-1]], axis=-1)
    return lam * (up - 2.0 * y + dn) + y - y**3


# (method, batch, n_feat, t_end) for the fused-DIRK section.
# One point keeps the interpret-tier suite affordable; n_feat is large enough
# that the factored system, not launch bookkeeping, is the per-iteration cost.
FUSED_POINTS = (("kvaerno5", 4, 32, 1.0),)


def _fused_dirk_rows(repeats=2):
    """Fused vs unfused DIRK steps/sec on the interpret (launch-proxy) backend.

    The suite normally runs under REPRO_KERNEL_BACKEND=ref in CI; this section
    pins the interpret backend itself (and restores the previous one) so the
    comparison always measures kernel launches, not the jnp oracle.
    """
    smoke = os.environ.get("REPRO_STIFF_SMOKE", "0") == "1"
    prev = ops.backend()
    out = []
    try:
        ops.set_backend("interpret")
        for method, batch, n_feat, t_end in FUSED_POINTS:
            if not smoke:
                t_end *= 5.0
            lam = float((n_feat + 1) ** 2)
            x = jnp.linspace(0.0, 1.0, n_feat + 2)[1:-1]
            amps = 1.0 + 0.2 * jnp.arange(batch, dtype=jnp.float32)
            y0 = amps[:, None] * jnp.sin(jnp.pi * x)[None, :]

            per_sec = {}
            for fused in (False, True):
                fn = jax.jit(
                    lambda y, fused=fused: solve_ivp(
                        allen_cahn, y, None, t_start=0.0, t_end=t_end,
                        method=method, atol=1e-7, rtol=1e-4, args=lam,
                        max_steps=4000, fused=fused)
                )
                sol = fn(y0)
                assert bool(np.all(np.asarray(sol.status) == 0)), (
                    f"fused-DIRK bench solve failed: {np.asarray(sol.status)}")
                if fused:
                    assert "n_fused_steps" in sol.stats, (
                        "fused implicit path did not engage")
                total, _ = timed(fn, y0, repeats=repeats, reduce="min")
                n_loop = int(np.max(np.asarray(sol.stats["n_steps"])))
                label = "fused" if fused else "unfused"
                per_sec[label] = n_loop / total
                out.append((f"fused_dirk/{method}/{label}_steps_per_sec",
                            per_sec[label], f"{n_loop} loop steps, b={batch} f={n_feat}"))
            out.append((f"fused_dirk/{method}/fused_speedup",
                        per_sec["fused"] / per_sec["unfused"],
                        "steps/sec ratio, fused over unfused"))
    finally:
        ops.set_backend(prev)
    return out


def _solve(f, y0, t_end, method, max_steps, args=None, atol=1e-8, rtol=1e-5):
    fn = jax.jit(
        lambda y: solve_ivp(f, y, None, t_start=0.0, t_end=t_end, method=method,
                            atol=atol, rtol=rtol, args=args, max_steps=max_steps)
    )
    sol = fn(y0)
    total, _ = timed(fn, y0, repeats=2)
    stats = {k: np.asarray(v) for k, v in sol.stats.items()}
    return sol, stats, total


def _problem_rows(tag, f, y0, t_end, args, imp_steps, exp_steps):
    out = []
    isol, istats, itime = _solve(f, y0, t_end, "kvaerno5", imp_steps, args)
    esol, estats, etime = _solve(f, y0, t_end, "dopri5", exp_steps, args)
    i_acc = float(istats["n_accepted"].mean())
    e_acc = float(estats["n_accepted"].mean())
    i_done = bool(np.all(np.asarray(isol.status) == 0))
    e_done = bool(np.all(np.asarray(esol.status) == 0))
    out.append((f"{tag}/kvaerno5/total_time", itime * 1e6,
                f"acc={i_acc:.0f} newton={istats['n_newton_iters'].mean():.0f} "
                f"jac={istats['n_jac_evals'].mean():.0f} finished={i_done}"))
    out.append((f"{tag}/dopri5/total_time", etime * 1e6,
                f"acc={e_acc:.0f} finished={e_done}"))
    if not i_done:
        # A truncated implicit solve would make the headline ratio bogus:
        # report the failure itself instead of a flattering number.
        out.append((f"{tag}/IMPLICIT_SOLVE_FAILED", 1.0,
                    f"statuses={np.asarray(isol.status).tolist()}"))
        return out
    ratio = e_acc / max(i_acc, 1.0)
    out.append((f"{tag}/explicit_vs_implicit_step_ratio", ratio,
                "x more accepted steps when explicit"
                + ("" if e_done else " (lower bound: capped)")))
    return out


def rows():
    smoke = os.environ.get("REPRO_STIFF_SMOKE", "0") == "1"
    batch = 4 if smoke else 32
    key = jax.random.PRNGKey(0)

    out = []
    # Van der Pol mu=1000: relaxation-oscillation stiffness.
    y0 = jnp.array([2.0, 0.0]) + 0.05 * jax.random.normal(key, (batch, 2))
    t_end = 2.0 if smoke else 20.0
    exp_cap = 4000 if smoke else 200_000
    out += _problem_rows("vdp1000", vdp, y0, t_end, 1000.0,
                         imp_steps=20_000, exp_steps=exp_cap)

    # Robertson kinetics: rate constants spanning 9 orders of magnitude.
    ry0 = jnp.tile(jnp.array([[1.0, 0.0, 0.0]]), (batch, 1))
    rt_end = 1.0 if smoke else 100.0
    rexp_cap = 4000 if smoke else 50_000
    out += _problem_rows("robertson", robertson, ry0, rt_end, None,
                         imp_steps=20_000, exp_steps=rexp_cap)

    # Fused-DIRK launch-proxy comparison (pins its own backend).
    out += _fused_dirk_rows()
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", nargs="?", const="BENCH_stiff.json", default=None,
                        metavar="PATH")
    opts = parser.parse_args()

    records = []
    print("name,value,derived")
    for name, v, extra in rows():
        print(f"stiff/{name},{v},{extra}", flush=True)
        records.append({"suite": "stiff", "name": name, "value": v, "derived": extra})

    if opts.json:
        from .common import calibration_us

        payload = {"bench": "stiff", "unit": "us for *_time rows",
                   "calibration_us": calibration_us(), "rows": records}
        with open(opts.json, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {len(records)} rows to {opts.json}")


if __name__ == "__main__":
    main()
