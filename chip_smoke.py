#!/usr/bin/env python3
"""Smoke test of the solver's main paths on a TPU.

    python3 chip_smoke.py             # one chip: batch, network field, stiff, events, served
    python3 chip_smoke.py --chips 4   # only the multi-chip path: sharded_solve and
                                      # 4-device serving, each against one device

Every phase goes through the entry points users call (``CompiledSolver``,
``SolveService``, ``sharded_solve``) on the compiled Pallas kernel backend,
with seeded data at the sizes users run.  Each phase prints one JSON line:
its shapes, compile and run seconds (runs end in ``block_until_ready``),
per-instance status counts, an ``n_steps`` summary, and its deviations from
the same solve on the ``ref`` backend (plain XLA, no Pallas) and, where one
exists, from the closed form -- each next to the bound it is held to.  The
last line is ``{"ok": true, "device": {...}}``, printed only when every
check of every phase passed; otherwise the script exits 1.  It exits 2,
before any phase, when JAX sees no TPU or the kernel backend is not
``pallas``.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from benchmarks.common import vdp
from benchmarks.stiff_bench import allen_cahn, robertson
from repro.core import (
    AutoDiffAdjoint,
    CompiledSolver,
    Event,
    FusedFallbackReason,
    SolveRequest,
    SolveService,
    Status,
    sharded_solve,
)
from repro.core.serving import next_pow2
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache

SEED = 0
G = 9.81  # bouncing ball gravity
VDP_MU = 2.0

# Phase sizes (paper Table 3 for the batch phase; the rest sized so the
# chip does a deployment's worth of work).
SIZES = dict(
    batch=dict(batch=65_536, n_eval=200),
    network=dict(batch=8_192, features=256, hidden=1_024),
    allen_cahn=dict(batch=4_096, features=128),
    robertson=dict(batch=65_536),
    events=dict(batch=65_536),
    served=dict(requests=2_048, max_batch=256, features=(2, 32, 128),
                grids=(0, 8, 200)),
    sharded=dict(batch=65_536, n_eval=200),
    served_multi=dict(requests=256, max_batch=16, features=(2, 32), grids=(0, 8)),
)


# ------------------------------------------------------------------ fields


def mlp_field(t, y, p):
    """2-layer tanh MLP vector field; float32 matmuls at full precision."""
    hi = jax.lax.Precision.HIGHEST
    h = jnp.tanh(jnp.dot(y, p["w1"], precision=hi) + p["b1"])
    return jnp.dot(h, p["w2"], precision=hi) + p["b2"]


def ball(t, y, args):
    return jnp.stack((y[..., 1], jnp.full_like(y[..., 1], -G)), axis=-1)


def decay_rates(f):
    """Per-feature decay rates of the served field, 1 .. 100 (feature 0 is 1)."""
    return 10.0 ** np.linspace(0.0, 2.0, f) if f > 1 else np.ones(1)


def decay(t, y, args):
    return -decay_rates(y.shape[-1]).astype(np.float32) * y


BALL_LEVEL = 1.0  # the ball's non-terminal event: falling through this height
DECAY_STOP, DECAY_MARK = 0.3, 0.6  # served events on feature 0


def _ground(t, y, args):
    return y[0]


def _level(t, y, args):
    return y[0] - BALL_LEVEL


def _decay_stop(t, y, args):
    return y[0] - DECAY_STOP


def _decay_mark(t, y, args):
    return y[0] - DECAY_MARK


# ------------------------------------------------------------------ plumbing


class Check:
    """One phase's report line and its failed checks."""

    def __init__(self, phase, **shapes):
        self.line = {"phase": phase, "shapes": shapes}
        self.failures = []

    def expect(self, cond, what):
        if not cond:
            self.failures.append(what)

    def bound(self, name, value, limit):
        value = float(value)
        self.line[name] = value
        self.line[name + "_bound"] = limit
        self.expect(value <= limit, f"{name} {value:.3g} exceeds {limit:g}")  # NaN fails too

    def timings(self, compile_s, run_s):
        self.line["compile_s"] = compile_s
        self.line["run_s"] = run_s

    def statuses(self, status, n_steps):
        status = np.asarray(status)
        values, counts = np.unique(status, return_counts=True)
        self.line["status"] = {Status(int(v)).name: int(c) for v, c in zip(values, counts)}
        n_steps = np.asarray(n_steps)
        self.line["n_steps"] = {"min": int(n_steps.min()), "mean": float(n_steps.mean()),
                                "max": int(n_steps.max())}
        good = np.isin(status, (Status.SUCCESS.value, Status.EVENT.value))
        self.expect(bool(good.all()), f"statuses {self.line['status']}")

    def fused(self, stats):
        reason = np.asarray(stats["fused_fallback_reason"])
        self.line["fused"] = FusedFallbackReason(int(reason[0])).name
        self.expect(bool((reason == FusedFallbackReason.ENGAGED).all()),
                    f"fused path not engaged: {np.unique(reason)}")
        self.expect(np.array_equal(stats["n_fused_steps"], stats["n_steps"]),
                    "n_fused_steps != n_steps")

    def fail(self, e):
        self.failures.append(f"{type(e).__name__}: {e}")


def emit(check, results):
    check.line["ok"] = not check.failures
    if check.failures:
        check.line["failures"] = check.failures
    print(json.dumps(check.line), flush=True)
    results.append(check.line["ok"])


@contextlib.contextmanager
def phase(name, results, **shapes):
    check = Check(name, **shapes)
    try:
        yield check
    except Exception as e:  # noqa: BLE001 -- reported in the phase line, fails the run
        check.fail(e)
    emit(check, results)


@contextlib.contextmanager
def kernel_backend(name):
    old = ops.backend()
    ops.set_backend(name)
    try:
        yield
    finally:
        ops.set_backend(old)


def solve_once(solver, f, y0, **kw):
    """AOT-compile one ``CompiledSolver`` program for these operands on the
    active kernel backend, run it once; returns (host Solution, compile s,
    run s).  Operands are host arrays, placed on the device before the clock
    starts (``y0`` is donated in final-state solves, so each call gets its
    own copy)."""
    spec = lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
    t0 = time.perf_counter()
    exe = solver.compile(f, spec(y0), **jax.tree.map(spec, kw))
    compile_s = time.perf_counter() - t0
    y0_dev, kw_dev = jax.device_put((y0, kw))
    jax.block_until_ready((y0_dev, kw_dev))
    t0 = time.perf_counter()
    sol = exe(y0_dev, **kw_dev).block_until_ready()
    run_s = time.perf_counter() - t0
    return jax.tree.map(np.asarray, sol), compile_s, run_s


def max_dev(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


def run_problem(results, name, f, y0, kw, *, method, rtol, atol, bound,
                fused_modes=(False, True), max_steps=10_000, events=None,
                exact=None, shapes=None):
    """One problem through ``CompiledSolver``: a ``ref``-backend reference,
    then one phase line per fused mode on the active backend."""
    def solver(fused):
        return CompiledSolver(AutoDiffAdjoint(method, rtol=rtol, atol=atol,
                                              max_steps=max_steps, events=events,
                                              fused=fused))

    unfused = solver(False)  # one cache for both backends: the key tells them apart
    with kernel_backend("ref"):
        ref, _, _ = solve_once(unfused, f, y0, **kw)
    for fused in fused_modes:
        with phase(f"{name}/{'fused' if fused else 'unfused'}", results,
                   **(shapes or {"y0": list(np.shape(y0))})) as check:
            sol, c_s, r_s = solve_once(solver(True) if fused else unfused, f, y0, **kw)
            check.timings(c_s, r_s)
            check.statuses(sol.status, sol.stats["n_steps"])
            check.expect(bool(np.isfinite(sol.ys).all()), "non-finite ys")
            check.bound("dev_ref", max_dev(sol.ys, ref.ys), bound)
            if events is not None:
                fired = sol.event_mask & ref.event_mask
                check.expect(np.array_equal(sol.event_mask, ref.event_mask),
                             "event masks differ from ref")
                check.bound("dev_ref_event_t", max_dev(sol.event_t[fired],
                                                       ref.event_t[fired]), bound)
            if fused:
                check.fused(sol.stats)
            if exact is not None:
                exact(check, sol)


# ------------------------------------------------------------------ one-chip phases


def vdp_batch(batch, n_eval):
    """Seeded VdP initial states near the limit cycle and an eval grid over
    about one cycle."""
    rng = np.random.default_rng(SEED)
    y0 = (np.array([2.0, 0.0]) + 0.1 * rng.standard_normal((batch, 2))).astype(np.float32)
    t_cycle = (3.0 - 2.0 * np.log(2.0)) * VDP_MU + 2.0 * np.pi / VDP_MU ** (1 / 3)
    return y0, np.linspace(0.0, t_cycle, n_eval, dtype=np.float32)


def phase_batch(results, batch, n_eval):
    """Paper Table 3: VdP mu=2, dopri5, rtol=atol=1e-5, one cycle, 200 points."""
    y0, t_eval = vdp_batch(batch, n_eval)
    # Bound: at rtol = atol = 1e-5 the ref solve of these instances is itself
    # up to 2.9e-3 from an f64 rtol = 1e-11 solve (XLA:CPU), and a rounding
    # difference that moves one step-size decision moves the whole trajectory
    # by up to that error: two roundings may differ by twice it.
    run_problem(results, "batch", vdp, y0, dict(t_eval=t_eval, args=np.float32(VDP_MU)),
                method="dopri5", rtol=1e-5, atol=1e-5, bound=6e-3, max_steps=2_000,
                shapes={"y0": [batch, 2], "t_eval": [n_eval], "ys": [batch, n_eval, 2]})


def phase_network(results, batch, features, hidden):
    """A seeded 2-layer tanh MLP field at f=256 (the feature-tiled fused step)."""
    rng = np.random.default_rng(SEED + 1)
    params = {
        "w1": rng.standard_normal((features, hidden)) / np.sqrt(features),
        "b1": 0.1 * rng.standard_normal(hidden),
        "w2": rng.standard_normal((hidden, features)) / np.sqrt(hidden),
        "b2": 0.1 * rng.standard_normal(features),
    }
    params = {k: v.astype(np.float32) for k, v in params.items()}
    y0 = rng.standard_normal((batch, features)).astype(np.float32)
    t = np.zeros(batch, np.float32), np.ones(batch, np.float32)
    run_problem(results, "network", mlp_field, y0,
                dict(t_start=t[0], t_end=t[1], args=params),
                method="tsit5", rtol=1e-4, atol=1e-6, bound=2e-3,
                shapes={"y0": [batch, features], "hidden": hidden})


def phase_stiff(results, allen_cahn_size, robertson_size):
    """kvaerno5 on Allen-Cahn (f=128) and Robertson kinetics (f=3)."""
    batch, n = allen_cahn_size["batch"], allen_cahn_size["features"]
    rng = np.random.default_rng(SEED + 2)
    x = np.linspace(0.0, 1.0, n + 2)[1:-1]
    amp = rng.uniform(0.5, 1.5, batch)
    y0 = (amp[:, None] * np.sin(np.pi * x)[None, :]).astype(np.float32)
    zeros, ends = np.zeros(batch, np.float32), np.full(batch, 0.25, np.float32)
    run_problem(results, "stiff/allen_cahn", allen_cahn, y0,
                dict(t_start=zeros, t_end=ends, args=np.float32((n + 1) ** 2)),
                method="kvaerno5", rtol=1e-4, atol=1e-7, bound=1e-3, max_steps=4_000)

    batch = robertson_size["batch"]
    total = rng.uniform(0.5, 1.5, batch).astype(np.float32)
    y0 = np.stack([total, np.zeros_like(total), np.zeros_like(total)], axis=1)

    def conserved(check, sol):  # y1 + y2 + y3 is a linear invariant
        check.bound("dev_invariant", np.max(np.abs(sol.ys.sum(axis=1) - total) / total), 1e-4)

    run_problem(results, "stiff/robertson", robertson, y0,
                dict(t_start=np.zeros(batch, np.float32),
                     t_end=np.full(batch, 100.0, np.float32)),
                method="kvaerno5", rtol=1e-5, atol=1e-8, bound=1e-4, max_steps=4_000,
                exact=conserved)


def phase_events(results, batch):
    """Bouncing ball: terminal ground event + non-terminal level crossing."""
    rng = np.random.default_rng(SEED + 3)
    h0 = rng.uniform(2.0, 10.0, batch)
    v0 = rng.uniform(-2.0, 2.0, batch)
    y0 = np.stack([h0, v0], axis=1).astype(np.float32)
    events = (Event(_ground, terminal=True, direction=-1.0),
              Event(_level, terminal=False, direction=-1.0))
    # Closed form: h(t) = h0 + v0 t - g t^2 / 2 falls through level L at
    # t = (v0 + sqrt(v0^2 + 2 g (h0 - L))) / g.
    t_hit = lambda level: (v0 + np.sqrt(v0**2 + 2.0 * G * (h0 - level))) / G

    def closed_form(check, sol):
        check.expect(bool((sol.status == Status.EVENT.value).all()), "a ball never landed")
        check.expect(bool(sol.event_mask.all()), "an event did not fire")
        check.bound("dev_exact_event_t", max(max_dev(sol.event_t[:, 0], t_hit(0.0)),
                                             max_dev(sol.event_t[:, 1], t_hit(BALL_LEVEL))),
                    1e-4)
        check.bound("dev_exact_y", max_dev(sol.ys, np.stack(
            [np.zeros(batch), v0 - G * t_hit(0.0)], axis=1)), 1e-3)

    run_problem(results, "events", ball, y0,
                dict(t_start=np.zeros(batch, np.float32),
                     t_end=np.full(batch, 10.0, np.float32)),
                method="dopri5", rtol=1e-6, atol=1e-6, bound=1e-4, events=events,
                fused_modes=(True,), exact=closed_form,
                shapes={"y0": [batch, 2], "events": len(events)})


def served_stream(n_requests, features, grids, *, seed):
    """A seeded stream of single-instance decay requests: mixed widths, eval
    grids and methods; one request in eight carries two events on feature 0
    (terminal at ``DECAY_STOP``, non-terminal at ``DECAY_MARK``)."""
    events = (Event(_decay_stop, terminal=True, direction=-1.0),
              Event(_decay_mark, terminal=False, direction=-1.0))
    with_events = {m: AutoDiffAdjoint(m, events=events) for m in ("dopri5", "kvaerno5")}
    rng = np.random.default_rng(seed)
    stream = []
    for i in range(n_requests):
        f = int(rng.choice(features))
        n_eval = int(rng.choice(grids))
        method = str(rng.choice(["dopri5", "kvaerno5"]))
        t1 = float(rng.uniform(0.5, 2.0))
        stream.append(SolveRequest(
            decay, rng.uniform(0.5, 1.5, f).astype(np.float32), 0.0, t1,
            t_eval=np.linspace(0.0, t1, n_eval) if n_eval else None,
            method=with_events[method] if i % 8 == 0 else method,
            rtol=1e-5, atol=1e-7,
        ))
    return stream


def _bucket_classes(stream, max_batch):
    """One example request per bucket, with the batch classes that bucket
    will launch when the whole stream is queued and flushed."""
    groups = {}
    for r in stream:
        n_eval = None if r.t_eval is None else next_pow2(len(r.t_eval))
        key = (len(r.y0), n_eval, r.method if isinstance(r.method, str) else id(r.method))
        groups.setdefault(key, []).append(r)
    out = []
    for reqs in groups.values():
        full, rest = divmod(len(reqs), max_batch)
        classes = ([max_batch] if full else []) + ([next_pow2(rest)] if rest else [])
        out.append((reqs[0], sorted(set(classes))))
    return out


def serve(stream, *, max_batch, devices, prewarm=False, max_inflight=4, probe=None):
    """Serve ``stream`` through one ``SolveService``; returns (service,
    results, prewarm s, serve s).  A result is the host ``Solution`` view or
    the exception its future raised.  ``probe(service)`` runs after the
    final flush, while the batches are in flight."""
    svc = SolveService(max_batch=max_batch, max_delay=None, max_inflight=max_inflight,
                       devices=devices)
    t0 = time.perf_counter()
    if prewarm:
        for example, classes in _bucket_classes(stream, max_batch):
            svc.prewarm(example, batch_classes=classes)
    prewarm_s = time.perf_counter() - t0
    misses = svc.stats()["cache_misses"]
    t0 = time.perf_counter()
    futures = [svc.submit(r) for r in stream]
    svc.flush()
    if probe is not None:
        probe(svc)
    out = []
    for fut in futures:
        try:
            out.append(fut.result())
        except Exception as e:  # noqa: BLE001 -- a failed request fails the phase
            out.append(e)
    serve_s = time.perf_counter() - t0
    if prewarm and svc.stats()["cache_misses"] != misses:
        raise AssertionError(f"cache misses grew after prewarm: {misses} -> "
                             f"{svc.stats()['cache_misses']}")
    return svc, out, prewarm_s, serve_s


def _decay_exact(req, sol):
    """Max deviation of one served decay solution from its closed form, on
    the points the solver reached (dense output stops at a terminal event)."""
    rates = decay_rates(len(req.y0))
    if req.t_eval is None:
        t, ys = sol.ts[:1], sol.ys[:1]
    else:
        stop = sol.event_t[0, 0] if sol.event_t is not None and sol.event_mask[0, 0] else req.t1
        keep = req.t_eval < stop - 1e-4
        t, ys = req.t_eval[keep], sol.ys[0, keep]
    exact = req.y0[None, :] * np.exp(-rates[None, :] * np.asarray(t, np.float64)[:, None])
    return max_dev(ys, exact) if len(t) else 0.0


def check_served(check, stream, got, ref=None, *, bound):
    """Statuses, closed form and (when given) the ``ref`` results of a
    served stream; ``got``/``ref`` hold one result or exception per request."""
    errors = [g for g in got if isinstance(g, Exception)]
    check.expect(not errors, f"{len(errors)} requests raised, first: {errors[:1]}")
    pairs = [(r, g) for r, g in zip(stream, got) if not isinstance(g, Exception)]
    if not pairs:
        return
    check.statuses(np.concatenate([s.status for _, s in pairs]),
                   np.concatenate([s.stats["n_steps"] for _, s in pairs]))
    check.bound("dev_exact", max(_decay_exact(r, s) for r, s in pairs), bound)
    ev = [(r, s) for r, s in pairs if s.event_t is not None and s.event_mask[0, 0]]
    check.line["n_event_stops"] = len(ev)
    if ev:
        check.bound("dev_exact_event_t", max(
            abs(float(s.event_t[0, 0]) - np.log(r.y0[0] / DECAY_STOP)) for r, s in ev),
            bound)
    if ref is not None:
        check.expect(not any(isinstance(g, Exception) for g in ref), "a ref request raised")
        check.bound("dev_ref", max(max_dev(g.ys, h.ys) for g, h in zip(got, ref)
                                   if not isinstance(g, Exception)
                                   and not isinstance(h, Exception)), bound)


def phase_served(results, requests, max_batch, features, grids):
    """``SolveService`` after ``prewarm`` on a seeded mixed stream."""
    devices = jax.devices()[:1]
    stream = served_stream(requests, features, grids, seed=SEED + 4)
    with phase("served", results, requests=requests, max_batch=max_batch,
               features=list(features), grids=list(grids),
               methods=["dopri5", "kvaerno5"], events=2) as check:
        with kernel_backend("ref"):
            _, ref, _, _ = serve(stream, max_batch=max_batch, devices=devices)
        svc, got, prewarm_s, serve_s = serve(stream, max_batch=max_batch,
                                             devices=devices, prewarm=True)
        check.timings(prewarm_s, serve_s)
        stats = svc.stats()
        check.line["n_programs"] = stats["n_programs"]
        check.line["n_batches"] = stats["n_batches"]
        check.line["cache_misses"] = stats["cache_misses"]
        check_served(check, stream, got, ref, bound=1e-3)


# ------------------------------------------------------------------ multi-chip phases


def phase_sharded(results, batch, n_eval, devices):
    """``sharded_solve`` of the batch phase over a mesh, against the
    single-device ``jax.jit`` program (explicit stepper: bitwise)."""
    y0, t_eval = vdp_batch(batch, n_eval)
    mu = np.float32(VDP_MU)
    driver = AutoDiffAdjoint("dopri5", rtol=1e-5, atol=1e-5, max_steps=2_000)
    mesh = Mesh(np.array(devices), ("data",))
    with phase("sharded", results, y0=[batch, 2], t_eval=[n_eval],
               devices=len(devices)) as check:
        def timed(fn):
            t0 = time.perf_counter()
            out = fn().block_until_ready()
            return out, time.perf_counter() - t0

        run = lambda: sharded_solve(mesh, vdp, y0, t_eval, args=mu, solver=driver)
        _, first_s = timed(run)
        sol, run_s = timed(run)
        check.timings(first_s - run_s, run_s)
        one = jax.jit(lambda y, te, a: driver.solve(vdp, y, te, args=a))
        ref = one(*jax.device_put((y0, t_eval, mu), devices[0])).block_until_ready()
        check.expect(ref.ys.devices() == {devices[0]}, "one-device program left device 0")
        check.expect(sol.ys.sharding.device_set == set(devices), "sharded ys not on the mesh")
        sol, ref = jax.tree.map(np.asarray, (sol, ref))
        check.statuses(sol.status, sol.stats["n_steps"])
        fields = {"ys": (sol.ys, ref.ys), "ts": (sol.ts, ref.ts),
                  "status": (sol.status, ref.status),
                  **{k: (sol.stats[k], ref.stats[k]) for k in ("n_steps", "n_accepted")}}
        differ = [k for k, (a, b) in fields.items() if not np.array_equal(a, b)]
        check.line["bitwise_equal"] = not differ
        check.expect(not differ, f"sharded differs from one device in {differ}")


def phase_served_multi(results, requests, max_batch, features, grids, devices):
    """A served stream round-robined over ``devices``, against the same
    stream pinned to one device: bitwise, each batch on its own device."""
    stream = served_stream(requests, features, grids, seed=SEED + 5)
    with phase("served_multi", results, requests=requests, max_batch=max_batch,
               devices=len(devices)) as check:
        placed = []

        def probe(svc):  # every launched batch, its device and its buffers' devices
            for rec in svc._inflight:
                leaves = jax.tree.leaves(rec.sol)
                placed.append((rec.device, {d for x in leaves for d in x.devices()}))

        _, one, _, _ = serve(stream, max_batch=max_batch, devices=devices[:1],
                             max_inflight=len(stream))
        _, got, _, serve_s = serve(stream, max_batch=max_batch, devices=devices,
                                   max_inflight=len(stream), probe=probe)
        check.timings(0.0, serve_s)
        check_served(check, stream, got, bound=1e-3)
        used = {str(d) for d, _ in placed}
        check.line["devices_used"] = sorted(used)
        check.expect(len(used) == len(devices), f"batches ran on {sorted(used)} only")
        check.expect(all(on == {d} for d, on in placed), "a batch's buffers left its device")
        differ = 0
        for a, b in zip(got, one):
            if isinstance(a, Exception) or isinstance(b, Exception):
                differ += 1
                continue
            same = (np.array_equal(a.ys, b.ys) and np.array_equal(a.ts, b.ts)
                    and np.array_equal(a.status, b.status))
            differ += not same
        check.line["bitwise_equal"] = differ == 0
        check.expect(differ == 0, f"{differ} requests differ from the one-device service")


# ------------------------------------------------------------------ entry point


def run_one_chip(results):
    phase_batch(results, **SIZES["batch"])
    phase_network(results, **SIZES["network"])
    phase_stiff(results, SIZES["allen_cahn"], SIZES["robertson"])
    phase_events(results, **SIZES["events"])
    phase_served(results, **SIZES["served"])


def run_multi_chip(results, devices):
    phase_sharded(results, **SIZES["sharded"], devices=devices)
    phase_served_multi(results, **SIZES["served_multi"], devices=devices)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: every one-chip phase; 4: only the multi-chip path")
    opts = parser.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    if ops.backend() != "pallas":
        print(f"chip_smoke: kernel backend is {ops.backend()!r}, not 'pallas' "
              "(unset REPRO_KERNEL_BACKEND)", file=sys.stderr)
        return 2

    print(f"chip_smoke: compile cache in {enable_compile_cache()}", file=sys.stderr)
    results = []
    if opts.chips == 1:
        run_one_chip(results)
    else:
        run_multi_chip(results, devices[:opts.chips])
    if not results or not all(results):
        print(f"chip_smoke: {results.count(False)} of {len(results)} phases failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
