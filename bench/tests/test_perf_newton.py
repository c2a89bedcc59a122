"""The Newton layer's readers: lane occupancy and reject share from the
program's n_newton_trips / n_newton_rejects counters, and the linear-solve
kernels' device time per trip from the trace."""

import jax
import numpy as np
import pytest

from bench.harness import cells, record, spec, trace

SEED = 2**31 + 13


def solve(steps, iters, trips, rejects):
    stats = {"n_steps": steps, "n_accepted": steps, "n_newton_iters": iters,
             "n_newton_trips": trips, "n_newton_rejects": rejects}
    return record.Solve(len(steps), 0, 2, 4, {k: np.asarray(v) for k, v in stats.items()})


def without(s, *keys):
    return record.Solve(s.batch, s.n_eval, s.features, s.itemsize,
                        {k: v for k, v in s.stats.items() if k not in keys})


A = solve([4, 2], [20, 6], [24, 10], [1, 0])
B = solve([3, 3, 1], [9, 12, 2], [14, 14, 5], [0, 2, 1])


def run(*solves, role_s=None):
    summary = None if role_s is None else trace.Summary(2.0, 1.9, role_s, [], [], 1)
    return record.Run("w", list(solves), None, summary, None)


def read(metric, r):
    return spec.reader(metric).read(r)


def test_lane_occupancy_is_needed_over_paid_iterations():
    assert read("newton.lane_occupancy", run(A, B)) == pytest.approx(
        100 * (26 + 23) / (34 + 33))


def test_reject_share_is_newton_rejects_over_attempts():
    assert read("newton.reject_share", run(A, B)) == pytest.approx(100 * (1 + 3) / (6 + 7))


def test_ms_per_trip_divides_by_each_solves_launches():
    # A solve launches its longest-running lane's trips: 24 and 14.
    r = run(A, B, role_s={"linear_solve": 0.19, "dense_output": 5.0})
    assert read("linear_solve.ms_per_trip", r) == pytest.approx(1000 * 0.19 / (24 + 14))


@pytest.mark.parametrize("metric", ["newton.lane_occupancy", "newton.reject_share",
                                    "linear_solve.ms_per_trip"])
def test_readers_without_their_counters_read_nothing(metric):
    parent = without(A, "n_newton_trips", "n_newton_rejects")
    explicit = without(A, "n_newton_iters", "n_newton_trips", "n_newton_rejects")
    role = {"linear_solve": 0.19}
    assert read(metric, run(parent, role_s=role)) is None
    assert read(metric, run(explicit, role_s=role)) is None
    assert read(metric, run(role_s=role)) is None


def test_ms_per_trip_without_linear_solve_time_reads_nothing():
    assert read("linear_solve.ms_per_trip", run(A)) is None
    assert read("linear_solve.ms_per_trip", run(A, role_s={})) is None
    assert read("linear_solve.ms_per_trip", run(A, role_s={"dense_output": 1.0})) is None


def test_readers_on_a_rehearsed_window(cell_of):
    cell = cell_of("allen_cahn.batch")
    runner = cells.make(cell, jax.devices()[0], rehearsal=True)
    runner.setup()
    win = runner.window(runner.prepare(SEED, 0.1), 0.1, cells.NoTracer())
    runner.check(win)
    for s in win.solves:
        st = s.stats
        # kvaerno5 has no static evaluation: every batched evaluation after
        # the two of the first step size is a Newton trip.
        assert st["n_newton_trips"].max() == st["n_f_evals"].max() - 2
        assert np.all(st["n_newton_trips"] >= st["n_newton_iters"])
        assert np.all(st["n_newton_rejects"] <= st["n_steps"] - st["n_accepted"])
    occupancy = read("newton.lane_occupancy", run(*win.solves))
    assert 0 < occupancy <= 100
    assert 0 <= read("newton.reject_share", run(*win.solves)) < 100
    launches = sum(int(s.stats["n_newton_trips"].max()) for s in win.solves)
    r = run(*win.solves, role_s={"linear_solve": 1.0})
    assert read("linear_solve.ms_per_trip", r) == pytest.approx(1000 / launches)
