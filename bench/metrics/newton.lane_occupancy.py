"""Share of the Newton layer's lane-iterations that a lane needed (%): sum
of n_newton_iters over sum of n_newton_trips, over every instance of the
window's solves.  A trip is one batched Newton iteration, one linear-solve
launch over the whole batch, and an instance pays for every trip of the
attempts it ran, whether it still iterated or had converged."""


def read(run):
    iters = trips = 0
    for s in run.solves:
        if "n_newton_trips" in s.stats:
            iters += int(s.stats["n_newton_iters"].sum())
            trips += int(s.stats["n_newton_trips"].sum())
    return 100.0 * iters / trips if trips else None
