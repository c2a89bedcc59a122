"""Device time of the linear-solve kernels per batched Newton trip (ms):
the traced window's linear_solve role time over the launches of its
solves.  A solve launches one batched linear solve per Newton trip, and its
longest-running instance rides every trip, so a solve's launches are the
max of n_newton_trips over its batch."""


def read(run):
    seconds = run.trace.role_s.get("linear_solve", 0.0) if run.trace else 0.0
    trips = sum(int(s.stats["n_newton_trips"].max()) for s in run.solves
                if "n_newton_trips" in s.stats)
    return 1000.0 * seconds / trips if seconds > 0 and trips else None
