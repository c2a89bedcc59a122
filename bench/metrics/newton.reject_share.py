"""Share of step attempts rejected because the instance's own Newton solve
failed, diverged or hit its iteration cap (%): sum of n_newton_rejects over
sum of n_steps, over every instance of the window's solves."""


def read(run):
    rejects = steps = 0
    for s in run.solves:
        if "n_newton_rejects" in s.stats:
            rejects += int(s.stats["n_newton_rejects"].sum())
            steps += int(s.stats["n_steps"].sum())
    return 100.0 * rejects / steps if steps else None
