"""resident_mean.batch: requests stepped per iteration (Refresh plus Reuse),
mean over the iterations dispatched in the window (scheduler)."""


def read(run):
    if not run.iters:
        return None
    return sum(r["n_refresh"] + r["n_reuse"] for r in run.iters) / \
        len(run.iters)
