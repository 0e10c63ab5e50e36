"""host_ms_per_iter: the engine loop's host work per iteration, planning
plus buffer fills and dispatch (the engine's ``plan_s`` and ``fill_s``),
mean over the iterations dispatched in the window (engine loop)."""


def read(run):
    if not run.iters:
        return None
    return 1e3 * sum(r["plan_s"] + r["fill_s"] for r in run.iters) / \
        len(run.iters)
