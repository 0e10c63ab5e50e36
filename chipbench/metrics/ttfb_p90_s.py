"""ttfb_p90_s: from a request's due time to the host holding its first
block with no masked position, 90th percentile over every request due in
the window (host clock). A request still without that block at the end is
infinitely late if it was due more than the mix's ``tail_guard_s`` before
the end, and left out otherwise; a failed request is infinitely late."""
from chipbench import measure as M


def read(run):
    xs, _ = M.ttfb_samples(run.reqs, run.t_end,
                           run.traffic.get("tail_guard_s", 0.0))
    return M.percentile(xs, 90) if xs else None
