"""queue_wait_p90_s.chat: from a request's due time to its admission into
a slot (``Request.t_admitted - arrival``, both on the engine's clock), 90th
percentile over the requests due in the window; one not admitted by the
end counts as for ``ttfb_p90_s`` (scheduler)."""
from chipbench import measure as M


def read(run):
    xs, _ = M.queue_wait_samples(run, run.traffic.get("tail_guard_s", 0.0))
    return M.percentile(xs, 90) if xs else None
