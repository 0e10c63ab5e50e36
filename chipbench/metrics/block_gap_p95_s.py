"""block_gap_p95_s: time between successive whole blocks of one request,
95th percentile over all gaps in the window (host clock). A gap still open
at the window's end counts, measured to the end."""
from chipbench import measure as M


def read(run):
    xs = M.block_gap_samples(run.reqs, run.t_end)
    return M.percentile(xs, 95) if xs else None
