"""mfu.batch: model operations of the window's real work over the window
times the chip's peak, in percent (the whole step). Each committed step of
each request counts once: a Refresh step the whole sequence through every
layer with attention over it, a Reuse step the block against its kept
positions, each the block's logit rows; padded buckets and preemption
recompute never count (device, host-clock window)."""
from chipbench import work as W


def read(run):
    if run.peaks is None:
        return None
    d, tr = run.dims, run.traffic
    sb, ri = tr["block_size"], tr["refresh_interval"]
    retain = run.retain
    total = {r.rid: r.prompt_len + r.n_blocks * sb for r in run.reqs}
    f = 0.0
    for rid, evs in run.events.items():
        for e in evs:
            if e.t > run.t_end:
                continue
            ph = "refresh" if e.step == 0 or (ri and e.step % ri == 0) \
                else "reuse"
            f += W.step_flops(d, ph, total[rid], sb, retain)
    return 100.0 * f / (run.seconds * run.peaks["bf16_flops_per_s"])
