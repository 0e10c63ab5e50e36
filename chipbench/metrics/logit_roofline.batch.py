"""logit_roofline.batch: the fused logit/argmax kernel's share of its
roofline, in percent: the least time ``max(F / peak FLOP/s, B / peak
bytes/s)`` over the summed device time of its events. F is ``2 D V`` per
real logit row; B is the ``V x D`` table read once per row tile that holds
a real row, plus the real hidden rows. Work counts from the iterations the
window synced; padding rows do no work here, so the share can only
under-read (kernels)."""
from chipbench import work as W

KERNEL = "logit_argmax"
T_TILE = 256        # the kernel's row tile


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.kernel_time(KERNEL)
    if t <= 0:
        return None
    d = run.dims
    tile = min(T_TILE, run.serve.max_num_logits)
    rows = tiles = 0
    for r in run.iters:
        if r.get("sync_s", 0.0) <= 0.0:
            continue            # not synced in the window
        n = r["logit_tokens_real"]
        rows += n
        m = run.serve.max_num_logits
        for off in range(0, n, m):
            tiles += -(-min(m, n - off) // tile)
    if not rows:
        return None
    flops = rows * W.logit_flops_per_row(d)
    nbytes = tiles * W.logit_call_bytes(d, 0) + rows * d["d_model"] * W.BYTES
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
