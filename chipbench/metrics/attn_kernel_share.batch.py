"""attn_kernel_share.batch: device time of the ``flash_varlen`` Pallas
kernel's events (``flash_varlen_call``, the packed Refresh self-attention,
and ``flash_varlen_cross_call``, the Reuse cross-attention) over the
device's busy time (device trace)."""

KERNEL = "flash_varlen"


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    t = run.trace.kernel_time(KERNEL)
    return t / run.trace.busy_s if t > 0 else None
