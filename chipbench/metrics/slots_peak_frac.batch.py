"""slots_peak_frac.batch: the most requests holding a slot at once in the
window (sampled at every commit event) over the slots the memory plan
allocated (slot pool)."""


def read(run):
    if not run.running_peak or not run.slots_allocated:
        return None
    return run.running_peak / run.slots_allocated
