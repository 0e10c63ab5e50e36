"""setup_s: from the process's start to the first due request: imports,
weights made on the device, the engine's warmup, every Refresh shape of the
mix and the warm-up bursts (host clock)."""


def read(run):
    return run.setup["setup_s"]
