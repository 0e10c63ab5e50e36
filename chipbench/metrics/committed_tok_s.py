"""committed_tok_s: committed output tokens whose values reached the host
inside the window, over the window (host clock)."""


def read(run):
    n = sum(e.n for evs in run.events.values() for e in evs
            if e.t <= run.t_end)
    return n / run.seconds
