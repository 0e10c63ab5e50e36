"""Percentile and window arithmetic for the end-to-end metrics.

All times are seconds on the harness's clock (``time.perf_counter``). A
request is *due* at the window's start plus its offset; its blocks are
stamped when their last masked position reaches the host (the engine's
streaming callback). The window is ``[t0, t_end]``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List

INF = math.inf


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the sample at or below it. Infinite values sort last. Empty -> nan."""
    if not values:
        return math.nan
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def beyond(values: List[float], q: float) -> int:
    """How many samples lie above the ``q``-th percentile."""
    p = percentile(values, q)
    return sum(1 for v in values if v > p)


@dataclass
class ReqRecord:
    """One request of the window as the client sees it."""
    rid: int
    due: float                      # absolute, harness clock
    n_blocks: int
    prompt_len: int
    failed: bool = False            # rejected or shed by the engine
    blocks: List[float] = field(default_factory=list)  # block done times

    @property
    def finished(self) -> bool:
        return len(self.blocks) >= self.n_blocks


def ttfb_samples(reqs: List[ReqRecord], t_end: float,
                 tail_guard_s: float) -> tuple:
    """Time from due to the first whole block, one sample per request due
    in the window. A failed request, or one still without its first block
    at ``t_end`` though due more than ``tail_guard_s`` before it, is
    infinitely late; one due later than that and still waiting is left
    out. Returns (samples, n_left_out)."""
    out, left = [], 0
    for r in reqs:
        if r.due > t_end:
            continue
        if r.failed:
            out.append(INF)
        elif r.blocks and r.blocks[0] <= t_end:
            out.append(r.blocks[0] - r.due)
        elif r.due < t_end - tail_guard_s:
            out.append(INF)
        else:
            left += 1
    return out, left


def block_gap_samples(reqs: List[ReqRecord], t_end: float) -> List[float]:
    """Gaps between successive whole blocks of one request, over every
    request. A gap still open at ``t_end`` (a block done, the next not, the
    request not finished) counts, measured to ``t_end``. A failed request
    contributes one infinite gap: it misses every limit."""
    out: List[float] = []
    for r in reqs:
        if r.due > t_end:
            continue
        if r.failed:
            out.append(INF)
            continue
        done = [t for t in r.blocks if t <= t_end]
        out += [b - a for a, b in zip(done, done[1:])]
        if done and len(done) < r.n_blocks:
            out.append(t_end - done[-1])
    return out


def finite(x: float, cap: float = 1e9) -> float:
    """JSON has no infinity: an infinite percentile is written as ``cap``."""
    return cap if math.isinf(x) else x



def queue_wait_samples(run, tail_guard_s: float) -> tuple:
    """Time from due to admission (``Request.t_admitted - arrival``, both on
    the engine's clock, whose zero is where its loop started), one sample
    per request due in the window. One not admitted when the window closed
    is infinitely late if it was due more than ``tail_guard_s`` before the
    end, and left out otherwise. Returns (samples, n_left_out)."""
    end = run.t_end - run.t0 - run.late_s       # the window's end, engine
    out, left = [], 0
    for r in run.reqs:
        due = run.arrival[r.rid]
        if due > end:
            continue
        adm = run.admitted.get(r.rid)
        if adm is not None and adm <= end:
            out.append(adm - due)
        elif r.failed or due < end - tail_guard_s:
            out.append(INF)
        else:
            left += 1
    return out, left
