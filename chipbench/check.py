"""The comparison that decides ``correct``.

Once the window has closed and the engine is released, three things are
compared, each with its limit:

* ``schedule_violations``: every commit event of every request in the
  window follows the mix's denoising schedule (``traffic/<mix>.json``):
  ``steps_per_block`` steps a block, step ``s`` committing
  ``ceil(masked / (steps_per_block - s))`` new positions (all that are
  left at the last step), committed positions never changing, blocks in
  order. Limit 0: no change can buy speed by committing more per step.
* ``compiles_in_window``: programs compiled or loaded from the cache
  while the window was open. Limit 0: nothing compiles inside it.
* ``logit_gap_max``: a sample of the requests the window finished — the
  longest always among them, the rest drawn from the seed — is replayed
  by the plain reference along the trajectory the timed path served. The
  number is the widest gap, over every committed position of the sample,
  by which the served token's logit lies below the reference's best at
  that step (the engine commits greedy tokens). Its limit is the cell's
  ``check.gap_limit``; PERF.md gives the readings it was set from.

With ``control`` the same comparison is also made for the reference in a
lower precision put in the program's place (``reference.py``); it has to
come out not correct.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from chipbench.reference import Reference, Trajectory


def commit_count(n_masked: int, steps_left: int) -> int:
    """The linear schedule: finish the block by its last step."""
    if steps_left <= 1:
        return n_masked
    return max(1, math.ceil(n_masked / steps_left))


def schedule_violations(events, block_size: int, steps_per_block: int,
                        mask_id: int) -> int:
    """Commit events of one request that break the schedule. A block in
    which a committed position returns to the mask (a preemption's
    rollback) starts over."""
    bad = 0
    prev = {}
    step = {}
    current = 0
    for e in events:
        b = e.block
        before = prev.get(b, np.full(block_size, mask_id, np.int32))
        after = np.asarray(e.tokens)
        was = before != mask_id
        if (was & (after == mask_id)).any():        # rolled back
            before = np.full(block_size, mask_id, np.int32)
            was = before != mask_id
            step[b] = 0
        s = step.get(b, 0)
        masked = int((before == mask_id).sum())
        new = int(((before == mask_id) & (after != mask_id)).sum())
        if (b != current or s >= steps_per_block or masked == 0
                or (after[was] != before[was]).any()
                or new != commit_count(masked, steps_per_block - s)):
            bad += 1
        step[b] = s + 1
        prev[b] = after.astype(np.int32)
        if b == current and not (after == mask_id).any():
            current = b + 1
    return bad


def trajectory(rid: int, prompt: np.ndarray, events, n_blocks: int,
               block_size: int, mask_id: int) -> Trajectory:
    """The served steps of one finished request, from its commit events."""
    steps: List[list] = [[] for _ in range(n_blocks)]
    prev = {}
    for e in events:
        before = prev.get(e.block, np.full(block_size, mask_id, np.int32))
        if ((before != mask_id) & (e.tokens == mask_id)).any():
            steps[e.block] = []                 # rolled back: starts over
            before = np.full(block_size, mask_id, np.int32)
        pos = np.nonzero((before == mask_id) & (e.tokens != mask_id))[0]
        steps[e.block].append((before, pos, e.tokens[pos].astype(np.int32)))
        prev[e.block] = e.tokens.astype(np.int32)
    return Trajectory(rid, np.asarray(prompt, np.int32),
                      n_blocks * block_size, steps)


def sample(finished: List[tuple], n: int, seed: int) -> List[int]:
    """``finished``: [(rid, total_len)]. The longest (lowest rid on a tie)
    and ``n - 1`` more drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda x: (-x[1], x[0]))
    first, rest = order[0][0], sorted(r for r, _ in order[1:])
    rng = np.random.default_rng([seed, 0x636B])
    k = min(n - 1, len(rest))
    pick = rng.choice(len(rest), size=k, replace=False) if k else []
    return [first] + [rest[i] for i in sorted(pick)]


def trajectories(run, prompts: Dict[int, np.ndarray], serve: dict,
                 seed: int, n: int) -> List[Trajectory]:
    fin = [(r.rid, r.prompt_len + r.n_blocks * serve["block_size"])
           for r in run.reqs if r.finished and r.blocks[-1] <= run.t_end]
    return [trajectory(rid, prompts[rid], run.events[rid],
                       next(r.n_blocks for r in run.reqs if r.rid == rid),
                       serve["block_size"], serve["mask_id"])
            for rid in sample(fin, n, seed)]


def _judge(gap: float, n_tok: int, bad: int, compiles: int,
           limit: float) -> dict:
    correct = bool(n_tok > 0 and gap <= limit and bad == 0
                   and compiles == 0)
    return dict(correct=correct, checks={
        "schedule_violations": {"value": bad, "limit": 0},
        "compiles_in_window": {"value": compiles, "limit": 0},
        "logit_gap_max": {"value": gap, "limit": limit},
        "tokens_checked": {"value": n_tok, "limit": 1},
    })


def check_run(run, prompts, params, serve: dict, seed: int, spec: dict,
              control: Optional[str] = None) -> dict:
    """The schedule of every request, the compiles in the window, and the
    reference's replay of the sample. Returns ``correct`` and the numbers
    compared with their limits; with ``control`` (a ``Reference`` quant
    mode) also ``control``: the same verdict with the control's gaps."""
    bad = sum(schedule_violations(evs, serve["block_size"],
                                  serve["steps_per_block"], serve["mask_id"])
              for evs in run.events.values())
    trajs = trajectories(run, prompts, serve, seed, spec["sample_requests"])
    ref = Reference(run.dims, params, serve)
    quants = (None,) if control is None else (None, control)
    gaps = [ref.gaps(t, quants) for t in trajs]
    n_tok = int(sum(len(g[0]) for g in gaps))
    limit = float(spec["gap_limit"])

    def widest(i):
        return float(max((g[i].max() for g in gaps if len(g[i])),
                         default=np.nan))
    out = _judge(widest(0), n_tok, bad, run.compiles_in_window, limit)
    out["sampled"] = [t.rid for t in trajs]
    if control is not None:
        out["control"] = _judge(widest(1), n_tok, bad,
                                run.compiles_in_window, limit)
        out["control"]["mode"] = control
    return out
