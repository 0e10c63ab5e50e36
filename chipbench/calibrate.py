"""Readings that the correctness limit is set from, on one warmed engine.

For each seed: the weights of that seed, a short window of the cell's own
traffic at its own load (drained to the end, so the longest requests
finish), then the run's own check (``check.check_run``): the widest gap of
a served token below the float32 reference's best over the sample (the
program's reading), with ``correct`` at the cell's limit. For the control
seeds ``check_run`` also judges the fp8 control along the same
trajectories: the widest gap of the token the fp8 copy puts first, with
its ``correct`` at the same limit.

    python3 chipbench/calibrate.py --workload <cell> --seeds 101-112 \
        --control-seeds 101-103 --seconds 15
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _seeds(s: str):
    out = []
    for part in s.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out

def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    seeds, ctrl = _seeds(args.seeds), set(_seeds(args.control_seeds))
    from chipbench import tools
    cell, b = tools.setup(args.workload, seeds[0])
    from chipbench import check as CK
    from chipbench import harness as H
    from chipbench import traffic as TR
    spec = cell.cell["check"]
    for i, seed in enumerate(seeds):
        if i:
            tools.swap_weights(b, seed)
        reqs = TR.generate(cell.traffic, cell.cell, args.seconds, seed,
                           b.cfg.vocab_size, b.eng.mask_id)
        run = H.window(b, reqs, args.seconds, drain=True)
        run.t_end = float("inf")       # every drained request counts
        prompts = {r.rid: q.prompt for q, r in zip(reqs, run.reqs)}
        t = time.perf_counter()
        res = CK.check_run(run, prompts, b.params, b.ref_serve(), seed, spec,
                           control="fp8" if seed in ctrl else None)
        out = dict(seed=seed, requests=len(reqs), sampled=res["sampled"],
                   correct=res["correct"], checks=res["checks"],
                   ref_s=time.perf_counter() - t)
        if "control" in res:
            out["control"] = res["control"]
        print(json.dumps(out), flush=True)
    return 0

if __name__ == "__main__":
    sys.exit(main())
