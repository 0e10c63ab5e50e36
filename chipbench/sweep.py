"""Find a chat cell's knee: serve the cell's traffic at several fixed
rates, one window each, on one warmed engine, and print what each rate
sustained. The knee is the highest rate whose backlog stays bounded; the
cell's ``rate_rps`` is set once from it (0.8x) and is then part of the
cell.

    python3 chipbench/sweep.py --workload <chat cell> --rates 1,1.5,2 \
        --seconds 30 --seed 5
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    from chipbench import tools
    cell, b = tools.setup(args.workload, args.seed)
    from chipbench import harness as H
    from chipbench import measure as M
    from chipbench import traffic as TR
    print(json.dumps({"setup": {k: v for k, v in b.times.items()
                                if not k.startswith("t_")},
                      "slots": b.serve.max_slots}), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.cell["rate_rps"] = rate
        reqs = TR.generate(cell.traffic, cell.cell, args.seconds,
                           args.seed + i, b.cfg.vocab_size, b.eng.mask_id)
        t = time.perf_counter()
        run = H.window(b, reqs, args.seconds, drain=True)
        drain = time.perf_counter() - t - args.seconds
        ttfb, left = M.ttfb_samples(run.reqs, run.t_end,
                                    cell.traffic["tail_guard_s"])
        gaps = M.block_gap_samples(run.reqs, run.t_end)
        fin = sum(1 for r in run.reqs if r.finished and
                  r.blocks[-1] <= run.t_end)
        tok = sum(e.n for ev in run.events.values() for e in ev
                  if e.t <= run.t_end)
        waits, _ = M.queue_wait_samples(run, cell.traffic["tail_guard_s"])
        print(json.dumps(dict(
            rate=rate, due=len(reqs), finished_in_window=fin,
            ttfb_p50=M.finite(M.percentile(ttfb, 50)),
            ttfb_p90=M.finite(M.percentile(ttfb, 90)),
            gap_p95=M.finite(M.percentile(gaps, 95)),
            queue_wait_p90=M.finite(M.percentile(waits, 90)),
            tok_s=tok / args.seconds, drain_s=drain,
            iters=len(run.iters),
            resident_mean=sum(r["n_refresh"] + r["n_reuse"]
                              for r in run.iters) / max(1, len(run.iters)),
            compiles=run.compiles_in_window)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
