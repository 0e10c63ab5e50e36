"""Shared serving grid: (system × workload × rps) runs, cached to JSON.

Figures 3/4/5 and Table 4 of the paper all read from the same underlying
sweep, so we run it once. CPU-scale: reduced llada-8b config, scaled trace
lengths; *relative* numbers (ours vs baselines) are the reproduction target —
the paper's own claims are 1.61–1.81× (4090) / 1.60–1.74× (L40S) throughput
and ~4× tail latency.
"""
from __future__ import annotations

import json
import os

from repro.launch.serve import run_serve

CACHE = os.path.join(os.path.dirname(__file__), "..", "results",
                     "serve_grid.json")
SYSTEMS = ("fast-dllm", "dllm-cache", "sparse-dllm", "dllm-serve")
WORKLOADS = ("livebench", "burst", "osc")
# slot-sizing budget for these CPU runs (XLA:CPU reports no device memory
# limit): one TPU v5e chip's 16 GiB
CPU_HBM_BYTES = 16 << 30


def grid(quick: bool = True, refresh: bool = False) -> list:
    os.makedirs(os.path.dirname(CACHE), exist_ok=True)
    if os.path.exists(CACHE) and not refresh:
        with open(CACHE) as f:
            return json.load(f)
    # modeled-clock contention sweep (saturation sits near rps≈6 for the
    # scaled device model; the paper's 0.25-0.5 RPS wall scales likewise)
    rps_points = (2.0, 6.0) if quick else (1.0, 2.0, 4.0, 6.0, 12.0)
    n = 16 if quick else 24
    rows = []
    for wl in WORKLOADS:
        for sys_name in SYSTEMS:
            for rps in rps_points:
                r = run_serve("llada-8b", sys_name, wl, rps, n,
                              max_seq_len=192, block_size=8,
                              steps_per_block=8, max_slots=12,
                              max_num_batched_tokens=768,
                              max_num_logits=96, length_scale=0.12,
                              hbm_bytes=CPU_HBM_BYTES)
                rows.append(r)
                with open(CACHE, "w") as f:
                    json.dump(rows, f, indent=1)
    return rows


def best_baseline(rows, wl, rps, key="throughput_tok_s", hi=True):
    vals = [r[key] for r in rows
            if r["workload"] == wl and r["rps"] == rps
            and r["system"] != "dllm-serve"]
    return (max if hi else min)(vals)


def ours(rows, wl, rps, key="throughput_tok_s"):
    return [r[key] for r in rows
            if r["workload"] == wl and r["rps"] == rps
            and r["system"] == "dllm-serve"][0]
