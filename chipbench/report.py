"""Turns one measured run into the printed lines and the result line."""
from __future__ import annotations

import json
import math
import shutil
import sys
from typing import Optional

from chipbench import measure as M
from chipbench import spec as SP


def read_metric(run, entry: dict) -> Optional[float]:
    """Load ``chipbench/metrics/<name>.py`` and call its ``read(run)``; a
    reader that finds nothing to read returns None."""
    path = SP.metric_path(run.cell.root, entry["name"])
    v = SP.load_module(path).read(run)
    if v is None or (isinstance(v, float) and math.isnan(v)):
        return None
    return M.finite(float(v))


def setup_kind(setup: dict) -> str:
    """``cold`` where set-up compiled a program the persistent cache did
    not hold (the first run of a cell in a checkout), ``warm`` where the
    cache held every one, ``uncached`` where no persistent cache is in
    use."""
    if setup["cache_misses"]:
        return "cold"
    if setup["compiles"] and not setup["cache_hits"]:
        return "uncached"
    return "warm"


def _lines(run, device: dict, check: dict) -> list:
    r = run
    due = [q for q in r.reqs if q.due <= r.t_end]
    fin = [q for q in due if q.finished and q.blocks[-1] <= r.t_end]
    failed = [q for q in due if q.failed]
    ttfb, left = M.ttfb_samples(r.reqs, r.t_end,
                                r.traffic.get("tail_guard_s", 0.0))
    late = [q for q in ttfb if math.isinf(q)]
    gaps = M.block_gap_samples(r.reqs, r.t_end)
    s = r.setup
    return [
        f"cell: {r.cell.name}  config: {r.cell.workload['config']}  "
        f"traffic: {r.cell.workload['traffic']}  window_s: {r.seconds}",
        f"requests: due {len(due)}  finished {len(fin)}  failed "
        f"{len(failed)}  left out (due within tail_guard_s of the end, no "
        f"first block) {left}  infinitely late {len(late)}",
        f"generator: open loop, every request submitted before the window "
        f"with its due time; the engine's loop started "
        f"{1e3 * r.late_s:.3f} ms after the window opened",
        f"samples: first blocks {len(ttfb)} ({M.beyond(ttfb, 90)} beyond "
        f"p90), block gaps {len(gaps)} ({M.beyond(gaps, 95)} beyond p95), "
        f"committed tokens "
        f"{sum(e.n for ev in r.events.values() for e in ev)}",
        f"compiles inside the window: {r.compiles_in_window} "
        f"{r.compiled_in_window[:20]}",
        f"device_kind: {device['kind']}  count: {device['count']}  "
        f"peak_bytes_in_use: {device['memory_peak_bytes']}  bytes_limit: "
        f"{device['bytes_limit']}  slots: {r.slots_allocated}",
        "setup: " + "  ".join(f"{k} {v:.3f}" if isinstance(v, float)
                              else f"{k} {v}" for k, v in s.items()),
        f"setup run: {setup_kind(s)} ({s['compiles']} programs compiled or "
        f"loaded, {s['cache_misses']} of them compiled and written to the "
        f"persistent cache, {s['cache_hits']} read from it)",
        f"checked requests: {check['sampled']}",
    ]


def emit(out: dict, trace: bool, trace_dir=None,
         keep_trace: bool = False) -> dict:
    run, device, check = out["run"], out["device"], out["check"]
    from chipbench.peaks import peaks
    run.peaks = peaks(device["kind"]) if device["platform"] == "tpu" \
        else None
    breakdown = None
    if trace:
        from chipbench import tracefile
        run.trace = tracefile.summarize(trace_dir)
        breakdown = tracefile.breakdown(run.trace)
        device = dict(device, busy_s=run.trace.busy_s,
                      window_s=run.trace.window_s)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for e in run.cell.metrics(kind):
        v = read_metric(run, e)
        if v is not None:
            metrics[e["name"]] = {"value": v, "unit": e["unit"]}
    for line in _lines(run, device, check):
        print(line, flush=True)
    due = [q for q in run.reqs if q.due <= run.t_end]
    res = {"correct": check["correct"], "attempted": len(due),
           "failed": sum(1 for q in due if q.failed), "metrics": metrics,
           "device": {k: device[k] for k in
                      ("platform", "kind", "count", "memory_peak_bytes")
                      + (("busy_s", "window_s") if trace else ())}}
    if breakdown is not None:
        res["breakdown"] = breakdown
    s = run.setup
    res["setup"] = {"run": setup_kind(s), "compiles": s["compiles"],
                    "cache_misses": s["cache_misses"]}
    res["checks"] = check["checks"]
    for k, c in check["checks"].items():
        print(f"check {k}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return res
