"""Stage view of a profiler trace: device time by compiled program, and
device idle by the engine span the host was in.

The engine compiles each stage under its entry name (``jit_refresh_packed``,
``jit_reuse_packed``, ``jit_decode_packed``, ``jit_pool_write``, ...) and
marks its host work with ``dllm.*`` spans on the profiler's clock
(``repro.core.engine.SPANS``). Two steps, so the second can be tested on a
small recorded trace:

1. :func:`load` reads the newest ``.xplane.pb`` under a directory into
   plain events: per chip the device's operations (``XLA Ops``) and
   programs (``XLA Modules``, each name stripped of its fingerprint to
   ``jit_<entry>``), and on the host the engine's spans and the harness's
   window marks, each ``(name, start_ns, dur_ns)``.
2. :func:`reduce` clips them to the marked window. Busy time is the union
   of the operations' intervals, as in ``tracefile``; a program's time is
   the busy time inside its own intervals, so the programs' times never
   sum above busy time. Each idle stretch of the first chip is split by the
   innermost ``dllm.*`` span over it, ``host:none`` where none is.

Run as a tool on the chip, it serves traced windows of a cell with the
trace kept, and prints one JSON line a run with the stage view, the stage
metrics and the checks of the reduction:

    python3 chipbench/stages.py --workload llada-8b-1chip.chat \\
        --seeds 2147488749,2147488750 --seconds 51 [--sample out.json]
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:           # run as a script from the checkout
    sys.path.insert(0, str(ROOT))

from chipbench.tracefile import DEVICE_PLANE, OPS_LINE, _union  # noqa: E402

MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "dllm."
WINDOW = ("chipbench.window_open", "chipbench.window_close")
NONE = "host:none"
ARRIVAL = "dllm.arrival_wait"
LONG_GAP_S = 1e-3

# the engine's programs, by stage
REFRESH = "jit_refresh_packed"
REUSE = "jit_reuse_packed"
LOGITS = "jit_decode_packed"
POOL = ("jit_pool_gather", "jit_pool_write", "jit_pool_copy")
ENGINE_MODULES = (REFRESH, REUSE, LOGITS) + POOL + (
    "jit_refresh", "jit_reuse", "jit_decode")


def module_name(name: str) -> str:
    """``jit_refresh_packed(2553684796414980458)`` -> ``jit_refresh_packed``."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def load(trace_dir) -> dict:
    """Plain events from the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"devices": {chip: [[op, start_ns, dur_ns], ...]}, "modules": {chip:
    [[jit_<entry>, start_ns, dur_ns], ...]}, "host": [[name, start_ns,
    dur_ns], ...]}``; the host events are the ``dllm.*`` spans and the
    window marks."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods += [[module_name(ev.name), int(ev.start_ns),
                              int(ev.duration_ns)] for ev in line.events]
            devices[m.group(1)] = ops
            modules[m.group(1)] = mods
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX) or ev.name in WINDOW:
                    host.append([ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)])
    return {"devices": devices, "modules": modules, "host": host}


def _overlap(u: List[Tuple[int, int]], a: int, b: int) -> int:
    """Length of ``[a, b]`` covered by the sorted disjoint intervals
    ``u``."""
    i = max(0, bisect.bisect_right(u, (a, a)) - 1)
    n = 0
    while i < len(u) and u[i][0] < b:
        n += max(0, min(b, u[i][1]) - max(a, u[i][0]))
        i += 1
    return n


def innermost(spans: List[Tuple[str, int, int]]) -> List[tuple]:
    """The host timeline as ``(start, end, name)`` pieces, each named by
    the innermost span over it (the one that started last)."""
    pts = []
    for k, (name, s, e) in enumerate(spans):
        pts.append((s, 1, -(e - s), k))
        pts.append((e, 0, 0, k))
    pts.sort()
    active: List[int] = []
    out: List[tuple] = []
    prev = None
    for t, is_start, _, k in pts:
        if prev is not None and t > prev and active:
            out.append((prev, t, spans[active[-1]][0]))
        prev = t
        if is_start:
            active.append(k)
        else:
            active.remove(k)
    return out


@dataclass
class StageSummary:
    window_s: float
    busy_s: float
    # jit_<entry> -> [device seconds inside the window, calls]
    modules: Dict[str, list] = field(default_factory=dict)
    # innermost dllm.* span (host:none) -> idle seconds inside the window
    engine_idle: Dict[str, float] = field(default_factory=dict)
    long_idle_s: float = 0.0          # idle in gaps of LONG_GAP_S or more
    long_idle_spanned_s: float = 0.0  # ... of it under some dllm.* span
    # the longest gaps: (seconds, {innermost span: seconds})
    long_gaps: List[tuple] = field(default_factory=list)

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    def module_s(self, *names: str) -> float:
        return sum(self.modules.get(n, [0.0, 0])[0] for n in names)


def reduce(events: dict, n_long: int = 10) -> StageSummary:
    """Busy time, each program's device time and the idle time by engine
    span, inside the marked window of the first chip."""
    marks = {n: s for n, s, _ in events["host"] if n in WINDOW}
    w0, w1 = marks.get(WINDOW[0]), marks.get(WINDOW[1])
    if w0 is None or w1 is None or w1 <= w0:
        raise ValueError("the trace lacks the window marks")
    chip = sorted(events["devices"])[0] if events["devices"] else None
    ops = events["devices"].get(chip, []) if chip is not None else []
    busy = _union([(max(s, w0), min(s + d, w1)) for _, s, d, *_ in ops
                   if min(s + d, w1) > max(s, w0)])
    mods: Dict[str, list] = defaultdict(lambda: [0.0, 0])
    for name, s, d in events.get("modules", {}).get(chip, []):
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        mods[name][0] += _overlap(busy, a, b) / 1e9
        mods[name][1] += 1
    spans = [(n, s, s + d) for n, s, d in events["host"]
             if n.startswith(SPAN_PREFIX)]
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    edges = [w0] + [x for ab in busy for x in ab] + [w1]
    idle: Dict[str, float] = defaultdict(float)
    long_s = long_spanned = 0.0
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        split: Dict[str, float] = defaultdict(float)
        covered = 0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(pieces) and pieces[i][0] < b:
            s, e, name = pieces[i]
            c = min(b, e) - max(a, s)
            if c > 0:
                split[name] += c / 1e9
                covered += c
            i += 1
        if b - a > covered:
            split[NONE] += (b - a - covered) / 1e9
        for k, v in split.items():
            idle[k] += v
        if (b - a) / 1e9 >= LONG_GAP_S:
            long_s += (b - a) / 1e9
            long_spanned += covered / 1e9
            gaps.append(((b - a) / 1e9, dict(split)))
    gaps.sort(key=lambda g: -g[0])
    return StageSummary(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(b - a for a, b in busy) / 1e9,
        modules=dict(mods), engine_idle=dict(idle), long_idle_s=long_s,
        long_idle_spanned_s=long_spanned, long_gaps=gaps[:n_long])


# -- the stage metrics (``run.iters``: the engine's iter_log rows of the
#    window) ----------------------------------------------------------------

def _per(seconds: float, count: float, scale: float) -> Optional[float]:
    return scale * seconds / count if count > 0 and seconds > 0 else None


def metrics(s: StageSummary, iters: List[dict]) -> Dict[str, Optional[float]]:
    """Device time per real token of each stage, the pool's per iteration,
    and the idle share the host caused (idle not under the arrival wait)."""
    tot = defaultdict(int)
    for r in iters:
        for k in ("refresh_tokens_real", "reuse_tokens_real",
                  "logit_tokens_real"):
            tot[k] += r[k]
    return {
        "refresh_us_per_tok.chat": _per(s.module_s(REFRESH),
                                        tot["refresh_tokens_real"], 1e6),
        "reuse_us_per_tok.chat": _per(s.module_s(REUSE),
                                      tot["reuse_tokens_real"], 1e6),
        "logit_us_per_row.chat": _per(s.module_s(LOGITS),
                                      tot["logit_tokens_real"], 1e6),
        "pool_ms_per_iter.chat": _per(s.module_s(*POOL), len(iters), 1e3),
        "host_stall_frac.chat": (s.idle_s - s.engine_idle.get(ARRIVAL, 0.0))
        / s.window_s,
    }


def checks(s: StageSummary) -> dict:
    """The reduction's own shares: busy time inside the engine's programs,
    idle in gaps of 1 ms or more under a ``dllm.*`` span, and the module
    names that are not the engine's."""
    return {
        "engine_module_share": s.module_s(*ENGINE_MODULES) / s.busy_s
        if s.busy_s else None,
        "long_idle_spanned_share": s.long_idle_spanned_s / s.long_idle_s
        if s.long_idle_s else None,
        "other_modules": sorted(
            ([k, v[0], v[1]] for k, v in s.modules.items()
             if k not in ENGINE_MODULES), key=lambda x: -x[1]),
    }


def clock_lead_ms(events: dict) -> Optional[float]:
    """The least time from a ``dllm.pool.gather`` span's start to the start
    of the nearest ``jit_pool_gather`` program, in ms. The gather is
    launched onto an idle device (right after the sync), so it starts within
    a launch's time of its span; a negative reading means the device's clock
    in the trace runs ahead of the host's by at least that much, which is
    the resolution of the idle attribution."""
    spans = sorted(s for n, s, _ in events["host"]
                   if n == "dllm.pool.gather")
    mods = events.get("modules") or {}
    chip = sorted(mods)[0] if mods else None
    lead = []
    for n, m, _ in mods.get(chip, []):
        if n != "jit_pool_gather" or not spans:
            continue
        i = bisect.bisect_left(spans, m)
        near = min(spans[max(0, i - 1): i + 1], key=lambda s: abs(s - m))
        lead.append(m - near)
    return min(lead) / 1e6 if lead else None


def sample(events: dict, n_iters: int = 2, join_ns: int = 2000) -> dict:
    """A small recorded fixture: from the ``dllm.plan`` before the first
    iteration in the window that gathers from the pool, through the
    ``n_iters``-th ``dllm.sync`` after it. Device operations closer than
    ``join_ns`` are joined into one interval (busy time is all the
    reduction reads of them); window marks enclose the stretch."""
    host = sorted(events["host"], key=lambda e: e[1])
    w0 = [s for n, s, _ in host if n == WINDOW[0]][0]
    g = [s for n, s, _ in host if n == "dllm.pool.gather" and s > w0][0]
    a = max(s for n, s, _ in host if n == "dllm.plan" and s < g)
    b = [s + d for n, s, d in host if n == "dllm.sync" and s > g][n_iters]
    chip = sorted(events["devices"])[0]
    joined: List[list] = []
    for _, s, d, *_ in sorted(events["devices"][chip], key=lambda e: e[1]):
        s, e = max(s, a), min(s + d, b)
        if e <= s:
            continue
        if joined and s - joined[-1][2] <= join_ns:
            joined[-1][2] = max(joined[-1][2], e)
        else:
            joined.append(["ops", s, e])
    return {
        "note": "a traced chat window on a v5e (chip run): the engine's "
                "spans, its programs and the device's busy intervals "
                f"(operations closer than {join_ns} ns joined) from one "
                f"iteration's plan through {n_iters} more syncs",
        "devices": {chip: [[n, s, e - s] for n, s, e in joined]},
        "modules": {chip: [m for m in events["modules"][chip]
                           if m[1] < b and m[1] + m[2] > a]},
        "host": [[WINDOW[0], a, 1], [WINDOW[1], b, 1]]
        + [e for e in host if e[0].startswith(SPAN_PREFIX)
           and e[1] < b and e[1] + e[2] > a]}


def main(argv=None) -> int:
    import shutil
    import time
    t_proc0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sample", default="",
                    help="write a small fixture of the first run's trace")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("stages: needs a TPU", file=sys.stderr)
        return 2
    from chipbench import report
    from chipbench import spec as SP
    from chipbench.harness import serve_and_measure
    cell = SP.load_cell(args.workload, ROOT)
    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        tdir = ROOT / "chipbench_out" / "stages" / f"{args.workload}.{seed}"
        out = serve_and_measure(cell, seed, args.seconds, True,
                                t_proc0=t_proc0, trace_dir=tdir)
        res = report.emit(out, True, tdir, keep_trace=True)
        iters = out["run"].iters
        ev = load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        s = reduce(ev)
        if i == 0 and args.sample:
            Path(args.sample).write_text(json.dumps(sample(ev)))
        print("stages " + json.dumps(dict(
            seed=seed, correct=res["correct"], window_s=s.window_s,
            busy_s=s.busy_s, idle_s=s.idle_s, iters=len(iters),
            host_ms_per_iter=1e3 * sum(r["plan_s"] + r["fill_s"]
                                       for r in iters) / max(1, len(iters)),
            modules=sorted(([k] + v for k, v in s.modules.items()),
                           key=lambda x: -x[1]),
            engine_idle=sorted(s.engine_idle.items(), key=lambda x: -x[1]),
            long_gaps=s.long_gaps, metrics=metrics(s, iters),
            checks=checks(s), clock_lead_ms=clock_lead_ms(ev),
            harness=res["metrics"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
