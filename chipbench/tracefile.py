"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

Two steps, so the second can be tested on a small recorded trace:

1. :func:`load` reads the ``.xplane.pb`` the JAX profiler wrote into plain
   events: the device's operations (``/device:TPU:<i>`` planes, the
   ``XLA Ops`` line) and the harness's host spans (names starting
   ``chipbench.``), each ``(name, start_ns, dur_ns, detail)``.
2. :func:`reduce` clips them to the window the harness marked
   (``chipbench.window_open`` / ``chipbench.window_close``) and computes
   the union of the device's busy intervals per chip, each kernel's summed
   device time, and the idle gaps with the host span that overlaps each
   most.

An event's name is the operation's HLO text, ``%<id> = <shape> <op>(...)``.
A Pallas kernel's id is its jitted wrapper's name (``flash_varlen_call``,
``flash_varlen_cross_call``, ``fused_logit_argmax_call``, ...), so a
reader finds a kernel by a substring of the id alone, never of the
operands. The layer scans appear as ``while`` operations that contain the
operations of their bodies: they count towards busy time (a union) and are
left out of the per-operation totals.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "chipbench."


def _detail(ev) -> str:
    parts = []
    for k, v in ev.stats:
        if isinstance(v, (str, bytes)):
            v = v.decode() if isinstance(v, bytes) else v
            parts.append(f"{k}={v}")
    return " ".join(parts)


def load(trace_dir) -> dict:
    """Plain events from the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"devices": {chip: [[name, start_ns, dur_ns, detail], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append([ev.name, int(ev.start_ns),
                                int(ev.duration_ns), _detail(ev)])
            devices[m.group(1)] = evs
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"devices": devices, "host": host}


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float                          # mean over the chips used
    ops: Dict[str, float] = field(default_factory=dict)   # label -> s
    op_events: List[tuple] = field(default_factory=list)  # (label, name,
    #                                                     #  detail, dur_s)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def kernel_time(self, pattern: str) -> float:
        """Summed device seconds of the operations whose id contains
        ``pattern`` (divided over the chips, like ``busy_s``)."""
        return sum(d for _, i, _, d in self.op_events if pattern in i)


CONTAINERS = ("while", "conditional", "call")


def op_id(name: str) -> str:
    """``%fused_logit_argmax_call.2 = (...) custom-call(...)`` ->
    ``fused_logit_argmax_call.2``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def label(name: str) -> str:
    """The operation's id without its number: ``fusion``,
    ``flash_varlen_call``, ``copy-start``."""
    return re.sub(r"\.\d+$", "", op_id(name))


def reduce(events: dict, min_gap_s: float = 1e-4) -> Summary:
    """Busy time, per-label device time and idle gaps inside the marked
    window. Times are seconds; with several chips, busy and kernel times
    are means over the chips."""
    marks = {n: s for n, s, _ in events["host"]
             if n in ("chipbench.window_open", "chipbench.window_close")}
    w0 = marks.get("chipbench.window_open")
    w1 = marks.get("chipbench.window_close")
    if w0 is None or w1 is None or w1 <= w0:
        raise ValueError("the trace lacks the window marks")
    chips = events["devices"] or {"0": []}
    n = len(chips)
    busy = 0.0
    ops: Dict[str, float] = defaultdict(float)
    op_events: List[tuple] = []
    gaps: List[Tuple[str, float]] = []
    spans = [(nm, s, s + d) for nm, s, d in events["host"]
             if not nm.startswith("chipbench.window")]
    for chip, evs in sorted(chips.items()):
        iv = []
        for name, s, d, det in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            iv.append((a, b))
            lab = label(name)
            if lab in CONTAINERS:
                continue
            ops[lab] += (b - a) / 1e9 / n
            op_events.append((lab, op_id(name), det, (b - a) / 1e9 / n))
        u = _union(iv)
        busy += sum(b - a for a, b in u) / 1e9 / n
        if chip == sorted(chips)[0]:
            edges = [w0] + [x for ab in u for x in ab] + [w1]
            for a, b in zip(edges[::2], edges[1::2]):
                if (b - a) / 1e9 >= min_gap_s:
                    gaps.append((_host_in(spans, a, b), (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy, ops=dict(ops),
                   op_events=op_events, gaps=gaps)


def _host_in(spans, a: int, b: int) -> str:
    """The harness span overlapping ``[a, b]`` most, else ``other``."""
    best, cover = "host:other", 0
    for name, s, e in spans:
        c = min(e, b) - max(s, a)
        if c > cover:
            best, cover = "host:" + name[len(HOST_PREFIX):], c
    return best


def summarize(trace_dir) -> Summary:
    return reduce(load(trace_dir))


def breakdown(s: Summary) -> dict:
    ops = sorted(s.ops.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in s.gaps[:10]]}

