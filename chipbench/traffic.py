"""The one traffic generator: a mix is a data file, a run is a seed.

A traffic file (``chipbench/traffic/<name>.json``) gives the prompt-length
distribution, the generation length, the denoising schedule (block size,
steps per block, Refresh interval) and the arrival process. The cell file
gives how much of it: a rate for an open loop, or a request count for a
queue that is full from the start.

The length and arrival draws are copies of the repository's seeded replicas
of the paper's traces (``data/workloads.py``: livebench, osc, burst) at
scale 1.0, kept here so that a change to the program cannot change the
yardstick. Two departures make every seed the same amount of work:

* the sizes and the inter-arrival gaps are drawn once, from the mix's
  ``base_seed``, and sent in the order drawn; the run's ``--seed`` draws
  only the prompt token ids (and, in the harness, the weights). An order
  drawn from the seed let the order of the gaps decide how long the queue
  grew near the knee, so that tails read from seed to seed spread by more
  than their bounds could hold;
* an open loop sends exactly ``round(rate * seconds)`` requests, its gaps
  scaled so that the last falls inside the window (a Poisson process
  conditioned on its count).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class Req:
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    gen_len: int


def _poisson_gaps(n: int, rps: float, rng) -> np.ndarray:
    return rng.exponential(1.0 / rps, n)


def _burst_gaps(n: int, rps: float, rng, burst_factor: float,
                p_on: float) -> np.ndarray:
    """Markov-modulated Poisson: ON periods at ``burst_factor`` x rate."""
    out: List[float] = []
    on = False
    while len(out) < n:
        on = rng.random() < (p_on if not on else 0.7)
        rate = rps * burst_factor if on else rps * 0.4
        k = min(n - len(out), rng.integers(2, 8))
        for _ in range(k):
            out.append(rng.exponential(1.0 / rate))
    return np.asarray(out[:n])


def prompt_lengths(spec: dict, n: int, rng) -> np.ndarray:
    d = spec["dist"]
    if d == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif d == "normal":
        x = rng.normal(spec["mean"], spec["sd"], n)
    elif d == "pareto":
        x = (rng.pareto(spec["alpha"], n) + 1) * spec["scale"]
    else:
        raise ValueError(f"unknown prompt length distribution {d!r}")
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def length_range(traffic: dict) -> tuple:
    """(shortest, longest) total length a request of the mix can have."""
    p = traffic["prompt_len"]
    g = traffic["gen_len"]
    return int(p["min"]) + g, int(p["max"]) + g


def count(traffic: dict, cell: dict, seconds: float) -> int:
    """Requests one run sends."""
    arr = traffic["arrivals"]
    if arr["process"] == "at_start":
        return max(1, int(round(cell["requests_per_second_of_window"]
                                * seconds)))
    return max(1, int(round(cell["rate_rps"] * seconds)))


def generate(traffic: dict, cell: dict, seconds: float, seed: int,
             vocab_size: int, mask_id: int) -> List[Req]:
    """The requests of one run, in due order. Prompt ids are drawn from
    ``[0, vocab_size)`` without ``mask_id``."""
    n = count(traffic, cell, seconds)
    base = np.random.default_rng(traffic["base_seed"])
    arr = traffic["arrivals"]
    if arr["process"] == "at_start":
        gaps = np.zeros(n)
    elif arr["process"] == "poisson":
        gaps = _poisson_gaps(n + 1, cell["rate_rps"], base)
    elif arr["process"] == "burst":
        gaps = _burst_gaps(n + 1, cell["rate_rps"], base,
                           arr["burst_factor"], arr["p_on"])
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    plen = prompt_lengths(traffic["prompt_len"], n, base)
    if arr["process"] != "at_start":
        # n arrivals and one gap past the last: the n-th falls inside
        due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    else:
        due = gaps
    rng = np.random.default_rng(seed)
    out = []
    for d, p in zip(due, plen):
        ids = rng.integers(0, vocab_size - 1, int(p))
        ids = np.where(ids >= mask_id, ids + 1, ids).astype(np.int32)
        out.append(Req(float(d), ids, int(traffic["gen_len"])))
    return out
