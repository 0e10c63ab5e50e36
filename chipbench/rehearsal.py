"""A cell at a size the CPU can hold, for the tests: the widths of the
configuration's architecture module (``REHEARSAL``; LLaDA's two layers of
width 128 over a 4,096-token vocabulary, bf16 kept), the mix's prompts
8-40 tokens with 64-token answers, a small pool, a short window. Kernels
run interpreted there; the structure of the run is the chip's."""
from __future__ import annotations

import copy
import time

HBM = 16 << 30


def shrink(cell):
    cell = copy.deepcopy(cell)
    p = cell.traffic["prompt_len"]
    p.update(min=8, max=40)
    p.update(median=20) if "median" in p else p.update(mean=24, sd=8)
    cell.traffic["gen_len"] = 64
    cell.cell["serve"].update(max_seq_len=128, max_num_batched_tokens=256,
                              max_slots=6, token_bucket=128)
    if "rate_rps" in cell.cell:
        cell.cell["rate_rps"] = 3.0
    else:
        cell.cell["requests_per_second_of_window"] = 3
    cell.cell["check"]["gap_limit"] = GAP_LIMIT
    return cell


# At this size the widest gap of the bf16 program below the float32
# reference read 0 to 2.2e-3 over five seeds, the fp8 control's 2.8e-2 to
# 6.4e-2 (test_bench_control.py); the limit lies between.
GAP_LIMIT = 1e-2


def run(workload: str, seed: int = 7, seconds: float = 5.0,
        trace: bool = False, root=None, trace_dir=None):
    from chipbench import run as R
    from chipbench import spec as SP
    kw = {} if root is None else {"root": root}
    arch = SP.load_cell(workload, root).arch
    return R.execute(workload, seed, seconds, trace,
                     t_proc0=time.perf_counter(), hbm_bytes=HBM,
                     cfg_overrides=arch.REHEARSAL,
                     cell_overrides=shrink, trace_dir=trace_dir, **kw)
