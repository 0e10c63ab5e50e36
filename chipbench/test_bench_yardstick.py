"""The yardstick on the CPU: percentile and window arithmetic, the work
functions against hand arithmetic, the trace reduction on a small trace
recorded on the chip, and the copied generator pinned by drawn values."""
import json
import math
import types
from pathlib import Path

import pytest

from chipbench import measure as M
from chipbench import spec as SP
from chipbench import traffic as TR
from chipbench import work as W
from chipbench.report import read_metric

HERE = Path(__file__).resolve().parent
LLADA = SP.load_module(HERE / "archs" / "llada.py")
LLADA16 = dict(family="dense", n_layers=16, d_model=4096, n_heads=32,
               n_kv_heads=32, head_dim=128, d_ff=12288, vocab_size=126464,
               arch=LLADA)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert M.percentile(xs, 90) == 90
    assert M.percentile(xs, 95) == 95
    assert M.percentile([3.0], 90) == 3.0
    assert M.percentile([1, 2, math.inf], 50) == 2
    assert math.isinf(M.percentile([1, math.inf, math.inf], 50))
    assert M.beyond(xs, 90) == 10
    assert math.isnan(M.percentile([], 90))


def _req(rid, due, blocks, n_blocks=3, failed=False):
    return M.ReqRecord(rid, due, n_blocks, 10, failed=failed, blocks=blocks)


def test_ttfb_window_edges_and_tail_guard():
    t_end = 10.0
    reqs = [_req(0, 1.0, [1.5, 2.0, 2.5]),        # 0.5
            _req(1, 2.0, [11.0]),                 # first block after end
            _req(2, 9.0, []),                     # due inside the guard
            _req(3, 3.0, [], failed=True),        # rejected
            _req(4, 10.5, [])]                    # not due in the window
    xs, left = M.ttfb_samples(reqs, t_end, tail_guard_s=2.0)
    assert left == 1
    assert xs[0] == pytest.approx(0.5)
    assert sorted(xs)[1:] == [math.inf, math.inf]
    xs0, left0 = M.ttfb_samples(reqs, t_end, tail_guard_s=0.0)
    assert left0 == 0 and sum(math.isinf(x) for x in xs0) == 3


def test_block_gaps_count_open_gap_to_the_end():
    t_end = 10.0
    reqs = [_req(0, 0.0, [1.0, 3.0, 4.0]),        # finished: gaps 2, 1
            _req(1, 0.0, [2.0, 6.0]),             # open gap 6 -> 10
            _req(2, 0.0, [9.0, 12.0]),            # block after the end
            _req(3, 0.0, [], failed=True)]        # misses every limit
    gaps = sorted(M.block_gap_samples(reqs, t_end))
    assert gaps[:5] == [1.0, 1.0, 2.0, 4.0, 4.0]
    assert math.isinf(gaps[-1]) and len(gaps) == 6


def test_failed_requests_miss_every_limit():
    reqs = [_req(i, 0.0, [0.1, 0.2, 0.3]) for i in range(9)]
    reqs.append(_req(9, 0.0, [], failed=True))
    xs, _ = M.ttfb_samples(reqs, 5.0, 1.0)
    assert M.percentile(xs, 90) == pytest.approx(0.1)
    assert math.isinf(M.percentile(xs, 91))
    assert math.isinf(max(M.block_gap_samples(reqs, 5.0)))
    assert M.finite(math.inf) == 1e9


def test_work_counts_by_hand_at_llada_widths():
    per_tok = 2 * 16 * (4096 * 32 * 128 * 4 + 3 * 4096 * 12288)
    assert LLADA.matmul_flops_per_token(LLADA16) == per_tok
    assert per_tok == pytest.approx(6.98e9, rel=1e-3)
    assert W.logit_flops_per_row(LLADA16) == 2 * 4096 * 126464
    L, sb, retain = 556, 32, 608
    att = 4 * L * L * 32 * 128 * 16
    assert W.step_flops(LLADA16, "refresh", L, sb, retain) == \
        L * per_tok + att + sb * 2 * 4096 * 126464
    keys = min(retain, L - sb) + sb
    assert W.step_flops(LLADA16, "reuse", L, sb, retain) == \
        sb * per_tok + 4 * sb * keys * 32 * 128 * 16 + sb * 2 * 4096 * 126464
    assert W.logit_call_bytes(LLADA16, 256) == 2 * (126464 + 256) * 4096


def _run(events, seconds, iters=(), trace=None):
    cell = types.SimpleNamespace(root=HERE.parent)
    serve = types.SimpleNamespace(max_num_logits=256, max_seq_len=1216,
                                  retention_ratio=0.5)
    return types.SimpleNamespace(
        cell=cell, dims=LLADA16, serve=serve, seconds=seconds, t_end=1e9,
        retain=608,
        traffic=dict(block_size=32, refresh_interval=8),
        reqs=[M.ReqRecord(0, 0.0, 8, 300)], events={0: events},
        iters=list(iters), trace=trace, peaks=V5E)


def test_mfu_is_a_share_of_the_peak():
    ev = [types.SimpleNamespace(t=0.0, step=s % 8, n=4) for s in range(64)]
    total = 300 + 256
    flops = sum(W.step_flops(LLADA16, "refresh" if s % 8 == 0 else "reuse",
                             total, 32, 608) for s in range(64))
    at_peak = flops / V5E["bf16_flops_per_s"]
    for secs, want in ((at_peak, 100.0), (4 * at_peak, 25.0)):
        v = read_metric(_run(ev, secs), {"name": "mfu.batch"})
        assert v == pytest.approx(want, rel=1e-9)
        assert v <= 100.0 + 1e-9


def test_logit_roofline_on_synthetic_durations():
    rows = [300, 32, 512]          # real logit rows of three iterations
    iters = [dict(logit_tokens_real=n, sync_s=0.01) for n in rows]
    iters.append(dict(logit_tokens_real=999, sync_s=0.0))   # not synced
    tiles = 2 + 1 + 2
    F = sum(rows) * 2 * 4096 * 126464
    B = tiles * 2 * 126464 * 4096 + sum(rows) * 4096 * 2
    least = max(F / V5E["bf16_flops_per_s"], B / V5E["hbm_bytes_per_s"])
    for dur, want in ((least, 100.0), (2 * least, 50.0)):
        tr = types.SimpleNamespace(kernel_time=lambda k, d=dur:
                                   d if k == "logit_argmax" else 0.0)
        v = read_metric(_run([], 1.0, iters, tr),
                        {"name": "logit_roofline.batch"})
        assert v == pytest.approx(want, rel=1e-9) and v <= 100.0 + 1e-9
    tr0 = types.SimpleNamespace(kernel_time=lambda k: 0.0)
    assert read_metric(_run([], 1.0, iters, tr0),
                       {"name": "logit_roofline.batch"}) is None


def test_generator_pinned():
    tr = json.loads((HERE / "traffic" / "chat.json").read_text())
    cell = {"rate_rps": 2.0}
    a = TR.generate(tr, cell, 10.0, seed=123, vocab_size=1000, mask_id=999)
    assert len(a) == 20
    assert all(0 <= r.due < 10.0 for r in a)
    assert [len(r.prompt) for r in a[:5]] == PINNED_CHAT_LENS
    assert [round(r.due, 6) for r in a[:3]] == PINNED_CHAT_DUE
    assert all((r.prompt != 999).all() and r.prompt.max() < 1000 for r in a)
    # another seed: the same sizes and due times in the same order, other
    # prompt ids
    b = TR.generate(tr, cell, 10.0, seed=124, vocab_size=1000, mask_id=999)
    assert [(len(r.prompt), r.due) for r in a] == \
        [(len(r.prompt), r.due) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    bt = json.loads((HERE / "traffic" / "batch.json").read_text())
    c = TR.generate(bt, {"requests_per_second_of_window": 4}, 5.0, seed=7,
                    vocab_size=126464, mask_id=126463)
    assert len(c) == 20 and all(r.due == 0.0 for r in c)
    assert [len(r.prompt) for r in c[:5]] == PINNED_BATCH_LENS
    lo, hi = TR.length_range(bt)
    assert (lo, hi) == (150 + 256, 1200 + 256)


PINNED_CHAT_LENS = [518, 229, 345, 430, 311]
PINNED_CHAT_DUE = [0.25096, 0.62729, 0.6346]
PINNED_BATCH_LENS = [515, 484, 576, 512, 435]


def test_trace_reduction_by_hand():
    from chipbench import tracefile as TF
    ms = 1_000_000
    ev = {"devices": {"0": [
        ["%while.4 = (s32[]) while(%t), body=%b", 0 * ms, 15 * ms, ""],
        ["%fusion.1 = bf16[8] fusion(%flash_varlen_call.7)", 0, 10 * ms,
         ""],
        ["%fusion.2 = bf16[8] fusion(%x)", 5 * ms, 10 * ms, ""],
        ["%flash_varlen_call.7 = f32[32,640,128] custom-call(%a)",
         20 * ms, 10 * ms, ""],
        ["%copy.3 = bf16[8] copy(%y)", 45 * ms, 10 * ms, ""]]},
        "host": [["chipbench.window_open", 0, 1],
                 ["chipbench.window_close", 50 * ms, 1],
                 ["chipbench.sync", 14 * ms, 7 * ms],
                 ["chipbench.arrival_wait", 30 * ms, 15 * ms]]}
    s = TF.reduce(ev)
    assert s.window_s == pytest.approx(0.05)
    assert s.busy_s == pytest.approx(0.030)
    # a consumer of the kernel's output is not the kernel; the scan that
    # holds its body's operations counts in busy time only
    assert s.kernel_time("flash_varlen") == pytest.approx(0.010)
    assert s.ops["flash_varlen_call"] == pytest.approx(0.010)
    assert s.ops["fusion"] == pytest.approx(0.020)   # summed, not united
    assert "while" not in s.ops
    assert s.ops["copy"] == pytest.approx(0.005)
    assert s.gaps == [("host:arrival_wait", pytest.approx(0.015)),
                      ("host:sync", pytest.approx(0.005))]
    assert TF.breakdown(s)["idle_gaps"][0][0] == "host:arrival_wait"


def test_trace_reduction_on_a_recorded_chip_trace():
    """The first 40 device operations and harness spans of a traced chat
    window on a v5e (chip run, kept in testdata/): the window opened 2.47 s
    before the first request reached the device."""
    from chipbench import tracefile as TF
    ev = json.loads((HERE / "testdata" / "trace_chat_v5e.json").read_text())
    s = TF.reduce(ev)
    assert s.window_s == pytest.approx(2.529, abs=1e-3)
    assert 0 < s.busy_s < 0.1 * s.window_s
    assert "while" not in s.ops and "fusion" in s.ops
    assert sum(s.ops.values()) < s.busy_s
    assert s.kernel_time("flash_varlen") == 0.0
    name, gap = s.gaps[0]
    assert name == "host:arrival_wait" and gap == pytest.approx(2.4749,
                                                                 abs=1e-4)
    assert sum(g for _, g in s.gaps) <= s.window_s - s.busy_s + 1e-9
