"""The stage view of a trace (``stages.py``): the engine's ``dllm.*`` spans
read back from a CPU profiler trace, the reduction by hand and on a small
recorded chip trace, and the stage metrics."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import stages as ST

HERE = Path(__file__).resolve().parent
MS = 1_000_000


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def _parent(ev, spans, names):
    return [p for p in spans if p[0] in names and _inside(ev, p)]


def test_engine_spans_in_a_cpu_trace(tmp_path):
    import jax
    from repro.configs import ARCHS, reduced
    from repro.configs.base import ServeConfig
    from repro.core.engine import SPANS, Engine
    serve = ServeConfig(max_num_batched_tokens=512, max_num_logits=64,
                        block_size=8, steps_per_block=8, max_seq_len=128,
                        max_slots=8, max_refresh_per_iter=2,
                        selection="head", scheduler="phase",
                        logit_mode="chunked", varlen_pack=True)
    cfg = reduced(ARCHS["llada-8b"])
    eng = Engine(cfg, serve, seed=0, clock="wall")
    eng.warmup()
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(rng.integers(0, cfg.vocab_size - 1, 20), gen_len=16,
                   arrival=0.15 * i, rid=i)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    stats = eng.run()
    jax.profiler.stop_trace()
    ev = ST.load(tmp_path)
    assert ev["devices"] == {}            # the CPU has no TPU plane
    spans = sorted((e for e in ev["host"] if e[0] in SPANS),
                   key=lambda e: (e[1], -e[2]))
    by = {n: [e for e in spans if e[0] == n] for n in SPANS}
    n_it = len(stats.iter_log)
    assert len(by["dllm.dispatch"]) == n_it == len(by["dllm.dispatch.logits"])
    assert len(by["dllm.sync"]) == len(by["dllm.sync.wait"]) == n_it
    assert len(by["dllm.plan"]) >= n_it and by["dllm.arrival_wait"]
    top = [e for e in spans if e[0] in ("dllm.plan", "dllm.dispatch",
                                        "dllm.sync", "dllm.arrival_wait")]
    for a, b in zip(top, top[1:]):
        assert a[1] + a[2] <= b[1], (a, b)          # never overlap
    # every child inside its parent, as in docs/engine.md "Spans"
    parents = {"dllm.dispatch.refresh": ("dllm.dispatch",),
               "dllm.dispatch.reuse": ("dllm.dispatch",),
               "dllm.dispatch.logits": ("dllm.dispatch",),
               "dllm.pool.write": ("dllm.dispatch",),
               "dllm.pool.gather": ("dllm.dispatch.reuse",),
               "dllm.sync.wait": ("dllm.sync",),
               "dllm.sync.land": ("dllm.sync",)}
    for child, names in parents.items():
        assert by[child], child
        for e in by[child]:
            assert len(_parent(e, spans, names)) == 1, e
    for e in by["dllm.pool.write"]:
        assert not _parent(e, spans, ("dllm.dispatch.refresh",))
    # in each dispatch: Refresh, its pool write, Reuse, then the logits
    order = ["dllm.dispatch.refresh", "dllm.pool.write",
             "dllm.dispatch.reuse", "dllm.dispatch.logits"]
    for d in by["dllm.dispatch"]:
        kids = [e[0] for e in spans if e[0] in order and _inside(e, d)]
        assert kids == sorted(kids, key=order.index) and \
            kids[-1] == "dllm.dispatch.logits"
    # the pipelined loop: dispatch k, then a plan, then the sync of k
    for k, (d, s) in enumerate(zip(by["dllm.dispatch"], by["dllm.sync"])):
        assert d[1] + d[2] <= s[1]
        if k + 1 < n_it:
            assert s[1] + s[2] <= by["dllm.dispatch"][k + 1][1]
    for s in by["dllm.sync"]:
        w, = [e for e in by["dllm.sync.wait"] if _inside(e, s)]
        land, = [e for e in by["dllm.sync.land"] if _inside(e, s)]
        assert w[1] + w[2] <= land[1]


def test_stage_view_of_a_rehearsed_traced_window(tmp_path):
    """The harness's traced window at the rehearsal size, trace kept: the
    CPU has no device plane, so the whole window is idle and every piece of
    it lies under an engine span or none; the program-based metrics have
    nothing to read."""
    from chipbench import rehearsal
    res = rehearsal.run("llada-8b-1chip.chat", seed=9, trace=True,
                        trace_dir=tmp_path)
    assert res["correct"] is True
    s = ST.reduce(ST.load(tmp_path))
    assert s.busy_s == 0 and s.modules == {}
    assert s.window_s == pytest.approx(res["device"]["window_s"])
    assert sum(s.engine_idle.values()) == pytest.approx(s.window_s)
    assert {"dllm.plan", "dllm.dispatch.refresh", "dllm.sync.wait",
            "dllm.arrival_wait"} <= set(s.engine_idle)
    m = ST.metrics(s, [dict(refresh_tokens_real=1, reuse_tokens_real=1,
                            logit_tokens_real=1)])
    assert m["refresh_us_per_tok.chat"] is None
    assert m["pool_ms_per_iter.chat"] is None
    assert 0 < m["host_stall_frac.chat"] < 1


def _hand_events():
    """Window 0-100 ms. Device: Refresh 5-25 (with a hole at 10-11),
    pool write 25-30, logits 60-70, glue 70-72. Host: plan 0-4, dispatch
    4-40 (refresh 4-20, pool write 20-24, logits 30-40), sync 40-50 (wait
    40-44, land 44-50), arrival wait 50-58, plan 58-60, dispatch 60-62,
    nothing 62-80, sync 80-90."""
    ops = [["%fusion.1 = f32[8] fusion()", 5 * MS, 5 * MS],
           ["%fusion.2 = f32[8] fusion()", 11 * MS, 14 * MS],
           ["%dus.1 = f32[8] dynamic-update-slice()", 25 * MS, 5 * MS],
           ["%call.1 = f32[8] custom-call()", 60 * MS, 10 * MS],
           ["%concatenate.1 = f32[8] concatenate()", 70 * MS, 2 * MS]]
    mods = [["jit_refresh_packed", 5 * MS, 20 * MS],
            ["jit_pool_write", 25 * MS, 5 * MS],
            ["jit_decode_packed", 60 * MS, 10 * MS],
            ["jit_concatenate", 70 * MS, 2 * MS]]
    host = [["chipbench.window_open", 0, 1],
            ["chipbench.window_close", 100 * MS, 1],
            ["dllm.plan", 0, 4 * MS], ["dllm.dispatch", 4 * MS, 36 * MS],
            ["dllm.dispatch.refresh", 4 * MS, 16 * MS],
            ["dllm.pool.write", 20 * MS, 4 * MS],
            ["dllm.dispatch.logits", 30 * MS, 10 * MS],
            ["dllm.sync", 40 * MS, 10 * MS],
            ["dllm.sync.wait", 40 * MS, 4 * MS],
            ["dllm.sync.land", 44 * MS, 6 * MS],
            ["dllm.arrival_wait", 50 * MS, 8 * MS],
            ["dllm.plan", 58 * MS, 2 * MS],
            ["dllm.dispatch", 60 * MS, 2 * MS],
            ["dllm.sync", 80 * MS, 10 * MS]]
    return {"devices": {"0": ops}, "modules": {"0": mods}, "host": host}


def test_stage_reduction_by_hand():
    s = ST.reduce(_hand_events())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.036)
    # a program's time is busy time inside it: the hole is not Refresh's
    assert s.modules["jit_refresh_packed"] == [pytest.approx(0.019), 1]
    assert s.modules["jit_pool_write"] == [pytest.approx(0.005), 1]
    assert s.modules["jit_decode_packed"] == [pytest.approx(0.010), 1]
    assert s.module_s(*ST.ENGINE_MODULES) == pytest.approx(0.034)
    idle = s.engine_idle
    assert idle["dllm.plan"] == pytest.approx(0.004 + 0.002)
    assert idle["dllm.dispatch.refresh"] == pytest.approx(0.002)  # 4-5, 10-11
    assert "dllm.dispatch" not in idle            # its children cover it
    assert idle["dllm.dispatch.logits"] == pytest.approx(0.010)   # 30-40
    assert idle["dllm.sync.wait"] == pytest.approx(0.004)
    assert idle["dllm.sync.land"] == pytest.approx(0.006)
    assert idle["dllm.arrival_wait"] == pytest.approx(0.008)
    assert idle["dllm.sync"] == pytest.approx(0.010)              # 80-90
    assert idle[ST.NONE] == pytest.approx(0.008 + 0.010)          # 72-80, 90-100
    assert sum(idle.values()) == pytest.approx(s.idle_s)
    assert s.long_idle_s == pytest.approx(s.idle_s)
    assert s.long_idle_spanned_s == pytest.approx(s.idle_s - 0.018)
    assert s.long_gaps[0][0] == pytest.approx(0.030)              # 30-60
    iters = [dict(refresh_tokens_real=400, reuse_tokens_real=0,
                  logit_tokens_real=64),
             dict(refresh_tokens_real=0, reuse_tokens_real=0,
                  logit_tokens_real=0)]
    m = ST.metrics(s, iters)
    assert m["refresh_us_per_tok.chat"] == pytest.approx(19e3 / 400)
    assert m["reuse_us_per_tok.chat"] is None     # no Reuse program ran
    assert m["logit_us_per_row.chat"] == pytest.approx(10e3 / 64)
    assert m["pool_ms_per_iter.chat"] == pytest.approx(5.0 / 2)
    assert m["host_stall_frac.chat"] == pytest.approx((0.064 - 0.008) / 0.1)
    c = ST.checks(s)
    assert c["engine_module_share"] == pytest.approx(0.034 / 0.036)
    assert c["other_modules"] == [["jit_concatenate", pytest.approx(0.002),
                                   1]]


def test_innermost_span_of_nested_and_adjacent_spans():
    pieces = ST.innermost([("a", 0, 10), ("b", 2, 5), ("c", 5, 7),
                           ("d", 12, 14)])
    assert pieces == [(0, 2, "a"), (2, 5, "b"), (5, 7, "c"), (7, 10, "a"),
                      (12, 14, "d")]
    assert ST.module_name("jit_refresh_packed(2553684796414980458)") == \
        "jit_refresh_packed"


def test_reduction_needs_the_window_marks():
    ev = _hand_events()
    ev["host"] = ev["host"][2:]
    with pytest.raises(ValueError, match="window"):
        ST.reduce(ev)


def test_stage_reduction_on_a_recorded_chip_trace():
    """Three iterations of a traced chat window on a v5e (chip run, kept in
    testdata/): a Refresh, three Reuse steps, their pool gathers and logit
    stages, and the engine's spans over them."""
    ev = json.loads((HERE / "testdata" / "trace_chat_v5e_stages.json")
                    .read_text())
    s = ST.reduce(ev)
    assert s.window_s == pytest.approx(0.098579, abs=1e-6)
    assert s.busy_s == pytest.approx(0.089587, abs=1e-6)
    assert sum(v[0] for v in s.modules.values()) <= s.busy_s
    assert sum(s.engine_idle.values()) == pytest.approx(s.idle_s)
    calls = {k: v[1] for k, v in s.modules.items()}
    assert calls == {"jit_refresh_packed": 1, "jit_pool_write": 1,
                     "jit_pool_gather": 3, "jit_reuse_packed": 3,
                     "jit_decode_packed": 4, "jit_reshape": 7}
    assert ST.checks(s)["engine_module_share"] > 0.999
    assert ST.checks(s)["long_idle_spanned_share"] > 0.99
    # between iterations the device waits for the host's device_get
    assert max(s.engine_idle, key=s.engine_idle.get) == "dllm.sync.wait"
    assert s.engine_idle["dllm.sync.wait"] > 0.9 * s.idle_s
    # the gathers start on the device before their host spans: the trace's
    # device clock leads the host's by at least 0.41 ms
    assert ST.clock_lead_ms(ev) == pytest.approx(-0.4123, abs=1e-3)
