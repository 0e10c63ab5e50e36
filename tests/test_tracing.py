"""Names and stamps the engine leaves for a trace reader: each stage and
pool program is compiled under its entry name, and commits are stamped
when their values reach the host (wall clock) or at dispatch (modeled
clock). The engine's spans are read back from a profiler trace in
``chipbench/test_bench_stages.py``."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import jax_compat as JC
from repro.configs import ARCHS, reduced
from repro.configs.base import ServeConfig
from repro.core.engine import Engine
from repro.core.request import State

BASE = ServeConfig(max_num_batched_tokens=512, max_num_logits=64,
                   block_size=8, steps_per_block=8, max_seq_len=128,
                   max_slots=8, max_refresh_per_iter=2,
                   selection="head", scheduler="phase", logit_mode="chunked",
                   varlen_pack=True)


@pytest.mark.parametrize("wrap", [
    lambda f: JC.jit(f, entry="x"),
    lambda f: JC.jit_sharded(f, mesh=None, entry="x"),
], ids=["jit", "jit_sharded"])
def test_entry_names_the_compiled_program(wrap):
    def fn(a):
        return a * 2

    text = wrap(fn).lower(jnp.ones(3)).as_text()
    assert text.startswith("module @jit_x ")
    assert fn.__name__ == "fn"            # the wrapped function keeps its own


def _submit(eng, cfg, n, seed=0, arrival=0.0):
    rng = np.random.default_rng(seed)
    return [eng.submit(rng.integers(0, cfg.vocab_size - 1,
                                    int(rng.integers(8, 40))),
                       gen_len=16, arrival=arrival * i, rid=i)
            for i in range(n)]


def test_engine_programs_are_named_by_stage():
    names = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        cfg = reduced(ARCHS["llada-8b"])
        serve = dataclasses.replace(BASE, prefix_sharing=True)
        eng = Engine(cfg, serve, seed=0)
        eng.warmup()
        _submit(eng, cfg, 3)
        eng.run()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    entries = set(eng._compile_counter)
    assert {"refresh_packed", "reuse_packed", "decode_packed", "pool_write",
            "pool_gather", "pool_copy"} <= entries
    assert {f"jit({e})" for e in entries} <= set(names)
    assert not {"jit(fn)", "jit(<lambda>)", "jit(wfn)"} & set(names)


def test_wall_clock_commits_are_stamped_where_values_land():
    cfg = reduced(ARCHS["llada-8b"])
    events = []
    eng = Engine(cfg, BASE, seed=0, clock="wall", stream_cb=events.append)
    dispatched = {}
    dispatch = eng._dispatch_iteration

    def record(prep):
        t = eng._run_clock()
        for r in prep.plan.refresh + prep.plan.reuse:
            dispatched.setdefault(r.rid, t)
        time.sleep(0.002)       # the values cannot land before this
        return dispatch(prep)

    eng._dispatch_iteration = record
    reqs = _submit(eng, cfg, 4, arrival=0.01)
    eng.run()
    assert all(r.state == State.FINISHED for r in reqs)
    first = {}
    for e in events:
        first.setdefault(e["rid"], e)
    for r in reqs:
        # the first commit lands after the dispatch that committed it
        assert r.t_first_commit >= dispatched[r.rid] + 0.002
        assert first[r.rid]["t"] == r.t_first_commit
        fin = [e for e in events if e["rid"] == r.rid and e["finished"]]
        assert len(fin) == 1 and fin[0]["t"] == r.t_finished
        assert r.t_first_commit <= r.t_finished <= eng.stats.wall_time


def test_modeled_clock_stamps_commits_at_dispatch():
    """Modeled stamps are the dispatch's vtime: the same in both loops,
    and the stream event carries the same stamp."""
    cfg = reduced(ARCHS["llada-8b"])
    out = {}
    for pipe in (False, True):
        events = []
        eng = Engine(cfg, dataclasses.replace(BASE, pipeline=pipe), seed=0,
                     clock="modeled", stream_cb=events.append)
        vtimes = set()
        dispatch = eng._dispatch_iteration

        def record(prep):
            pending = dispatch(prep)
            vtimes.add(eng.vtime)
            return pending

        eng._dispatch_iteration = record
        reqs = _submit(eng, cfg, 4, arrival=0.05)
        eng.run()
        for r in reqs:
            assert r.t_first_commit in vtimes and r.t_finished in vtimes
            assert r.t_first_commit < r.t_finished
            assert [e["t"] for e in events if e["rid"] == r.rid][0] == \
                r.t_first_commit
        out[pipe] = [(r.t_first_commit, r.t_finished) for r in reqs]
    assert out[False] == out[True]
