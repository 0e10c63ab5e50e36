"""Bit-identity of the pipelined dispatch-ahead loop vs the sync oracle.

The tentpole contract (docs/engine.md): ``pipeline=True`` restructures WHEN
host work happens — plan i+1 while i executes, ONE deferred device_get — but
must change NOTHING observable: token ids, every EngineStats counter, the
final KV-pool device cache, and the compile ledger are exact matches against
``pipeline=False`` (which syncs every iteration), on the modeled clock,
across padded/packed layouts, attention/SSM models, and under
preemption + injected faults.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ServeConfig
from repro.core.engine import Engine
from repro.core.faults import FaultPlan
from repro.core.request import State

BASE = ServeConfig(max_num_batched_tokens=512, max_num_logits=64,
                   block_size=8, steps_per_block=8, max_seq_len=128,
                   max_slots=8, max_refresh_per_iter=2,
                   selection="head", scheduler="phase", logit_mode="chunked")

# every integer EngineStats counter — the conservation surface. Timing
# fields (host_plan_s & co) legitimately differ between the two loops;
# wall_time on the modeled clock is vtime and must match to fp tolerance.
COUNTERS = (
    "iterations", "refresh_steps", "reuse_steps", "committed_tokens",
    "deferred_steps", "peak_query_tokens",
    "refresh_tokens_real", "refresh_tokens_exec",
    "reuse_tokens_real", "reuse_tokens_exec",
    "logit_tokens_real", "logit_tokens_exec",
    "packed_refresh_calls", "padded_refresh_calls",
    "packed_reuse_calls", "padded_reuse_calls",
    "submitted", "finished", "rejected_oversized", "rejected_queue_full",
    "shed_deadline", "shed_queue", "preemptions", "recomputed_tokens",
    "dispatch_retries", "shared_hits", "shared_cow_promotes",
    "phys_slots_peak", "alloc_fault_iters",
)


def _run(pipeline, serve=BASE, arch="llada-8b", n=5, seed=0,
         fault_seed=None, stream_events=None, warm=False):
    cfg = reduced(ARCHS[arch])
    sv = dataclasses.replace(serve, pipeline=pipeline)
    faults = FaultPlan.seeded(fault_seed) if fault_seed is not None else None
    cb = stream_events.append if stream_events is not None else None
    eng = Engine(cfg, sv, seed=seed, clock="modeled", faults=faults,
                 stream_cb=cb)
    if warm:
        eng.warmup()
    rng = np.random.default_rng(seed)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size - 1,
                                    int(rng.integers(8, 40))),
                       gen_len=16, arrival=0.05 * i, rid=i)
            for i in range(n)]
    stats = eng.run()
    return eng, reqs, stats


def _assert_identical(sync, pipe):
    es, rs, ss = sync
    ep, rp, sp = pipe
    for a, b in zip(rs, rp):
        assert a.state == b.state
        assert np.array_equal(a.tokens, b.tokens), a.rid
    for k in COUNTERS:
        assert getattr(ss, k) == getattr(sp, k), k
    assert abs(ss.wall_time - sp.wall_time) < 1e-9
    # identical dispatch sequence => identical compile ledger: pipelining
    # may not introduce a single extra trace
    assert dict(ss.compile_counts) == dict(sp.compile_counts)
    # the final device caches saw the same write sequence
    cs, cp = jax.device_get((es.pool.cache, ep.pool.cache))
    for a, b in zip(jax.tree.leaves(cs), jax.tree.leaves(cp)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the loops really differed: dispatch-ahead overlapped host work
    assert ss.overlap_frac == 0.0 and ss.dispatched_ahead == 0
    if sp.iterations > 1:
        assert sp.overlap_frac > 0.0
        assert sp.dispatched_ahead > 0


@pytest.mark.parametrize("arch", ["llada-8b", "mamba2-130m"])
@pytest.mark.parametrize("packed", [False, True])
def test_pipelined_is_bit_identical(arch, packed):
    serve = dataclasses.replace(BASE, varlen_pack=packed)
    _assert_identical(_run(False, serve, arch=arch),
                      _run(True, serve, arch=arch))


@pytest.mark.parametrize("fault_seed", [None, 3], ids=["clean", "faults"])
def test_pipelined_in_place_reuse_is_bit_identical(fault_seed):
    """Packed Reuse that reads the slot pool in place, followed in the next
    iteration by a Refresh whose pool write donates that pool: the
    pipelined loop dispatches the write while the Reuse may still read the
    old buffer, and must land the sync oracle's tokens. With injected
    faults a retried dispatch reads the pool as it is then."""
    serve = dataclasses.replace(BASE, varlen_pack=True,
                                use_flash_kernel=True)
    sync = _run(False, serve, fault_seed=fault_seed)
    pipe = _run(True, serve, fault_seed=fault_seed)
    _assert_identical(sync, pipe)
    ss = pipe[2]
    assert ss.reuse_inplace_calls == ss.packed_reuse_calls > 0
    log = list(ss.iter_log)
    assert any(a["n_reuse"] and b["n_refresh"]
               for a, b in zip(log, log[1:]))
    if fault_seed is not None:
        assert ss.dispatch_retries > 0


def test_bit_identical_under_preemption_and_faults():
    """Chaos + starvation preemption: in-flight commits whose request was
    preempted must be discarded EXACTLY as the oracle overwrites them —
    epoch mismatches, rollback debt, and retries all line up."""
    serve = dataclasses.replace(BASE, max_slots=4,
                                preempt_starvation_s=0.05)
    sync = _run(False, serve, n=6, fault_seed=3)
    pipe = _run(True, serve, n=6, fault_seed=3)
    _assert_identical(sync, pipe)
    assert sync[2].preemptions + sync[2].dispatch_retries > 0, \
        "chaos run exercised neither preemption nor retries"


def test_zero_post_warmup_compiles_pipelined():
    """The dispatch-ahead loop reuses the same warmed entry points: a full
    pipelined serve after warmup adds ZERO compilations (padded path)."""
    eng, reqs, stats = _run(True, warm=True)
    assert all(r.state == State.FINISHED for r in reqs)
    assert stats.compiles_warmup > 0
    assert stats.compiles_post_warmup == 0, stats.compile_counts


def test_stream_callback_accounts_every_commit():
    events = []
    eng, reqs, stats = _run(True, stream_events=events)
    assert len(events) == stats.streamed_events > 0
    assert sum(e["n_committed"] for e in events) == stats.committed_tokens
    fin = [e for e in events if e["finished"]]
    assert len(fin) == len(reqs)
    # the final streamed block of each request matches its actual tokens
    for e in fin:
        r = reqs[e["rid"]]
        s = r.prompt_len + e["block_idx"] * BASE.block_size
        assert np.array_equal(e["tokens"], r.tokens[s:s + BASE.block_size])
    # events fire at the deferred sync, so timestamps are the modeled
    # commit times — monotone per request
    by_rid = {}
    for e in events:
        assert e["t"] >= by_rid.get(e["rid"], -1.0)
        by_rid[e["rid"]] = e["t"]


def test_iter_log_records_per_stage_host_times():
    _, _, stats = _run(True)
    rows = list(stats.iter_log)
    assert rows, "iter_log empty"
    for row in rows:
        assert row["plan_s"] >= 0.0 and row["fill_s"] >= 0.0
        assert row["sync_s"] >= 0.0
    # every dispatched iteration was synced exactly once: sync_wait_s is
    # the sum of the per-row sync times
    assert abs(sum(r["sync_s"] for r in rows) - stats.sync_wait_s) < 1e-6
