"""Packed Reuse reads the retained K/V in place from the slot pool
(docs/engine.md, "Reuse reads the pool in place"): it agrees with the
padded oracle, copies no slot out of the pool, and the caches that cannot
be read in place (hybrid, SSM, int8) still gather."""
import dataclasses
import types

import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ServeConfig
from repro.core.engine import Engine
from repro.core.request import Phase, State

SERVE = ServeConfig(max_num_batched_tokens=512, max_num_logits=64,
                    block_size=8, steps_per_block=8, max_seq_len=128,
                    max_slots=8, max_refresh_per_iter=2,
                    selection="head", scheduler="phase", logit_mode="chunked",
                    varlen_pack=True, use_flash_kernel=True)


def _serve(serve, arch="llada-8b", prompts=None, n=5, warm=False,
           max_iters=100_000):
    cfg = reduced(ARCHS[arch])
    eng = Engine(cfg, serve, seed=0, clock="modeled")
    if warm:
        eng.warmup()
    rng = np.random.default_rng(0)
    if prompts is None:
        prompts = [rng.integers(0, cfg.vocab_size - 1,
                                int(rng.integers(8, 40))) for _ in range(n)]
    reqs = [eng.submit(p, gen_len=16, arrival=0.0, rid=i)
            for i, p in enumerate(prompts)]
    eng.run(max_iters=max_iters)
    return eng, reqs


@pytest.mark.parametrize("sharing", [False, True])
def test_inplace_reuse_matches_padded_oracle(sharing):
    """Mid-serve, the packed Reuse of every request in its Reuse phase,
    read in place through the slot table, gives the padded oracle's hidden
    states over gathered slots. Two requests share a prompt, so with
    prefix sharing one of them reads the other's pool row."""
    cfg = reduced(ARCHS["llada-8b"])
    rng = np.random.default_rng(1)
    base = [rng.integers(0, cfg.vocab_size - 1, n) for n in (20, 33, 12)]
    serve = dataclasses.replace(SERVE, prefix_sharing=sharing)
    eng, reqs = _serve(serve, prompts=[base[0], base[1], base[0], base[2]],
                       max_iters=3)
    assert eng._reuse_reads_pool()
    live = [r for r in reqs if r.state == State.RUNNING
            and r.phase == Phase.REUSE]
    assert len(live) >= 2
    if sharing:
        assert eng.stats.shared_hits > 0
        assert any(eng.pool.rows([r.slot])[0] != r.slot for r in live)
    Sb = serve.block_size
    layout = types.SimpleNamespace(
        requests=live, cu_seqlens=np.arange(len(live) + 1) * Sb)
    h_inplace, _ = eng._run_reuse_packed(layout)
    h_oracle, _ = eng._run_reuse(live)
    np.testing.assert_allclose(np.asarray(h_inplace),
                               np.asarray(h_oracle), atol=1e-4)


def test_inplace_reuse_gathers_nothing():
    """After warmup and a serve, no pool gather was ever compiled, every
    packed Reuse read the pool in place, and no iteration copied a slot."""
    eng, reqs = _serve(SERVE, warm=True)
    assert all(r.state == State.FINISHED for r in reqs)
    stats = eng.stats
    assert "pool_gather" not in eng._compile_counter
    assert stats.packed_reuse_calls > 0
    assert stats.reuse_inplace_calls == stats.packed_reuse_calls
    assert all(r["reuse_gathered_slots"] == 0 for r in stats.iter_log)


@pytest.mark.parametrize("arch,overrides", [
    ("zamba2-7b", {}),
    ("mamba2-130m", {}),
    ("llada-8b", {"kv_quant": "int8"}),
], ids=["hybrid", "ssm", "int8"])
def test_caches_not_read_in_place_still_gather(arch, overrides):
    """The hybrid and SSM caches and the int8 view take the gather path:
    every packed Reuse copies its slots, and iter_log counts them."""
    serve = dataclasses.replace(SERVE, **overrides)
    eng, reqs = _serve(serve, arch=arch, n=3)
    assert all(r.state == State.FINISHED for r in reqs)
    stats = eng.stats
    assert not eng._reuse_reads_pool()
    assert stats.packed_reuse_calls > 0 and stats.reuse_inplace_calls == 0
    assert eng._compile_counter["pool_gather"] > 0
    rows = [r for r in stats.iter_log if r["n_reuse"]]
    assert rows and all(r["reuse_gathered_slots"] >= r["n_reuse"]
                        for r in rows)
