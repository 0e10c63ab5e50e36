"""Mesh-serving agreement suite (the sharding oracle).

Two anchors:
  * in-process: a 1×1-mesh engine must be BIT-identical to the no-mesh
    engine — device_put to a one-device mesh and the sharded jit wrappers
    are numerically transparent, so every padded-vs-packed oracle keeps
    holding on the single-device path.
  * subprocess (2 CPU host devices, same precedent as the dry-run cells):
    ``launch/shard_check.py`` serves the same trace unsharded and on a
    ``REPRO_MESH=1,2`` mesh and demands matching committed token ids,
    captured slot-pool caches, and EngineStats token counters — for an
    attention arch and an SSM arch, with the jnp paths AND with the Pallas
    hot paths shard_mapped per shard (``--kernels``), plus a ``(2, 1)``
    data-axis mesh exercising the slot pool sharded over ``data``.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ServeConfig
from repro.core.engine import Engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ,
           PYTHONPATH=os.path.join(REPO, "src"),
           REPRO_XLA_FLAGS="--xla_force_host_platform_device_count=2",
           REPRO_MESH="1,2")

BASE = ServeConfig(max_num_batched_tokens=512, max_num_logits=64,
                   block_size=8, steps_per_block=8, max_seq_len=128,
                   max_slots=8, max_refresh_per_iter=2,
                   logit_mode="chunked", varlen_pack=True, token_bucket=64)


def _serve(serve, arch="llada-8b", n=4, seed=0):
    cfg = reduced(ARCHS[arch])
    eng = Engine(cfg, serve, seed=seed)
    rng = np.random.default_rng(seed)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size - 1,
                                    int(rng.integers(8, 40))),
                       gen_len=16, arrival=0.0, rid=i) for i in range(n)]
    stats = eng.run()
    return eng, reqs, stats


def test_1x1_mesh_bit_identical_to_no_mesh():
    import jax

    from repro.models import layers as Lmod
    saved = dict(Lmod._SHARDING_POLICY)
    try:
        eng0, r0, st0 = _serve(BASE)
        eng1, r1, st1 = _serve(dataclasses.replace(BASE, mesh_shape=(1, 1)))
        assert eng1.mesh_devices == 1
        for a, b in zip(r0, r1):
            assert np.array_equal(a.output_tokens(), b.output_tokens())
        assert st0.committed_tokens == st1.committed_tokens
        assert st0.refresh_tokens_exec == st1.refresh_tokens_exec
        for la, lb in zip(jax.tree.leaves(jax.device_get(eng0.pool.cache)),
                          jax.tree.leaves(jax.device_get(eng1.pool.cache))):
            assert np.array_equal(np.asarray(la), np.asarray(lb))
    finally:
        # the mesh engine installs a global serving policy — restore so later
        # (policy-free) tests in this process see the state they started with
        Lmod.set_sharding_policy(saved)


def test_1x1_mesh_bit_identical_with_kernels():
    """The bit-identity law must also hold with the Pallas hot paths live:
    a 1-sized model axis skips shard_map entirely (kernels.ops dispatches
    the identical local call), so 1×1-mesh == no-mesh byte for byte."""
    import jax

    from repro.models import layers as Lmod
    saved = dict(Lmod._SHARDING_POLICY)
    kbase = dataclasses.replace(BASE, use_flash_kernel=True,
                                logit_mode="fused")
    try:
        eng0, r0, st0 = _serve(kbase)
        eng1, r1, st1 = _serve(dataclasses.replace(kbase, mesh_shape=(1, 1)))
        assert eng1.mesh_devices == 1
        assert eng1.kernels_active
        for a, b in zip(r0, r1):
            assert np.array_equal(a.output_tokens(), b.output_tokens())
        assert st0.committed_tokens == st1.committed_tokens
        for la, lb in zip(jax.tree.leaves(jax.device_get(eng0.pool.cache)),
                          jax.tree.leaves(jax.device_get(eng1.pool.cache))):
            assert np.array_equal(np.asarray(la), np.asarray(lb))
    finally:
        Lmod.set_sharding_policy(saved)


def test_mesh_engine_rejects_indivisible_kernel_dims():
    """The old blanket mesh×kernels rejection is gone; what remains is the
    fail-loud divisibility law — validated BEFORE the mesh is built, so no
    3-device host is needed. Reduced llada has 4 heads / vocab 256: both
    indivisible by a 3-way model axis."""
    cfg = reduced(ARCHS["llada-8b"])
    with pytest.raises(ValueError, match="Pallas.*divide"):
        Engine(cfg, dataclasses.replace(BASE, mesh_shape=(1, 3),
                                        use_flash_kernel=True,
                                        logit_mode="fused"))
    # jnp paths on the same mesh shape carry no kernel divisibility law:
    # construction must get past kernel validation to the mesh build
    # (which then fails for lack of 3 devices — a different, device error)
    with pytest.raises(Exception) as ei:
        Engine(cfg, dataclasses.replace(BASE, mesh_shape=(1, 3)))
    assert "Pallas" not in str(ei.value)


@pytest.mark.parametrize("arch,extra", [
    ("llada-8b", ["--warmup"]),      # attention stream + sharded AOT warmup
    ("mamba2-130m", []),             # segment-reset SSD scan
    # Pallas hot paths per-shard: head-sharded varlen attention + fused
    # vocab-sharded argmax, SSD scan over state heads — vs the 1-device
    # kernel run (token ids bit-identical)
    ("llada-8b", ["--kernels"]),
    ("mamba2-130m", ["--kernels"]),
    # data-axis mesh: slot pool sharded over 'data' (padded slot axis),
    # replica streams serve the same trace bit-identically
    ("llada-8b", ["--kernels", "--mesh", "2,1"]),
    # refcounted prefix sharing over duplicated prompts: dedup hits, COW
    # promotes, and the promote-on-release target choice must be
    # device-count invariant (1-device run == 2-device mesh run)
    ("llada-8b", ["--sharing", "--n", "6"]),
])
def test_shard_agreement_subprocess(arch, extra, tmp_path):
    out = tmp_path / "agree.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.shard_check", "--arch", arch,
         "--out", str(out)] + extra,
        capture_output=True, text=True, env=ENV, cwd=REPO, timeout=420)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["ok"], rec
    assert rec["mesh_devices"] == 2, rec
    if "--kernels" in extra and arch == "llada-8b":
        # packed Reuse reads the head-sharded pool in place on a model
        # mesh; a pool whose slot axis is split over data is gathered
        inplace = rec["reuse_inplace_calls"]
        assert rec["packed_reuse_calls"] > 0, rec
        assert inplace == (0 if "2,1" in extra
                           else rec["packed_reuse_calls"]), rec
    if "--sharing" in extra:
        # shard_check itself fails on zero hits, but pin it here too:
        # a vacuous agreement run must never count as coverage
        assert rec["shared_hits"] > 0, rec
