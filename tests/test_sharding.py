"""Sharding-rule validity + per-device memory-plan invariants.

``Rules`` must emit placeable specs for ANY (heads, mesh) combination —
jax rejects uneven shards, so every sharded dim has to divide exactly
(non-divisible dims replicate; KV-head non-divisibility engages the
retained-length fallback) — and the ``Rules.cache`` spec trees must match
the actual cache pytrees the backbone emits (what the sharded ``KVPool``
allocates from). The per-device ``plan_memory`` arithmetic mirrors the same
divisibility laws, so its capacity-coupling invariant is tested here too.
"""
import dataclasses
import functools

import jax
import pytest

from tests._hyp_compat import given, settings, st

from repro.configs import ARCHS, get_config, reduced
from repro.configs.base import ServeConfig
from repro.launch.mesh import SimMesh, axis_size
from repro.launch.sharding import Rules
from repro.models import backbone as BB
from repro.models import transformer as T

FAMILY_ARCHS = ("llada-8b", "mamba2-130m", "zamba2-7b")


def _spec_leaves(shapes, specs):
    """(shape-leaf, spec) pairs with PartitionSpecs kept atomic."""
    s_leaves, treedef = jax.tree.flatten(shapes)
    return list(zip(s_leaves, treedef.flatten_up_to(specs)))


def _assert_valid(mesh, leaf, spec, where=""):
    """The placeability law: len(spec) == ndim, each mesh axis used at most
    once, and every sharded dim divisible by its combined shard count."""
    spec = tuple(spec)
    assert len(spec) <= leaf.ndim, (where, spec, leaf.shape)
    used = []
    for dim, entry in zip(leaf.shape, spec + (None,) * leaf.ndim):
        axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
        shards = 1
        for a in axes:
            assert a not in used, (where, spec, "axis reused")
            used.append(a)
            shards *= axis_size(mesh, a)
        assert shards <= 1 or (dim % shards == 0 and dim >= shards), \
            (where, spec, leaf.shape, f"dim {dim} not divisible by {shards}")


MESHES = ((1, 1), (1, 2), (2, 2), (1, 3), (2, 4), (1, 16), (2, 2, 2))


@settings(max_examples=30, deadline=None)
@given(arch=st.sampled_from(FAMILY_ARCHS + ("gemma-2b", "internvl2-76b")),
       mesh_i=st.integers(0, len(MESHES) - 1),
       n_heads=st.sampled_from((1, 2, 3, 4, 6, 8)),
       kv_div=st.sampled_from((1, 2, 4)),
       train=st.booleans())
def test_rules_specs_always_placeable(arch, mesh_i, n_heads, kv_div, train):
    """Property: ANY (heads, mesh) combination yields placeable specs for
    params AND all three cache families — non-divisible dims replicated."""
    kv = max(1, n_heads // kv_div)
    if n_heads % kv:
        kv = n_heads
    cfg = reduced(ARCHS[arch], n_heads=n_heads, n_kv_heads=kv)
    mesh = SimMesh(MESHES[mesh_i])
    rules = Rules(cfg, mesh, train=train)
    shapes = jax.eval_shape(functools.partial(BB.init_params, cfg),
                            jax.random.PRNGKey(0))
    for leaf, spec in _spec_leaves(shapes, rules.params(shapes)):
        _assert_valid(mesh, leaf, spec, where="params")
    for batch in (1, 5, 8):
        for retain in (24, 64, 63):
            cache_shapes = _analytic_cache_shapes(cfg, batch, retain)
            specs = rules.cache(batch, retain)
            for leaf, spec in _spec_leaves(cache_shapes, specs):
                _assert_valid(mesh, leaf, spec, where=f"cache r={retain}")


def _analytic_cache_shapes(cfg, batch, retain):
    """Family cache pytree, shape-only — the SAME shape model the profiler
    bills per-device slot bytes with (no second copy to drift; anchored
    against the real ``eval_shape`` tree in
    ``test_cache_specs_match_backbone_cache_structure``)."""
    from repro.core.budgeting import _slot_cache_shapes
    return _slot_cache_shapes(cfg, ServeConfig(), retain,
                              batch=batch)


def _cache_shapes(cfg, batch, retain):
    """The REAL cache pytree (shape-only) a Refresh step emits — what the
    sharded KVPool allocates from, so ``Rules.cache`` must match it."""
    ctx = T.ServeContext(block_size=8, retain=retain, kernel_size=3,
                         selection="head", q_chunk=64, max_seq_len=64)
    S = 64
    out = jax.eval_shape(
        lambda p, t, bs: BB.serve_refresh(
            p, cfg, t, bs, ctx,
            frontend=(jax.ShapeDtypeStruct(
                (batch, cfg.frontend_len, cfg.frontend_dim), "float32")
                if cfg.frontend_dim else None)),
        jax.eval_shape(functools.partial(BB.init_params, cfg),
                       jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((batch, S), "int32"),
        jax.ShapeDtypeStruct((batch,), "int32"))
    return out.cache


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_cache_specs_match_backbone_cache_structure(arch):
    """``Rules.cache`` emits the exact pytree structure of each family's
    cache (PackedKV / SSMCache / HybridCache) with one spec entry per dim —
    the contract the sharded KVPool's tree_map allocation relies on."""
    from jax.sharding import PartitionSpec
    cfg = reduced(ARCHS[arch])
    rules = Rules(cfg, SimMesh((1, 2)), train=False)
    cache_shapes = _cache_shapes(cfg, batch=4, retain=24)
    specs = rules.cache(4, 24)
    assert jax.tree.structure(cache_shapes) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    for leaf, spec in _spec_leaves(cache_shapes, specs):
        assert len(tuple(spec)) == leaf.ndim, (arch, spec, leaf.shape)
    # the analytic shape model the property test samples from must agree
    # with the real backbone cache tree
    analytic = _analytic_cache_shapes(cfg, batch=4, retain=24)
    assert jax.tree.structure(analytic) == jax.tree.structure(cache_shapes)
    assert [tuple(a.shape) for a in jax.tree.leaves(analytic)] \
        == [tuple(b.shape) for b in jax.tree.leaves(cache_shapes)]


def test_retained_length_fallback_engages_on_mqa():
    """KV heads not divisible (MQA K=1 on model=2) -> heads replicated and
    the retained-length axis picks up the model sharding when divisible,
    stays replicated otherwise."""
    cfg = reduced(ARCHS["gemma-2b"])     # MQA: n_kv_heads == 1
    assert cfg.n_kv_heads == 1
    rules = Rules(cfg, SimMesh((1, 2)), train=False)
    kv = rules.packed_kv(batch=5, retain=64)      # batch%1==0 -> b over data
    assert tuple(kv.k)[2] is None                 # K replicated
    r_axes = tuple(kv.k)[3]                       # 'model' or ('model',)
    r_axes = (r_axes,) if isinstance(r_axes, str) else tuple(r_axes or ())
    assert "model" in r_axes, kv.k                # R sharded
    kv_odd = rules.packed_kv(batch=5, retain=63)  # 63 % 2 != 0
    assert tuple(kv_odd.k)[3] in (None, ()), kv_odd.k     # replicated


def test_divisible_heads_shard_over_model():
    cfg = reduced(ARCHS["llada-8b"])              # reduced: 4 KV heads
    rules = Rules(cfg, SimMesh((1, 2)), train=False)
    kv = rules.packed_kv(batch=4, retain=64)
    assert tuple(kv.k)[2] == "model"


# ---------------------------------------------------------------------------
# per-device memory planning (the §4.2-4.3 coupling on an N-device mesh)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", FAMILY_ARCHS)
@pytest.mark.parametrize("mesh_shape", [(1, 2), (1, 4)])
def test_plan_memory_per_device_capacity_coupling(arch, mesh_shape):
    """On a simulated N-device mesh the profiler must bill strictly smaller
    per-device weight + KV-slot bytes than one device and convert the freed
    headroom into at least as many (here: strictly more) slots."""
    from repro.core.budgeting import plan_memory
    cfg = get_config(arch)
    base = ServeConfig(max_num_batched_tokens=4000, max_num_logits=2048,
                       max_seq_len=2048, max_slots=1 << 20)
    hbm = 48 << 30
    p1 = plan_memory(cfg, base, hbm)
    pn = plan_memory(cfg, dataclasses.replace(base, mesh_shape=mesh_shape),
                     hbm)
    assert pn.mesh_devices == mesh_shape[0] * mesh_shape[1]
    assert pn.weights_bytes < p1.weights_bytes
    assert pn.slot_bytes < p1.slot_bytes
    assert pn.kv_pool_bytes >= p1.kv_pool_bytes
    assert pn.max_slots > p1.max_slots, (p1.summary(), pn.summary())


def test_plan_memory_no_mesh_equals_1x1_mesh():
    from repro.core.budgeting import plan_memory
    cfg = get_config("llada-8b")
    base = ServeConfig(max_num_batched_tokens=4000, max_seq_len=2048,
                       max_slots=64)
    p0 = plan_memory(cfg, base, 24 << 30)
    p1 = plan_memory(cfg, dataclasses.replace(base, mesh_shape=(1, 1)),
                     24 << 30)
    assert (p0.weights_bytes, p0.slot_bytes, p0.max_slots) \
        == (p1.weights_bytes, p1.slot_bytes, p1.max_slots)


def test_plan_memory_data_axis_replica_slots():
    """The slot pool shards its slot axis over ``data``: a (2, m) mesh
    carries 2 independent replica streams, so global slot capacity must be
    >= 2x the (1, m) plan (per-device bytes are identical — the data axis
    replicates weights at serve time)."""
    from repro.core.budgeting import plan_memory
    cfg = get_config("llada-8b")
    base = ServeConfig(max_num_batched_tokens=4000, max_num_logits=2048,
                       max_seq_len=2048, max_slots=1 << 20)
    hbm = 48 << 30
    p1 = plan_memory(cfg, dataclasses.replace(base, mesh_shape=(1, 2)), hbm)
    p2 = plan_memory(cfg, dataclasses.replace(base, mesh_shape=(2, 2)), hbm)
    assert p2.weights_bytes == p1.weights_bytes
    assert p2.slot_bytes == p1.slot_bytes
    assert p2.max_slots >= 2 * p1.max_slots, (p1.summary(), p2.summary())


# ---------------------------------------------------------------------------
# Pallas kernel partitioning law (kernels × TP; see kernels.ops)
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(arch=st.sampled_from(FAMILY_ARCHS),
       mesh_i=st.integers(0, len(MESHES) - 1),
       n_heads=st.sampled_from((1, 2, 3, 4, 6, 8)),
       kv_div=st.sampled_from((1, 2, 4)),
       flash=st.booleans(),
       fused=st.booleans())
def test_kernel_partition_plan_never_silently_falls_back(
        arch, mesh_i, n_heads, kv_div, flash, fused):
    """Property: ANY (heads, vocab) × mesh combination with kernels enabled
    either yields a full per-shard partition plan (every enabled kernel dim
    divides the model axis) or raises the divisibility ValueError — there is
    no middle ground where a kernel would silently run replicated."""
    from repro.launch.sharding import kernel_partition_plan
    kv = max(1, n_heads // kv_div)
    if n_heads % kv:
        kv = n_heads
    cfg = reduced(ARCHS[arch], n_heads=n_heads, n_kv_heads=kv)
    serve = ServeConfig(
        mesh_shape=MESHES[mesh_i], use_flash_kernel=flash,
        logit_mode="fused" if fused else "chunked")
    m = serve.mesh_model
    dims = {}
    if flash:
        if cfg.has_attention:
            dims["n_heads"] = cfg.n_heads
            dims["n_kv_heads"] = cfg.n_kv_heads
        if cfg.ssm_state:
            dims["ssm_heads"] = cfg.ssm_heads
    if fused:
        dims["vocab_size"] = cfg.vocab_size
    divisible = all(v % m == 0 for v in dims.values())
    if divisible:
        plan = kernel_partition_plan(cfg, serve)
        assert set(plan) == set(dims)
        assert all(s == m for s in plan.values())
    else:
        with pytest.raises(ValueError, match="divide"):
            kernel_partition_plan(cfg, serve)
