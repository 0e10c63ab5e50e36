"""Logit-Aware Activation Budgeting + Offline Memory Profiler (paper §4.2-4.3).

The profiler maps the memory envelope under worst-case serving pressure and
returns a :class:`MemoryPlan`: how much HBM is reserved for transient
activations (dominated by the logit stage) and how many KV slots fit in the
remainder. Because the logit reservation depends on ``logit_mode``, the plan
mechanically reproduces the paper's capacity coupling: decomposing the logit
tensor shrinks the activation reservation and converts the reclaimed bytes
into additional concurrent requests ("KV Cache Maximization").

Two profiling modes:
  * analytic  — closed-form worst-case byte accounting (used for capacity
    planning of the big dry-run configs; §3.2 arithmetic).
  * measured  — lower + compile the actual step functions and read
    ``memory_analysis().temp_size_in_bytes`` (exact under XLA's static
    planner; used by the logit-budget benchmark).

Mesh serving (``ServeConfig.mesh_shape``): every term is billed **per
device**. ``hbm_bytes`` is per-device HBM; weights follow the exact
``launch.sharding.Rules.params`` placement (evaluated shape-only over a
:class:`~repro.launch.mesh.SimMesh`, so a 2-GPU plan computes inside a 1-CPU
test process), KV-slot bytes follow the ``Rules.cache`` within-slot sharding
(KV heads over ``model`` when divisible, retained-length fallback otherwise
— a slot's *count* stays global: each device holds 1/TP of every slot), and
activation/logit reservations shard over heads/FFN/vocab when divisible.
That keeps the paper's §4.2-4.3 coupling live on an N-GPU mesh: per-device
bytes reclaimed from weights + activations convert into MORE slots, never
fewer. The data axis is billed conservatively (slots replicated over it).
"""
from __future__ import annotations

import functools

from dataclasses import dataclass

from repro.configs.base import ModelConfig, ServeConfig


def dtype_bytes(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}[dtype]


def _tp_div(n: int, m: int) -> int:
    """Shard count the model axis contributes to a dim of size ``n`` —
    ``m`` on exact division (the Rules.div law), else 1 (replicated)."""
    return m if m > 1 and n and n % m == 0 and n >= m else 1


def _sharded_tree_bytes(mesh, shapes, specs, kv_quant: str = "none") -> int:
    """Per-device bytes of a (shape-tree, spec-tree) pair: each leaf's dims
    divide by the combined size of the mesh axes its spec names (ceil — the
    rules only shard on exact division anyway).

    ``kv_quant="int8"`` bills the leaves :func:`kernels.kv_quant.quant_mask`
    selects (PackedKV k/v) at 1 byte/element plus their per-(layer, slot)
    float32 scale — the same predicate the pool's runtime jits quantize
    with, so analytic capacity and allocated bytes cannot drift."""
    import jax

    def leaf_bytes(leaf, spec, quant):
        total = 1 if quant else leaf.dtype.itemsize
        for dim, entry in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            axes = (entry,) if isinstance(entry, str) else (entry or ())
            shards = 1
            for a in axes:
                shards *= mesh.shape[a]
            total *= -(-dim // shards)
        if quant:
            # [L, B] f32 scale; the layer/slot axes are never sharded by
            # the cache rules, so the scale is billed whole per device
            total += leaf.shape[0] * leaf.shape[1] * 4
        return total

    # a PartitionSpec is itself a tuple pytree — flatten the spec tree up to
    # the shape treedef so each P stays atomic alongside its shape leaf
    s_leaves, treedef = jax.tree.flatten(shapes)
    p_leaves = treedef.flatten_up_to(specs)
    if kv_quant == "none":
        flags = [False] * len(s_leaves)
    else:
        from repro.kernels.kv_quant import quant_mask
        flags = jax.tree.leaves(quant_mask(shapes))  # plain-bool leaves
    return int(sum(leaf_bytes(s, p, q)
                   for s, p, q in zip(s_leaves, p_leaves, flags)))


@functools.lru_cache(maxsize=None)
def weight_bytes_per_device(cfg: ModelConfig, mesh_shape) -> int:
    """Per-device parameter bytes under the ACTUAL serving placement.

    Shape-only: ``jax.eval_shape`` over ``init_params`` + the same
    ``Rules.params`` specs the engine places with, summed per shard (a
    :class:`SimMesh` stands in for the devices, so any mesh size can be
    planned from any host). ``mesh_shape=None`` bills one device."""
    import jax

    from repro.launch.mesh import SimMesh
    from repro.launch.sharding import Rules
    from repro.models import backbone as BB

    mesh = SimMesh(mesh_shape or (1, 1))
    shapes = jax.eval_shape(functools.partial(BB.init_params, cfg),
                            jax.random.PRNGKey(0))
    specs = Rules(cfg, mesh, train=False).params(shapes)
    return _sharded_tree_bytes(mesh, shapes, specs)


# ---------------------------------------------------------------------------
# analytic accounting
# ---------------------------------------------------------------------------

def logit_exec_tokens(serve: ServeConfig, n_logit_tokens: int) -> int:
    """Rows the engine's decode dispatch actually materializes for ``n``
    real hidden rows: token-bucket rounding under the packed engine (exact
    below one bucket — rows arrive in whole blocks), pow2 rounding on the
    padded oracle. The logit stage packs for *every* family under
    ``varlen_pack`` (the output head is family-agnostic)."""
    n = max(1, n_logit_tokens)
    if serve.varlen_pack:
        return token_bucket_round(n, serve.token_bucket)
    return pow2_bucket(n, lo=serve.block_size)


def logit_activation_bytes(cfg: ModelConfig, serve: ServeConfig,
                           n_logit_tokens: int) -> int:
    """Peak bytes of the output-projection stage under each C1 mode, billed
    by *executed* rows (the engine's bucketing policy, not the real count).
    Vocab-parallel under a mesh: each device materializes its [n, V/TP]
    shard (the argmax reduces across shards, never gathering [n, V])."""
    n_exec = logit_exec_tokens(serve, n_logit_tokens)
    v_pd = cfg.vocab_size // _tp_div(cfg.vocab_size, serve.mesh_model)
    if serve.logit_mode == "monolithic":
        # the paper's §3.2 boom: the full [N, V] tensor (f32 after softcap)
        return n_exec * v_pd * 4
    if serve.logit_mode == "chunked":
        return min(n_exec, serve.max_num_logits) * v_pd * 4
    # fused: the Pallas online kernel holds one [T_tile, V_tile] f32 block
    # per shard (vocab-sharded under a model axis > 1 — each shard scans its
    # V/TP slice and a cheap (max, index, logsumexp) reduce merges them)
    return 256 * serve.vocab_tile * 4


def _slot_cache_shapes(cfg: ModelConfig, serve: ServeConfig, retain: int,
                       batch: int = 1):
    """Shape-only cache pytree of ``batch`` slots — the engine pool's real
    per-slot geometry (family-specific leading layer axis included). The
    single shape model for the per-device billing here AND the Rules.cache
    property tests (``tests/test_sharding.py``)."""
    import jax
    import jax.numpy as jnp
    from repro.models.sparse_select import PackedKV
    sds = jax.ShapeDtypeStruct
    dt = jnp.dtype(cfg.dtype)

    def kv_tree(nl):
        kshape = (nl, batch, cfg.n_kv_heads, retain, cfg.resolved_head_dim)
        return PackedKV(k=sds(kshape, dt), v=sds(kshape, dt),
                        pos=sds(kshape[:-1], jnp.int32),
                        valid=sds(kshape[:-1], jnp.bool_))

    def ssm_shapes():
        from repro.models.ssm import conv_channels
        st = sds((cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                  cfg.ssm_state), jnp.float32)
        cv = sds((cfg.n_layers, batch, cfg.ssm_conv_kernel - 1,
                  conv_channels(cfg)), dt)
        return st, cv

    if cfg.family == "ssm":
        from repro.models.ssm import SSMCache
        st, cv = ssm_shapes()
        return SSMCache(state=st, conv=cv)
    if cfg.family == "hybrid":
        from repro.models.hybrid import HybridCache, group_shape
        n_groups, _, _ = group_shape(cfg)
        st, cv = ssm_shapes()
        return HybridCache(ssm_state=st, conv=cv, kv=kv_tree(n_groups))
    return kv_tree(cfg.n_layers)


@functools.lru_cache(maxsize=None)
def kv_slot_bytes(cfg: ModelConfig, serve: ServeConfig) -> int:
    """Static per-request KV region (§4.5): r·L tokens, head-major dense.

    Per DEVICE under a mesh, evaluated from the ACTUAL ``Rules.cache``
    specs over the engine's real pool geometry — the same single source of
    truth the engine shards its slot pool with (one law, no analytic copy
    to drift): KV heads over ``model`` when divisible, else the retained
    length when divisible (the idle-TP fallback), else replicated; SSM
    states shard over heads, conv tails replicate; nothing shards over
    data (``data_parallel=False``, matching the pool). The retained length
    is the engine's ``min(retained_len, max_seq_len - block_size)``, so
    the divisibility decision is billed on the dimension the pool actually
    allocates. The slot *count* is global — ``plan_memory`` divides
    per-device pool bytes by this."""
    from repro.launch.mesh import SimMesh
    from repro.launch.sharding import Rules

    retain = min(serve.retained_len,
                 max(1, serve.max_seq_len - serve.block_size))
    mesh = SimMesh(serve.mesh_shape or (1, 1))
    specs = Rules(cfg, mesh, train=False).cache(1, retain,
                                                data_parallel=False)
    shapes = _slot_cache_shapes(cfg, serve, retain)
    return _sharded_tree_bytes(mesh, shapes, specs, kv_quant=serve.kv_quant)


def can_pack_tokens(cfg: ModelConfig) -> bool:
    """True when the engine's token-packed Refresh/Reuse paths apply to
    ``cfg`` — which is now EVERY family: attention archs run the
    segment-masked varlen attention stream, SSM/hybrid archs run the
    segment-reset varlen SSD scan (``models/ssm.varlen_ssd_scan`` / the
    Pallas ``kernels/ssm_scan`` kernel), and modality-frontend archs
    (vlm/audio) pack their ``frontend_len`` projected rows as a
    fixed-length prefix of each request's segment in the same flat stream.
    No family falls back to the padded oracle on the hot path, so every
    family is provisioned (and billed) by packed tokens under
    ``varlen_pack=True``. Kept as a function (single source of truth for
    the engine gate and the profiler's activation accounting) so a future
    family with a genuinely unpackable geometry has one place to opt out.
    """
    del cfg  # every family packs
    return True


def admission_block_reason(serve: ServeConfig, req) -> "str | None":
    """Why ``req`` can NEVER be admitted under ``serve`` (None = admittable).

    The single source of truth for structured rejection — checked by
    ``Engine.submit`` (fail fast, before the queue) and by both schedulers'
    ``plan()`` sweeps (so a never-admittable request cannot head-of-line
    block the FCFS queue). Geometry only: transient conditions (no free
    slot, budget consumed this iteration) are deferrals, not rejections."""
    if req.total_len > serve.max_seq_len:
        return (f"total_len {req.total_len} (prompt {req.prompt_len} + gen "
                f"{req.gen_len}) exceeds max_seq_len {serve.max_seq_len}")
    if req.refresh_len > serve.max_num_batched_tokens:
        return (f"Refresh cost {req.refresh_len} (frontend {req.frontend_len}"
                f" + total {req.total_len}) exceeds the token budget "
                f"max_num_batched_tokens={serve.max_num_batched_tokens}; "
                f"the request can never be scheduled")
    return None


def pow2_bucket(n: int, lo: int = 1) -> int:
    """Smallest power-of-two multiple of ``lo`` that is ≥ n (the static-shape
    bucketing policy shared by the engine's jit caches and this profiler)."""
    b = lo
    while b < n:
        b *= 2
    return b


def token_bucket_round(n: int, bucket: int) -> int:
    """Packed-stream rounding, the single source of truth for the engine's
    Reuse/logit buckets and this profiler's exec-token accounting: exact
    below one bucket, ceil to bucket multiples above, and never beyond the
    pow2 oracle bucket — the invariant the CI waste gate asserts (the cap
    only binds for non-pow2 ``bucket`` values)."""
    n = max(1, n)
    b = max(1, bucket)
    r = n if n <= b else -(-n // b) * b
    return min(r, pow2_bucket(n))


def max_exec_tokens(serve: ServeConfig, cfg: ModelConfig) -> int:
    """Worst-case tokens one Refresh dispatch materializes activations for.

    Token-packed engines run the iteration's Refresh set as ONE fused
    stream and round its real token sum up to ``token_bucket`` (bounded by
    the scheduler budget — which counts modality-frontend prefix rows as
    query tokens, so the stream bound covers vlm/audio too). Padded
    engines pay the full ``batch_bucket × (frontend_len + max_seq_len)``
    rectangle regardless of true lengths (``refresh_slots`` normalizes the
    0-means-unlimited cap).
    """
    if serve.varlen_pack and can_pack_tokens(cfg):
        tb = max(1, serve.token_bucket)
        return -(-serve.max_num_batched_tokens // tb) * tb
    fe = cfg.frontend_len if cfg.frontend_dim else 0
    return max(serve.max_num_batched_tokens,
               pow2_bucket(serve.refresh_slots) * (serve.max_seq_len + fe))


def reuse_exec_tokens(serve: ServeConfig, cfg: ModelConfig) -> int:
    """Worst-case tokens one Reuse dispatch materializes activations for.

    The reuse set is bounded by both ``max_slots`` and the scheduler budget
    (block tokens are scheduling currency; the Reuse stream is text-only —
    frontend prefixes never enter it). Packed engines — every family,
    vlm/audio included — round the request count to whole token buckets
    (exact below one bucket); padded engines pay the pow2 batch bucket."""
    Sb = max(1, serve.block_size)
    r_max = max(1, min(serve.max_slots, serve.max_num_batched_tokens // Sb))
    if serve.varlen_pack and can_pack_tokens(cfg):
        rb = max(1, serve.token_bucket // Sb)
        return token_bucket_round(r_max, rb) * Sb
    return pow2_bucket(r_max) * Sb


def backbone_activation_bytes(cfg: ModelConfig, serve: ServeConfig) -> int:
    """Workspace for attention/MLP over one packed batch. Scaled by the
    *executed* tokens of the widest stage — Refresh (query-token budget
    under varlen packing, the padded rectangle otherwise) or Reuse (packed
    block stream vs pow2 batch). Under a mesh the wide intermediates shard
    over the model axis (FFN hidden / attention heads; the [T, 3D] stream
    stays replicated), so the reservation is per device. The packed (and
    sharded) engine's smaller reservation is converted into KV slots by
    :func:`plan_memory`."""
    b = dtype_bytes(cfg.dtype)
    m = serve.mesh_model
    T = max(max_exec_tokens(serve, cfg), reuse_exec_tokens(serve, cfg))
    width = max(cfg.d_ff // _tp_div(cfg.d_ff, m),
                cfg.n_heads * cfg.resolved_head_dim
                // _tp_div(cfg.n_heads, m),
                3 * cfg.d_model)
    return T * width * b * 2  # double-buffered


@dataclass(frozen=True)
class MemoryPlan:
    weights_bytes: int          # PER DEVICE (== global on 1 device/no mesh)
    activation_bytes: int       # reserved (incl. logit stage under the mode)
    logit_bytes: int
    slot_bytes: int             # per-device bytes of one (global) slot
    kv_pool_bytes: int
    max_slots: int              # global LOGICAL concurrent-request capacity
    mesh_devices: int = 1
    # memory-footprint multipliers (docs/memory.md): the physical slot count
    # the pool bytes actually fit, and the sharing/quantization knobs that
    # turned them into the logical ``max_slots`` above
    phys_slots: int = 0
    share_factor: float = 1.0
    kv_quant: str = "none"

    def summary(self) -> str:
        gb = 1 << 30
        mesh = f" mesh={self.mesh_devices}dev" if self.mesh_devices > 1 else ""
        share = (f" share={self.share_factor:.2f}x"
                 if self.share_factor != 1.0 else "")
        quant = f" kv={self.kv_quant}" if self.kv_quant != "none" else ""
        return (f"weights={self.weights_bytes/gb:.2f}GiB/dev "
                f"act={self.activation_bytes/gb:.3f}GiB "
                f"(logit={self.logit_bytes/gb:.3f}GiB) "
                f"kv_pool={self.kv_pool_bytes/gb:.2f}GiB "
                f"slots={self.max_slots}{mesh}{share}{quant}")


def plan_memory(cfg: ModelConfig, serve: ServeConfig, hbm_bytes: int,
                guard_band: float = 0.03,
                share_factor: float = 1.0) -> MemoryPlan:
    """The offline profiler's output: activation reservation + KV pool size.

    Worst-case N_logit = one active block per resident request is bounded by
    slots·block; we budget for the scheduler-level cap instead:
    ``max_num_batched_tokens`` query tokens all needing logits.

    Every term is per device (``hbm_bytes`` = one device's HBM). Under
    ``serve.mesh_shape`` the weight/KV-slot/activation bytes shrink by the
    sharded fractions, and the freed per-device headroom converts into MORE
    global slots — the §4.2-4.3 capacity coupling extended across a mesh.
    The slot pool shards its slot axis over the ``data`` axis (independent
    replica streams), so global capacity is per-replica slots × mesh_data.

    ``share_factor`` is the workload's measured logical/physical occupancy
    ratio (``data.workloads.prefix_share_factor``): with
    ``serve.prefix_sharing`` on, every physical slot the pool bytes fit
    backs that many logical residents on average, so the plan multiplies
    capacity before the ``serve.max_slots`` cap. int8 ``serve.kv_quant``
    instead shrinks ``slot_bytes`` (via ``kv_slot_bytes``) so more physical
    slots fit outright. Both multipliers are reported on the plan.
    """
    weights = weight_bytes_per_device(cfg, serve.mesh_shape)
    n_logit_worst = serve.max_num_batched_tokens
    logit = logit_activation_bytes(cfg, serve, n_logit_worst)
    act = backbone_activation_bytes(cfg, serve) + logit
    guard = int(hbm_bytes * guard_band)
    slot = kv_slot_bytes(cfg, serve)
    pool = max(0, hbm_bytes - weights - act - guard)
    replicas = max(1, serve.mesh_data)
    phys = replicas * (pool // slot) if slot else serve.max_slots
    share = share_factor if serve.prefix_sharing else 1.0
    slots = min(serve.max_slots, int(phys * share))
    return MemoryPlan(weights, act, logit, slot, pool, int(slots),
                      mesh_devices=serve.mesh_devices,
                      phys_slots=int(min(serve.max_slots, phys)),
                      share_factor=share, kv_quant=serve.kv_quant)


# ---------------------------------------------------------------------------
# measured profiling (exact, via XLA compile)
# ---------------------------------------------------------------------------

def measure_logit_peak(cfg: ModelConfig, serve: ServeConfig,
                       n_tokens: int) -> dict:
    """Compile the decode stage in every C1 mode and read XLA's exact
    temp-buffer peak. Runs on any backend (no allocation: AOT only)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm_head as LM

    dtype = jnp.dtype(cfg.dtype)
    h = jax.ShapeDtypeStruct((n_tokens, cfg.d_model), dtype)
    params = {"table": jax.ShapeDtypeStruct((cfg.vocab_size, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.ShapeDtypeStruct(
            (cfg.d_model, cfg.vocab_size), dtype)
    out = {}
    for mode in ("monolithic", "chunked", "fused"):
        def fn(params, h, mode=mode):
            return LM.decode_tokens(params, cfg, h,
                                    max_num_logits=serve.max_num_logits,
                                    mode=mode, vocab_tile=serve.vocab_tile)
        from repro import jax_compat as JC
        compiled = JC.jit(fn).lower(params, h).compile()
        ma = compiled.memory_analysis()
        out[mode] = int(ma.temp_size_in_bytes)
    return out
