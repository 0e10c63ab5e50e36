"""dLLM-Serve execution engine: continuous batching over Refresh/Reuse phases.

One engine iteration (§4.1 workflow):
  1. the scheduler builds an :class:`IterationPlan` under the query-token
     budget (C2),
  2. Refresh sub-batches run the full-seq forward + head-centric select/pack
     and write packed caches into the slot pool (C3),
  3. the Reuse set runs active-block attention over its slot caches (read
     in place from the pool on the attention families' kernel path),
  4. all block hidden states are decoded through the *budgeted* logit stage
     (C1: serial ``max_num_logits`` sub-batches / fused Pallas kernel),
  5. commits are applied host-side and request state machines advance.

Static-shape policy: two execution paths for the WHOLE iteration.

* padded (oracle): every stage is bucketed to powers of two — Refresh pads
  sequences to ``max_seq_len`` (plus the ``frontend_len`` prefix for
  vlm/audio), Reuse pads the request batch, and the logit stage pads the
  concatenated hidden rows — up to ~2× wasted FLOPs/HBM per stage. Kept
  purely as the correctness oracle: no family falls back to it on the hot
  path anymore.
* token-packed (``varlen_pack=True``, the paper's §4.1 flattened engine): no
  stage launches a pow2-padded rectangle for ANY family — attention archs
  run the segment-masked varlen attention stream, SSM/hybrid archs run the
  segment-reset varlen SSD scan (``kernels/ssm_scan``), and the
  modality-frontend archs (vlm/audio) pack their projected frontend rows as
  a fixed-length prefix of each request's segment. The iteration executes
  as a single packed pipeline driven by the scheduler's
  :class:`~repro.core.scheduler.PackedIterationLayout` (per-stage cu_seqlens):

    - Refresh: ONE ragged ``[T_total, ...]`` stream for the WHOLE iteration
      (``PackedIterationLayout.refresh_fused`` — a single fused dispatch
      across the refresh chunks), bucketed on *total tokens*
      (``token_bucket`` granularity; frontend prefix rows count), in-kernel
      segment masking + tile-skip (``kernels/flash_varlen``) or
      segment-reset state scan (``kernels/ssm_scan``), and select/pack that
      reads the stream in place (no padded K/V gather). vlm/audio segments
      are ``[frontend prefix ; text]``; Reuse and the logit stage address
      only the text region (block rows), so prefixes never enter them.
    - Reuse: the iteration's R active blocks form one ragged ``[R·Sb]``
      query stream (R rounded only to the token-bucket granularity). The
      attention families' kernel reads each request's retained K/V in place
      from the slot pool through a slot table (:meth:`_reuse_reads_pool`);
      the other caches are gathered first (docs/engine.md).
    - Logit stage: the real ``N`` hidden rows are decoded at token-bucket
      granularity with a validity mask threaded into the fused Pallas argmax
      kernel; all-padding chunks are never paid for.

  Per-stage ``*_tokens_real`` / ``*_tokens_exec`` counters expose the
  padding waste of each path (``refresh_waste`` / ``reuse_waste`` /
  ``logit_waste``).

Every jitted entry point is cached per bucket (padded: batch bucket; packed:
token/request-granularity bucket).

Mesh serving (``ServeConfig.mesh_shape``): the same pipeline executes
tensor-parallel under a (data, model) device mesh — params placed by
``launch.sharding.Rules.params``, the slot pool sharded by ``Rules.cache``,
per-stage PartitionSpecs threaded through the jitted entry points via
``repro.jax_compat.jit_sharded``, and the logit stage running vocab-parallel
(argmax/logsumexp reduce across vocab shards). The Pallas hot paths run
per-shard too: every stage dispatch happens inside the mesh context
(:meth:`Engine._mesh_ctx`) so the ``kernels.ops`` wrappers shard_map the
varlen attention / SSD scan over their local heads and the fused argmax over
the local vocab shard — kernels and tensor-parallelism compose. On a data
axis > 1 the slot pool shards its slot axis over ``data`` (independent
replica streams; the modeled clock credits the split). No mesh and a 1×1
mesh are
bit-identical to each other, so all padded-vs-packed oracles keep anchoring
correctness; the 1-vs-2-device agreement suite (``launch/shard_check.py``)
anchors the sharded path. See ``docs/sharding.md``.
"""
from __future__ import annotations

import contextlib
import itertools
import math
import time
from dataclasses import dataclass, field
from functools import partial, wraps
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import jax_compat as JC
from repro.jax_compat import P
from repro.configs.base import ModelConfig, ServeConfig
from repro.core import diffusion
from repro.core.budgeting import (admission_block_reason, can_pack_tokens,
                                  pow2_bucket as _bucket, token_bucket_round)
from repro.core.faults import FaultError, FaultPlan
from repro.kernels import flash_varlen as FV
from repro.kernels import ops as OPS
from repro.core.kv_pool import SPAN_GATHER, SPAN_WRITE, KVPool
from repro.core.request import Outcome, Phase, Request, State
from repro.core.scheduler import make_scheduler
from repro.launch.mesh import make_serving_mesh
from repro.models import backbone as BB
from repro.models import lm_head as LM
from repro.models import transformer as T
from repro.models.sparse_select import PackedKV

# Host spans on the profiler's clock (``jax.profiler.TraceAnnotation``), one
# per stretch of engine work; docs/engine.md "Spans" says what each covers.
# With no profiler active a span costs about a microsecond.
SPAN_PLAN = "dllm.plan"
SPAN_DISPATCH = "dllm.dispatch"
SPAN_REFRESH = "dllm.dispatch.refresh"
SPAN_REUSE = "dllm.dispatch.reuse"
SPAN_LOGITS = "dllm.dispatch.logits"
SPAN_SYNC = "dllm.sync"
SPAN_WAIT = "dllm.sync.wait"
SPAN_LAND = "dllm.sync.land"
SPAN_ARRIVAL = "dllm.arrival_wait"
SPANS = (SPAN_PLAN, SPAN_DISPATCH, SPAN_REFRESH, SPAN_REUSE, SPAN_LOGITS,
         SPAN_WRITE, SPAN_GATHER, SPAN_SYNC, SPAN_WAIT, SPAN_LAND,
         SPAN_ARRIVAL)
_span = jax.profiler.TraceAnnotation


def _spanned(name: str):
    """Run the decorated method inside the host span ``name``."""
    def deco(f):
        @wraps(f)
        def inner(*args, **kwargs):
            with _span(name):
                return f(*args, **kwargs)
        return inner
    return deco


@dataclass(frozen=True)
class DeviceModel:
    """Virtual accelerator cost model for the modeled clock.

    A serial CPU cannot reward batching (compute scales with tokens), so
    wall-clock serving runs on this host cannot exhibit the paper's
    concurrency gains. In modeled-clock mode the engine still executes every
    step for real (functional fidelity) but advances a virtual clock by
    ``launch + padded_flops/peak`` per device call — the standard
    discrete-event methodology for serving-system studies. ``launch``
    captures per-step dispatch/sync overhead (dLLM denoising is a long
    sequential chain of small steps — exactly the regime where packing more
    work per step wins); ``peak`` is effective device throughput.

    Defaults are scaled to the reduced CPU models: the toy is ~4000× smaller
    than LLaDA-8B, so peak is scaled by the same factor (82 TF/4000 ≈ 20 GF/s)
    to preserve the real system's compute:launch ratio (Refresh steps
    compute-bound at ~100 ms, Reuse steps ~10 ms, launches ~1 ms).
    """
    launch_s: float = 1e-3
    peak_flops: float = 20e9

    def call_cost(self, flops: float, work_split: float = 1.0) -> float:
        """Virtual seconds for one device call. ``work_split`` is the factor
        by which the per-call FLOPs genuinely divide across devices — the
        engine passes its tensor-parallel work split (1.0 when nothing
        shards, up to the model-axis size when the matmul weights fully
        divide; launch overhead is paid once regardless, and serving
        implements no data parallelism so a data axis never contributes)."""
        return self.launch_s + flops / (self.peak_flops
                                        * max(1.0, work_split))


@dataclass
class EngineStats:
    iterations: int = 0
    refresh_steps: int = 0
    reuse_steps: int = 0
    committed_tokens: int = 0
    deferred_steps: int = 0
    peak_query_tokens: int = 0
    wall_time: float = 0.0
    # padded-vs-packed accounting, one pair per stage: `real` is the stage's
    # true token count (Σ refresh_len — frontend prefix + text — for
    # Refresh, R·Sb for Reuse, N hidden rows for the logit stage); `exec` is
    # what the device actually consumed (pow2 rectangles on the oracle path,
    # token-bucket rounding packed).
    refresh_tokens_real: int = 0
    refresh_tokens_exec: int = 0
    reuse_tokens_real: int = 0
    reuse_tokens_exec: int = 0
    logit_tokens_real: int = 0
    logit_tokens_exec: int = 0
    packed_refresh_calls: int = 0
    padded_refresh_calls: int = 0
    packed_reuse_calls: int = 0
    padded_reuse_calls: int = 0
    reuse_inplace_calls: int = 0  # packed Reuse calls that read the slot
    #                               pool in place (no gather)
    # -- request lifecycle / robustness accounting (docs/robustness.md) ----
    # Conservation law (asserted by the chaos suite): every submitted
    # request reaches exactly one terminal outcome —
    # ``submitted == finished + shed + rejected``.
    submitted: int = 0
    finished: int = 0
    rejected_oversized: int = 0
    rejected_queue_full: int = 0
    shed_deadline: int = 0
    shed_queue: int = 0
    preemptions: int = 0          # preempt-and-requeue events (not terminal)
    recomputed_tokens: int = 0    # commits discarded by preemption rollbacks
    dispatch_retries: int = 0     # transient dispatch faults absorbed
    # -- content-addressed slot sharing (docs/memory.md) -------------------
    shared_hits: int = 0          # Refresh writes deduplicated against a
    #                               resident owner slot (device write skipped)
    shared_cow_promotes: int = 0  # copy-on-write row promotes (divergent
    #                               Refresh or free of a still-referenced
    #                               owner)
    phys_slots_peak: int = 0      # high-water distinct-owner slot occupancy
    #                               (== peak residency when sharing is off)
    alloc_fault_iters: int = 0    # iterations whose admission hit an
    #                               injected slot-allocation failure
    slow_fault_s: float = 0.0     # injected slow-iteration delay absorbed
    # -- retrace sentinel (docs/analysis.md) -------------------------------
    # Per-entry-point XLA compilation counters (refresh/reuse/decode stage
    # jits + the pool scatter/gather), counted at trace time by the
    # ``jax_compat`` jit shims. ``compiles_warmup`` snapshots the total the
    # moment ``Engine.warmup`` returns; anything above it afterwards is a
    # steady-state recompilation — the static budget the retrace sentinel
    # (``repro.analysis.retrace``) holds at ZERO for a warmed engine.
    compile_counts: Dict[str, int] = field(default_factory=dict)
    compiles_warmup: int = 0
    # -- pipelined-loop host/device accounting (docs/engine.md) ------------
    # Wall-clock time, regardless of clock mode: the modeled clock prices
    # DEVICE work, while these measure the HOST side of the serving loop —
    # the gap the dispatch-ahead pipeline hides.
    host_plan_s: float = 0.0      # building IterationPlans + packed layouts
    host_fill_s: float = 0.0      # stage buffer fills + dispatch enqueue
    sync_wait_s: float = 0.0      # blocked in the deferred device_get
    overlapped_host_s: float = 0.0  # plan time spent while a previous
    #                                 iteration's dispatch was still in flight
    dispatched_ahead: int = 0     # iterations planned with a sync pending
    streamed_events: int = 0      # per-iteration commit events emitted to
    #                               the streaming callback
    # one row per dispatched iteration. ``t`` is the iteration's ``now``,
    # taken before it is planned: vtime on the modeled clock, seconds since
    # run() started on the wall clock. ``plan_s`` / ``fill_s`` are host wall
    # seconds of planning and of the fills and dispatches; ``sync_s`` is the
    # wall seconds of its device_get, written at the sync (0.0 until then).
    # The iteration's commits are stamped on Request (t_first_commit,
    # t_finished): at dispatch on the modeled clock, at the sync on the wall
    # clock. ``reuse_gathered_slots`` counts the slots the iteration's
    # Reuse copied out of the pool (0 when it read the pool in place, or
    # ran no Reuse). A list when unlimited; the engine swaps in a maxlen
    # deque under ServeConfig.iter_log_cap (O(1) eviction of the oldest rows)
    iter_log: List[dict] = field(default_factory=list)

    @property
    def compiles_total(self) -> int:
        return sum(self.compile_counts.values())

    @property
    def compiles_post_warmup(self) -> int:
        """Compilations after the warmup snapshot (0 on a healthy warmed
        engine; equals ``compiles_total`` when warmup was never run)."""
        return self.compiles_total - self.compiles_warmup

    @property
    def overlap_frac(self) -> float:
        """Fraction of per-iteration host work (plan + fill) that ran while
        device work was in flight. Structural, not a wall-clock estimate:
        plan time counts as overlapped exactly when a dispatched iteration
        had not yet been synced — so the synchronous loop is identically 0
        and any dispatch-ahead shows up deterministically, even on hosts
        where timers are noisy (the CI gate relies on this)."""
        return self.overlapped_host_s / max(
            self.host_plan_s + self.host_fill_s, 1e-12)

    @property
    def rejected(self) -> int:
        return self.rejected_oversized + self.rejected_queue_full

    @property
    def shed(self) -> int:
        return self.shed_deadline + self.shed_queue

    def conserved(self) -> bool:
        """The lifecycle conservation law; True once the engine drains."""
        return self.submitted == self.finished + self.shed + self.rejected

    @property
    def refresh_waste(self) -> float:
        """exec/real token ratio (1.0 = zero padding waste)."""
        return self.refresh_tokens_exec / max(self.refresh_tokens_real, 1)

    @property
    def reuse_waste(self) -> float:
        return self.reuse_tokens_exec / max(self.reuse_tokens_real, 1)

    @property
    def logit_waste(self) -> float:
        return self.logit_tokens_exec / max(self.logit_tokens_real, 1)

    @property
    def throughput(self) -> float:
        return self.committed_tokens / max(self.wall_time, 1e-9)


@dataclass
class _CommitEntry:
    """One request's dispatched-but-unsynced commit (docs/engine.md).

    Recorded when the control plane advances at dispatch time; holds
    everything the deferred sync needs to land the token VALUES later: the
    hidden-row index, the block coordinates as of dispatch (the state
    machine has already moved on), the commit width, and the request's
    ``commit_epoch`` — a preemption rollback bumps the epoch, so a stale
    entry's values are dropped at sync (the rollback already booked those
    commits as recompute debt)."""
    req: Request
    row: int                  # request index in the decoded hidden stream
    block_start: int          # absolute offset of the committed block
    block_idx: int            # block index at dispatch (stream events)
    n_commit: int             # commit width passed to commit_tokens
    n_act: int                # positions actually unmasked (stats delta)
    epoch: int                # req.commit_epoch at dispatch
    finished: bool            # this commit completed the request
    # commit stamp, on the engine's run clock: modeled vtime at dispatch
    # (the synchronous loop's stamp); on the wall clock None until the sync
    # sets it to when the values reached the host
    t: Optional[float]


@dataclass
class _Prepared:
    """Host-side output of :meth:`Engine._begin_iteration`: one iteration's
    scheduler plan + packed layout, built as pure host work — the part the
    pipelined loop overlaps with in-flight device execution."""
    now: float
    plan: object              # IterationPlan
    layout: object            # PackedIterationLayout | None
    lifecycle: bool           # the plan shed/rejected/preempted something
    plan_s: float             # host seconds spent planning

    @property
    def has_exec(self) -> bool:
        return self.plan.has_exec


@dataclass
class _Pending:
    """One dispatched-but-unsynced iteration: the decode outputs still on
    device plus the commit entries to apply at the single deferred sync."""
    ids: jax.Array
    conf: jax.Array
    n_rows: int
    entries: List[_CommitEntry]
    log_row: dict


class Engine:
    def __init__(self, cfg: ModelConfig, serve: ServeConfig,
                 params: Optional[dict] = None, seed: int = 0,
                 clock: Optional[str] = None,
                 device_model: Optional[DeviceModel] = None,
                 faults: Optional[FaultPlan] = None,
                 stream_cb=None):
        self.cfg = cfg
        self.serve = serve
        # clock mode: the ctor arg (back-compat spelling every harness uses)
        # overrides ServeConfig.clock; None defers to the config
        self.clock = clock if clock is not None else serve.clock
        if self.clock not in ("wall", "modeled"):
            raise ValueError(f"Engine clock must be 'wall' or 'modeled', "
                             f"got {self.clock!r}")
        # streaming per-iteration token output (docs/engine.md): called once
        # per committed (request, iteration) at sync time — when the values
        # exist host-side — with a dict event; finished blocks surface
        # before the run completes instead of only via output_tokens()
        self._stream_cb = stream_cb
        self.faults = faults
        self.device = device_model or DeviceModel()
        self.vtime = 0.0
        # the wall clock's zero and scale (reset by each run())
        self._run_start = time.perf_counter()
        self._time_scale = 1.0
        self._n_params = cfg.n_active_params()
        self.mask_id = diffusion.mask_token_id(cfg.vocab_size)
        retain = min(serve.retained_len,
                     serve.max_seq_len - serve.block_size)
        self.ctx = T.ServeContext(
            block_size=serve.block_size, retain=retain,
            kernel_size=serve.kernel_size, selection=serve.selection,
            q_chunk=min(T.L.DEFAULT_Q_CHUNK, serve.max_seq_len),
            use_flash_kernel=serve.use_flash_kernel,
            max_seq_len=serve.max_seq_len)
        # ---- device mesh (tensor-parallel serving) -----------------------
        # mesh_shape=(data, model): params placed by Rules.params, the slot
        # pool sharded by Rules.cache, every stage jitted with per-stage
        # PartitionSpecs (repro.jax_compat.jit_sharded). No mesh / 1×1 mesh
        # executes the identical computation — the single-device path is the
        # bit-identical anchor for all padded-vs-packed oracles.
        #
        # The Pallas hot paths shard-map themselves per model shard (see
        # kernels.ops): validate the head/vocab divisibility law up front —
        # before the mesh is even built, so indivisible configs fail loudly
        # without needing the devices — instead of silently falling back.
        if serve.mesh_model > 1 and (serve.use_flash_kernel
                                     or serve.logit_mode == "fused"):
            from repro.launch.sharding import kernel_partition_plan
            kernel_partition_plan(cfg, serve)
        # memory-footprint multipliers (docs/memory.md): validate up front so
        # an unsupported combination fails at construction, never silently
        # serves a different storage mode than the config asked for
        if serve.kv_quant not in ("none", "int8"):
            raise ValueError(f"ServeConfig.kv_quant must be 'none' or "
                             f"'int8', got {serve.kv_quant!r}")
        if serve.kv_quant != "none" and serve.mesh_shape is not None:
            raise NotImplementedError(
                "kv_quant='int8' is not yet composed with mesh serving — "
                "the quantized pool's scale leaves need their own "
                "Rules.cache-derived placement (see docs/memory.md)")
        self.mesh = make_serving_mesh(serve.mesh_shape)
        self.mesh_devices = self.mesh.devices.size if self.mesh else 1
        pool_shardings = gather_shardings = None
        self._pool_pad = 0
        if self.mesh is not None:
            from functools import partial as _partial

            from repro.launch.sharding import Rules
            self.rules = Rules(cfg, self.mesh, train=False)
            pshapes = jax.eval_shape(_partial(BB.init_params, cfg),
                                     jax.random.PRNGKey(0))
            self._pspecs = self.rules.params(pshapes)
            param_shardings = self.rules.named(self._pspecs)
            # ONE cache layout for every *stream* — gathered sub-batches and
            # fresh Refresh caches (data_parallel=False: only the model axis
            # shards within a slot) — batch-size-dependent specs would
            # diverge across stages and break the in_shardings contract.
            # The slot POOL additionally shards its slot axis over the data
            # axis (slot_data_parallel): each of the mesh_data replica
            # streams stores its slots locally, so a (d, m) mesh holds d×
            # the slots of one device pair. Pad the pool's slot count up so
            # the axis always divides; writes scatter replicated caches into
            # the sharded pool and gathers land back in the stream layout.
            self._cache_spec = self.rules.cache(serve.max_slots + 1, retain,
                                                data_parallel=False)
            self._pool_pad = (-(serve.max_slots + 1)) % max(1, serve.mesh_data)
            self._pool_spec = self.rules.cache(
                serve.max_slots + 1 + self._pool_pad, retain,
                data_parallel=False, slot_data_parallel=True)
            pool_shardings = self.rules.named(self._pool_spec)
            gather_shardings = self.rules.named(self._cache_spec)
            # serving activation-sharding policy: replicate the token streams
            # at stage boundaries (weights/heads/vocab carry the TP sharding)
            # and pin the head weight vocab-parallel at its point of use so
            # the logit stage computes [N, V/TP] shards with the argmax
            # reducing across them. NamedSharding leaves (not bare specs):
            # the engine's jits don't run under a mesh context manager.
            from repro.models import layers as Lmod
            v_ax = self.rules.div(cfg.vocab_size)
            Lmod.set_sharding_policy(self.rules.named({
                "act3d": P(None, None, None),
                "packed_h": P(None, None),
                "logit_w": P(None, v_ax),
                "logit_w_tied": P(v_ax, None),
            }))
        else:
            self.rules = None
            self._pspecs = None
            param_shardings = None
            # the policy is process-global: a later single-device engine must
            # not trace against a previous mesh engine's stale NamedShardings
            # (the newest engine owns the policy — one serving mesh per
            # process; the dryrun/train launchers set their own in their
            # own processes and never construct an Engine)
            from repro.models import layers as Lmod
            Lmod.set_sharding_policy({})
        if params is None:
            # built on device, each leaf directly in the sharding it lives
            # in: no device (and not the host) ever holds the whole model
            params = JC.jit(partial(BB.init_params, cfg),
                            out_shardings=param_shardings)(
                jax.random.PRNGKey(seed))
        elif param_shardings is not None:
            params = jax.device_put(params, param_shardings)
        self.params = params
        self.scheduler = make_scheduler(serve)
        # retrace sentinel: every jit entry point of THIS engine (stage jits
        # + the pool scatter/gather) counts its compilations here, so the
        # post-warmup compile budget is per-engine, not process-global
        from collections import Counter
        self._compile_counter: Counter = Counter()
        self.pool = KVPool(serve.max_slots, shardings=pool_shardings,
                           gather_shardings=gather_shardings,
                           pad_slots=self._pool_pad,
                           compile_counter=self._compile_counter,
                           sharing=serve.prefix_sharing,
                           kv_quant=serve.kv_quant,
                           donate_cache=serve.donate_buffers)
        self._sharing = serve.prefix_sharing
        # robustness wiring: the scheduler drives the pool's take/free
        # generation ledger on admit/finish/preempt, and consumes the fault
        # plan's alloc-failure / mem-steal tokens at admission time
        self.scheduler.pool = self.pool
        self.scheduler.faults = faults
        self._iter = 0              # engine iteration counter (fault schedule)
        self._fault_blocked = False  # last plan suppressed by injected faults
        self.stats = EngineStats()
        if serve.iter_log_cap:
            from collections import deque
            self.stats.iter_log = deque(maxlen=serve.iter_log_cap)
        # modeled-clock TP work split: credit only the fraction of per-token
        # work that ACTUALLY shards (same exact-division law the memory
        # planner bills by) — total/per-device param bytes on a pure-TP
        # (1, model) mesh is 1.0 when nothing divides and approaches
        # mesh_model as the matmul weights shard, so an indivisible mesh
        # can never fake a modeled speedup.
        if serve.mesh_model > 1:
            from repro.core.budgeting import weight_bytes_per_device
            self._tp_work_split = (
                weight_bytes_per_device(cfg, None)
                / max(1, weight_bytes_per_device(cfg, (1, serve.mesh_model))))
        else:
            self._tp_work_split = 1.0
        # data-axis replica credit: the slot pool shards its slot axis over
        # ``data`` (above), so a (d, m) mesh carries d independent replica
        # streams of the serving state — the modeled clock credits the full
        # d× on top of the actually-sharded TP fraction.
        self._dp_work_split = (float(serve.mesh_data)
                               if self.mesh is not None
                               and serve.mesh_data > 1 else 1.0)
        # modality-frontend prefix rows per request (0 for text-only archs):
        # every Refresh geometry below spans frontend_len + text rows, and
        # block/reuse positions are offset by it (full-sequence coordinates).
        self._fe_len = cfg.frontend_len if cfg.frontend_dim else 0
        # token-packed execution covers every family (segment-masked
        # attention stream, segment-reset SSD scan, or frontend-prefix
        # segments); same predicate the offline profiler bills activations
        # by — can_pack_tokens is the single opt-out point.
        self._use_packed = serve.varlen_pack and can_pack_tokens(cfg)
        self._refresh_jit: Dict[int, callable] = {}
        self._refresh_packed_jit: Dict[tuple, callable] = {}
        self._reuse_jit: Dict[int, callable] = {}
        self._reuse_packed_jit: Dict[int, callable] = {}
        self._decode_jit: Dict[int, callable] = {}
        self._decode_packed_jit: Dict[int, callable] = {}
        # rng only feeds synthetic frontend payload stand-ins; request ids
        # come from a monotonic counter (rng-drawn rids could collide and
        # silently merge two requests' stats)
        self._rng = np.random.default_rng(seed)
        self._rid_counter = itertools.count()

    @property
    def tp_work_split(self) -> float:
        """Factor by which per-token work genuinely divides across the TP
        axis (1.0 ≤ split ≤ model-axis size; the modeled clock and the
        per-device token metrics both use it)."""
        return self._tp_work_split

    @property
    def work_split(self) -> float:
        """Total modeled work division: the TP fraction × the data-axis
        replica streams (slot pool sharded over ``data``)."""
        return self._tp_work_split * self._dp_work_split

    @property
    def kernels_active(self) -> bool:
        """True when the Pallas hot paths are live in this engine — under a
        model axis > 1 they dispatch per-shard (shard_map), validated at
        construction; there is no silent jnp fallback."""
        return bool(self.serve.use_flash_kernel
                    or self.serve.logit_mode == "fused")

    # ------------------------------------------------------------------
    # jitted step functions (cached per bucket size)
    # ------------------------------------------------------------------
    def _donate(self, *argnums: int) -> tuple:
        """Per-iteration stream buffers are single-use: every dispatch builds
        fresh device inputs (``jnp.asarray`` of numpy fills, a fresh pool
        gather) that are dead the moment the call returns, so under
        ``ServeConfig.donate_buffers`` they are donated and XLA reuses their
        storage for the outputs instead of double-buffering the packed
        streams. Params (argnum 0) are never donated. Donation is a
        lifetime hint only — numerics are bit-identical either way — so the
        oracle suites run unchanged with it on or off."""
        return tuple(argnums) if self.serve.donate_buffers else ()

    def _stage_specs(self, n_stream: int, with_cache: bool = False):
        """in_specs for one stage entry point: params carry their Rules
        placement, token/offset streams replicate (the serving mesh's model
        axis shards weights/heads/vocab, not tokens), and gathered caches
        carry the slot pool's one fixed layout. None when no mesh is
        configured (plain ``jax.jit``)."""
        if self.mesh is None:
            return None
        in_specs = (self._pspecs,) + (P(),) * n_stream
        if with_cache:
            in_specs += (self._cache_spec,)
        return in_specs

    def _refresh_out_specs(self):
        """Pin Refresh outputs: block hidden replicated, the captured cache
        already in the slot pool's ``Rules.cache`` layout (so the pool write
        is a sharded scatter, never a reshard)."""
        if self.mesh is None:
            return None
        return BB.RefreshOut(block_hidden=P(), cache=self._cache_spec)

    def _refresh_fn(self, n: int):
        if n not in self._refresh_jit:
            ctx = self.ctx

            def fn(params, tokens, token_valid, block_start, frontend):
                return BB.serve_refresh(params, self.cfg, tokens, block_start,
                                        ctx, frontend=frontend,
                                        token_valid=token_valid)

            in_specs = self._stage_specs(4)
            self._refresh_jit[n] = JC.jit_sharded(
                fn, mesh=self.mesh, in_specs=in_specs,
                out_specs=self._refresh_out_specs(),
                donate_argnums=self._donate(1, 2),
                entry="refresh", counter=self._compile_counter)
        return self._refresh_jit[n]

    def _token_bucket(self, n_tokens: int) -> int:
        """Round a real token count up to the packed-buffer granularity."""
        tb = max(1, self.serve.token_bucket)
        return max(tb, -(-n_tokens // tb) * tb)

    def _reuse_bucket(self, n_requests: int) -> int:
        """Packed-Reuse request-count granularity: R·block_size rounded to
        the token bucket (``rb = token_bucket // Sb`` whole blocks — never a
        pow2 batch bucket). Below one bucket the stream runs exactly-sized:
        R is already capped by ``max_slots``, so sub-bucket shapes add at
        most ``rb`` jit entries and the packed dispatch never pays more
        tokens than the pow2 oracle (see ``token_bucket_round``)."""
        rb = max(1, self.serve.token_bucket // self.serve.block_size)
        return token_bucket_round(n_requests, rb)

    def _logit_bucket(self, n_rows: int) -> int:
        """Packed logit-stage granularity: hidden rows arrive in whole
        blocks (N = n_decoded·Sb), so below one token bucket the stream runs
        exactly-sized (≤ token_bucket/Sb extra jit entries); above, it
        rounds to token-bucket multiples. Never a pow2 row bucket."""
        return token_bucket_round(n_rows, self.serve.token_bucket)

    def _refresh_packed_fn(self, tp: int, rp: int):
        if (tp, rp) not in self._refresh_packed_jit:
            ctx = self.ctx

            def fn(params, flat_tokens, positions, seg_ids, token_valid,
                   cu_seqlens, seq_lens, block_start, frontend):
                return BB.serve_refresh_packed(
                    params, self.cfg, flat_tokens, positions, seg_ids,
                    token_valid, cu_seqlens, seq_lens, block_start, ctx,
                    frontend=frontend)

            in_specs = self._stage_specs(8)
            self._refresh_packed_jit[(tp, rp)] = JC.jit_sharded(
                fn, mesh=self.mesh, in_specs=in_specs,
                out_specs=self._refresh_out_specs(),
                donate_argnums=self._donate(1, 2, 3, 4),
                entry="refresh_packed", counter=self._compile_counter)
        return self._refresh_packed_jit[(tp, rp)]

    def _reuse_fn(self, n: int):
        if n not in self._reuse_jit:
            ctx = self.ctx

            def fn(params, block_tokens, block_positions, cache):
                # KV-load dequant point: under kv_quant the gathered view is
                # still int8 + scales; scaling back happens inside THIS jit
                # (jnp on the padded oracle path), never as pool state
                cache = OPS.dequantize_gathered(cache, self.serve.kv_quant,
                                                self.pool.gathered_dtypes)
                return BB.serve_reuse(params, self.cfg, block_tokens,
                                      block_positions, cache, ctx)

            in_specs = self._stage_specs(2, with_cache=True)
            self._reuse_jit[n] = JC.jit_sharded(
                fn, mesh=self.mesh, in_specs=in_specs,
                donate_argnums=self._donate(1, 2, 3),
                entry="reuse", counter=self._compile_counter)
        return self._reuse_jit[n]

    def _reuse_reads_pool(self) -> bool:
        """Whether packed Reuse reads the retained K/V in place from the
        slot pool through a slot table, instead of gathering the slots
        first. Decided from what the pool holds: a plain ``PackedKV`` (the
        attention families; not the hybrid or SSM caches, nor the int8
        view) on the kernel path, with the slot axis on no more than one
        device (a slot axis split over ``data`` keeps each replica's slots
        on its own devices, and the gather is what brings them into the
        stream layout)."""
        pool = self.pool
        return (self.ctx.use_flash_kernel and pool.kv_quant == "none"
                and isinstance(pool.cache, PackedKV)
                and (pool.shardings is None
                     or pool.shardings.k.shard_shape(pool.cache.k.shape)[1]
                     == pool.cache.k.shape[1]))

    def _reuse_packed_fn(self, rp: int):
        if rp not in self._reuse_packed_jit:
            ctx = self.ctx
            if self._reuse_reads_pool():
                def fn(params, flat_tokens, flat_positions, pool, rows,
                       n_live):
                    return BB.serve_reuse_packed(
                        params, self.cfg, flat_tokens, flat_positions, pool,
                        ctx, rows=rows, n_live=n_live)

                # the pool is read in place and lives on: never donated
                in_specs = None if self.mesh is None else (
                    self._pspecs, P(), P(), self._pool_spec, P(), P())
                donate = self._donate(1, 2)
            else:
                def fn(params, flat_tokens, flat_positions, cache):
                    # same KV-load dequant as the padded oracle — here it
                    # fuses into the varlen cross-attention kernel's program
                    cache = OPS.dequantize_gathered(
                        cache, self.serve.kv_quant, self.pool.gathered_dtypes)
                    return BB.serve_reuse_packed(
                        params, self.cfg, flat_tokens, flat_positions, cache,
                        ctx)

                in_specs = self._stage_specs(2, with_cache=True)
                donate = self._donate(1, 2, 3)
            self._reuse_packed_jit[rp] = JC.jit_sharded(
                fn, mesh=self.mesh, in_specs=in_specs,
                donate_argnums=donate,
                entry="reuse_packed", counter=self._compile_counter)
        return self._reuse_packed_jit[rp]

    def _decode_fn(self, n: int):
        if n not in self._decode_jit:
            serve = self.serve

            def fn(params, h):
                # vocab-parallel under a mesh: the head weight stays sharded
                # over vocab (Rules placement) so each device computes its
                # vocab shard's logits and the argmax/logsumexp reduce across
                # shards — the full [N, V] never gathers onto one device.
                return LM.decode_tokens(
                    params["embed"], self.cfg, h,
                    max_num_logits=serve.max_num_logits,
                    mode=serve.logit_mode, vocab_tile=serve.vocab_tile)

            in_specs = self._stage_specs(1)
            self._decode_jit[n] = JC.jit_sharded(
                fn, mesh=self.mesh, in_specs=in_specs,
                donate_argnums=self._donate(1),
                entry="decode", counter=self._compile_counter)
        return self._decode_jit[n]

    def _decode_packed_fn(self, n: int):
        if n not in self._decode_packed_jit:
            serve = self.serve

            def fn(params, h, valid):
                return LM.decode_tokens_packed(
                    params["embed"], self.cfg, h, valid,
                    max_num_logits=serve.max_num_logits,
                    mode=serve.logit_mode, vocab_tile=serve.vocab_tile)

            in_specs = self._stage_specs(2)
            self._decode_packed_jit[n] = JC.jit_sharded(
                fn, mesh=self.mesh, in_specs=in_specs,
                donate_argnums=self._donate(1, 2),
                entry="decode_packed", counter=self._compile_counter)
        return self._decode_packed_jit[n]

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def warmup(self) -> float:
        """Pre-compile every bucketed step function (refresh/reuse/decode and
        the pool scatter/gather) with dummy inputs — the AOT warmup any
        production serving system performs before accepting traffic.

        Bucket bounds are audited against what the runtime can actually
        request (the invariant ``tests/test_engine.py`` asserts): every
        cap reads the NORMALIZED ``ServeConfig.refresh_slots`` (so
        ``max_refresh_per_iter=0`` warms up to the ``max_slots``-wide fused
        dispatch instead of nothing) and every doubling loop runs until it
        has covered the pow2 bucket of the cap (``b <= cap`` stopped short
        of ``pow2_bucket(cap)`` for non-pow2 caps, leaving the worst-case
        compile to fire mid-serve). Sub-worst-case buckets still compile
        lazily — only the largest shape per stage is guaranteed AOT.
        Returns the compile wall-time so harnesses can report it."""
        t0 = time.perf_counter()
        # warm under the same mesh context the dispatch path uses: the
        # Pallas wrappers consult the active mesh at trace time to
        # shard_map themselves per model shard
        with self._mesh_ctx():
            self._warmup_compile()
        # retrace-sentinel snapshot: everything compiled so far is warmup;
        # any compile-counter growth beyond this point is a steady-state
        # recompilation (the budget repro.analysis.retrace holds at zero)
        self.stats.compiles_warmup = sum(self._compile_counter.values())
        self.stats.compile_counts = dict(self._compile_counter)
        return time.perf_counter() - t0

    def _warmup_compile(self) -> None:
        S, Sb = self.serve.max_seq_len, self.serve.block_size
        F = self._fe_len
        r_eff = self.serve.refresh_slots

        def _fe(b):
            """Dummy frontend batch (None for text-only archs)."""
            if not F:
                return None
            return jnp.zeros((b, F, self.cfg.frontend_dim), jnp.float32)
        # the fused packed dispatch spans the WHOLE plan.refresh: the phase
        # scheduler caps that at refresh_slots, but the request-level
        # baseline admits whole batches up to max_slots and relies on the
        # engine to absorb them (serial chunks padded, one fused stream
        # packed) — warm the fused bucket to the scheduler's true bound.
        r_fused = r_eff if self.serve.scheduler == "phase" \
            else self.serve.max_slots
        if self._use_packed:
            # packed path: warm the worst-case (token bucket, request bucket)
            # per refresh fused-dispatch size; smaller buckets compile lazily.
            # Per-request segments span frontend prefix + text (S + F rows),
            # and the scheduler budget caps the stream either way.
            b = 1
            while True:
                tp = self._token_bucket(
                    min(b * (S + F), self.serve.max_num_batched_tokens))
                out = self._refresh_packed_fn(tp, b)(
                    self.params, jnp.zeros((tp,), jnp.int32),
                    jnp.zeros((tp,), jnp.int32),
                    jnp.zeros((tp,), jnp.int32),
                    jnp.ones((tp,), bool),
                    jnp.zeros((b,), jnp.int32),
                    jnp.full((b,), min(tp, S + F), jnp.int32),
                    jnp.zeros((b,), jnp.int32),
                    _fe(b))
                # warm the pool scatter at this bucket's batch shape too —
                # the runtime writes a slot list of exactly rp entries after
                # every refresh, so an ensure()-only warmup leaves pool_write
                # to compile mid-serve (the retrace sentinel catches this).
                # Scatter ZEROS: the dummy refresh output is mesh-dependent
                # numerics, and depositing it in the scratch slot would break
                # the 1-vs-N-device pool agreement oracle (shard_check)
                self.pool.write([self.pool.scratch_slot] * b,
                                jax.tree.map(jnp.zeros_like, out.cache))
                if b >= _bucket(r_fused):
                    break
                b *= 2
        # fresh dummy arrays per call, never a broadcast view of a shared
        # template: the stage jits donate their stream buffers, and a
        # same-shape broadcast can alias its source — reusing the template
        # after a donating call would read a dead buffer
        b = 1
        while not self._use_packed:
            out = self._refresh_fn(b)(
                self.params, jnp.zeros((b, S), jnp.int32),
                jnp.ones((b, F + S), bool),
                jnp.zeros((b,), jnp.int32), _fe(b))
            self.pool.write([self.pool.scratch_slot] * b,
                            jax.tree.map(jnp.zeros_like, out.cache))
            if b >= _bucket(r_eff):
                break
            b *= 2
        # auxiliary pool jit (COW promote copy) — warmed here so a sharing
        # pool's first divergence/free-while-shared never compiles mid-serve
        # (no-op without sharing); the refresh loops above materialized the
        # pool, so the copy compiles at its real shapes
        self.pool.warm_aux()
        r_cap = max(1, min(self.serve.max_slots,
                           self.serve.max_num_batched_tokens // Sb))
        if self._use_packed:
            # packed Reuse: buckets are token_bucket-granular request counts
            # (doubling warm; intermediate multiples compile lazily)
            rp = self._reuse_bucket(1)
            while True:
                self._reuse_packed_call(
                    rp, [self.pool.scratch_slot] * rp, 0,
                    np.zeros((rp * Sb,), np.int32),
                    np.zeros((rp * Sb,), np.int32))
                if rp >= self._reuse_bucket(r_cap):
                    break
                rp = min(rp * 2, self._reuse_bucket(r_cap))
        else:
            b = 1
            while True:
                cache = self.pool.gather([self.pool.scratch_slot] * b)
                self._reuse_fn(b)(self.params,
                                  jnp.zeros((b, Sb), jnp.int32),
                                  jnp.zeros((b, Sb), jnp.int32), cache)
                if b >= _bucket(r_cap):
                    break
                b *= 2
        max_logits = (r_eff + self.serve.max_slots) * Sb
        dt = jnp.dtype(self.cfg.dtype)
        if self.serve.varlen_pack:
            n = self._logit_bucket(Sb)
            while True:
                self._decode_packed_fn(n)(
                    self.params, jnp.zeros((n, self.cfg.d_model), dt),
                    jnp.ones((n,), bool))
                if n >= self._logit_bucket(max_logits):
                    break
                n = min(n * 2, self._logit_bucket(max_logits))
        else:
            # padded decode buckets: the runtime requests pow2_bucket(N,
            # lo=Sb) for N <= max_logits rows, so the bucket-cover invariant
            # stops exactly at pow2_bucket(max_logits, lo=Sb) — the old
            # ``while n <= max_logits * 2`` bound compiled one pow2 bucket
            # beyond anything the runtime can ever request.
            n = Sb
            while True:
                self._decode_fn(n)(self.params,
                                   jnp.zeros((n, self.cfg.d_model), dt))
                if n >= _bucket(max_logits, lo=Sb):
                    break
                n *= 2

    def submit(self, prompt: np.ndarray, gen_len: int, arrival: float = 0.0,
               rid: Optional[int] = None,
               frontend: Optional[np.ndarray] = None,
               deadline: float = math.inf) -> Request:
        """Queue a request. For modality-frontend archs ``frontend`` carries
        the request's precomputed patch/frame embeddings
        ``[frontend_len, frontend_dim]`` (the stub contract: the vision/audio
        tower runs offline); omitted, a deterministic stand-in is drawn from
        the engine rng so synthetic workloads exercise the real geometry.

        Admission control (docs/robustness.md): a request that can NEVER be
        admitted (total_len > max_seq_len, or Refresh cost > the token
        budget) is returned immediately in a terminal REJECTED state with a
        per-request ``error`` — it is never enqueued and cannot stall the
        engine. Under ``queue_cap`` the bounded-queue policy may reject this
        request or shed the oldest waiter instead; check ``req.outcome``.
        ``deadline`` is absolute trace time (inf = none): expired waiters
        are shed at plan time with Outcome.SHED_DEADLINE."""
        if self.cfg.frontend_dim:
            if frontend is None:
                frontend = self._rng.standard_normal(
                    (self.cfg.frontend_len, self.cfg.frontend_dim)).astype(
                        np.float32)
            frontend = np.asarray(frontend, np.float32)
            assert frontend.shape == (self.cfg.frontend_len,
                                      self.cfg.frontend_dim), frontend.shape
        else:
            assert frontend is None, \
                f"{self.cfg.name} is text-only but got frontend embeddings"
        req = Request(rid=rid if rid is not None else next(self._rid_counter),
                      prompt=np.asarray(prompt, np.int32), gen_len=gen_len,
                      arrival=arrival, cfg=self.serve, mask_id=self.mask_id,
                      frontend=frontend, deadline=deadline)
        self.stats.submitted += 1
        reason = admission_block_reason(self.serve, req)
        if reason is not None:
            req.state = State.REJECTED
            req.outcome = Outcome.REJECTED_OVERSIZED
            req.error = reason
            self._tally(req)
            return req
        for casualty in self.scheduler.submit(req):
            self._tally(casualty)     # bounded-queue reject/evict victims
        return req

    def _tally(self, req: Request) -> None:
        """Record a terminal outcome in the conservation counters."""
        o = req.outcome
        if o is Outcome.FINISHED:
            self.stats.finished += 1
        elif o is Outcome.REJECTED_OVERSIZED:
            self.stats.rejected_oversized += 1
        elif o is Outcome.REJECTED_QUEUE_FULL:
            self.stats.rejected_queue_full += 1
        elif o is Outcome.SHED_DEADLINE:
            self.stats.shed_deadline += 1
        elif o is Outcome.SHED_QUEUE:
            self.stats.shed_queue += 1
        else:                          # pragma: no cover - defensive
            raise AssertionError(f"tally of non-terminal request {req.rid}")

    def run(self, time_scale: float = 1.0, max_iters: int = 100_000,
            quiet: bool = True) -> EngineStats:
        """Serve until every submitted request reaches a terminal state
        (FINISHED, or SHED / REJECTED by the admission-control layer).

        wall clock: ``time_scale`` maps trace seconds to wall seconds.
        modeled clock: arrivals/latencies in virtual device seconds.

        Overload is NOT an error (docs/robustness.md): never-admittable
        requests are rejected with a structured per-request outcome at
        submit/plan time, deadline-expired waiters are shed, bounded queues
        apply backpressure, and starvation triggers preempt-and-requeue —
        the engine degrades instead of dying. The ``RuntimeError`` below is
        reserved for a TRUE invariant violation: a zero-progress iteration
        with admittable work resident and no future arrival, deadline, or
        pending injected fault that could unblock it (admission and
        deferral depend only on budget/slot state, which time alone cannot
        change). The old silent ``break`` here exited with unfinished
        requests still resident and recorded bogus throughput/latency
        stats for them.

        Pipelined loop (``ServeConfig.pipeline``, docs/engine.md): each lap
        (1) builds iteration i+1's plan + packed layout — pure host work
        that overlaps iteration i's dispatched stages still executing
        asynchronously on device, (2) performs the ONE deferred host sync
        of iteration i (its committed token values must land before i+1's
        stage buffers read ``r.tokens``), then (3) fills and dispatches
        i+1, leaving its sync pending for the next lap. The control plane
        (masked counts, block completion, FINISHED, the modeled clock)
        advanced at dispatch time and is value-independent, so the order
        of scheduler/stats/vtime mutations is exactly the synchronous
        loop's — bit-identity is by construction, not by luck. With
        ``pipeline=False`` each lap syncs immediately (the oracle)."""
        start = self._run_start = time.perf_counter()
        self._time_scale = time_scale
        pending: Optional[_Pending] = None
        it = 0
        while self.scheduler.has_work and it < max_iters:
            if self.clock == "modeled":
                now = self.vtime
            else:
                now = self._run_clock()
            prep = self._begin_iteration(now)
            if pending is not None:
                # the plan above was built while the previous dispatch was
                # still in flight — the overlap the pipeline buys
                self.stats.overlapped_host_s += prep.plan_s
                self.stats.dispatched_ahead += 1
                self._sync_iteration(pending)
                pending = None
            if prep.has_exec:
                nxt = self._dispatch_iteration(prep)
                if self.serve.pipeline:
                    pending = nxt
                else:
                    self._sync_iteration(nxt)
                progressed = True
            else:
                progressed = prep.lifecycle
            if not progressed:
                # time CAN unblock two things: a future arrival (admission)
                # and a future deadline (shedding a waiter that will never
                # fit alongside the current residents)
                events = [r.arrival for r in self.scheduler.waiting
                          if r.arrival > now]
                events += [r.deadline for r in self.scheduler.waiting
                           if now < r.deadline < math.inf]
                nxt = min(events, default=None)
                if nxt is None and self._fault_blocked:
                    # injected alloc faults / mem-pressure steals suppress
                    # admission transiently; the schedule is finite and
                    # advances per iteration, so spin — never a stall
                    it += 1
                    continue
                if nxt is None:
                    n_run = len(self.scheduler.running)
                    n_wait = len(self.scheduler.waiting)
                    raise RuntimeError(
                        f"engine stalled with work left at t={now:.3f}: "
                        f"{n_run} running / {n_wait} waiting requests and "
                        f"an empty iteration plan that no future arrival, "
                        f"deadline, or fault schedule can unblock — an "
                        f"engine/scheduler invariant violation (oversized, "
                        f"expired, and overload traffic is rejected or "
                        f"shed with structured outcomes before this "
                        f"point). Serve limits: max_num_batched_tokens="
                        f"{self.serve.max_num_batched_tokens}, block_size="
                        f"{self.serve.block_size}, max_slots="
                        f"{self.serve.max_slots}, refresh cap="
                        f"{self.serve.refresh_slots}.")
                if self.clock == "modeled":
                    self.vtime = max(self.vtime, nxt)   # jump to next event
                else:
                    wait = nxt * time_scale - (time.perf_counter() - start)
                    if wait > 0:
                        with _span(SPAN_ARRIVAL):
                            time.sleep(min(wait, 0.05))
            it += 1
        if pending is not None:
            # drain the last in-flight iteration OUTSIDE the loop: a drain
            # lap would advance the iteration counter (and with it the
            # fault schedule) past the synchronous oracle
            self._sync_iteration(pending)
            pending = None
        self.stats.wall_time = (self.vtime if self.clock == "modeled"
                                else time.perf_counter() - start)
        self.stats.iterations = it
        self.stats.compile_counts = dict(self._compile_counter)
        if self.pool.ledger is not None:
            self.stats.shared_hits = self.pool.ledger.hits
            self.stats.shared_cow_promotes = self.pool.ledger.cow_promotes
            self.stats.phys_slots_peak = self.pool.phys_peak
        return self.stats

    # -- modeled-clock cost accounting -------------------------------------
    def _charge(self, kind: str, exec_tokens: int, kv_len: int = 0,
                actual_tokens: Optional[int] = None) -> None:
        if self.clock != "modeled":
            return
        cfg = self.cfg
        # A stage is billed for real tokens only when its packed path really
        # executed (no more "pretend-packed" carve-outs): Refresh and Reuse
        # follow the engine gate — every family packs now (attention stream,
        # segment-reset SSD scan, or frontend-prefix segments for vlm/audio;
        # the padded oracle runs only when varlen_pack is off and then pays
        # the rectangle) — while the logit stage packs under varlen_pack for
        # every family (the output head is family-agnostic, so the engine
        # always buckets the hidden stream on tokens there).
        if kind == "decode":
            varlen = self.serve.varlen_pack
        else:
            varlen = self.serve.varlen_pack and self._use_packed
        tokens = (actual_tokens if varlen
                  and actual_tokens is not None else exec_tokens)
        flops = 2.0 * self._n_params * tokens
        if cfg.has_attention and kv_len:
            dh = cfg.resolved_head_dim
            flops += 4.0 * tokens * kv_len * cfg.n_heads * dh \
                * cfg.n_layers
        if kind == "decode":
            # the fused Pallas argmax tile-skips all-pad rows (the validity
            # mask threaded into the kernel), so it pays real rows; the
            # chunked/monolithic jnp matmul computes every bucketed row of
            # its [N, V] chunk and is billed for the rectangle — the decode
            # half of the modeled-clock gap the kernels close
            rows = tokens if self.serve.logit_mode == "fused" \
                else exec_tokens
            flops = 2.0 * cfg.d_model * cfg.vocab_size * rows
        # the model (TP) axis splits real work by its actually-sharded
        # fraction (_tp_work_split: 1.0 when nothing divides); the data axis
        # multiplies in its replica streams only when the slot pool really
        # shards over it (_dp_work_split — 1.0 on a data axis of 1, so a
        # replicating mesh can never fake a speedup)
        self.vtime += self.device.call_cost(
            flops, self._tp_work_split * self._dp_work_split)

    # ------------------------------------------------------------------
    # one engine iteration
    # ------------------------------------------------------------------
    def step(self, now: float) -> bool:
        """One engine iteration, fully synchronous: plan → dispatch → sync.
        Returns True when the iteration made progress — executed work OR a
        lifecycle event (shed / rejected / preempted request), which also
        changes engine state. :meth:`run` composes the same three phases
        with the sync deferred one iteration (dispatch-ahead); direct
        callers get the oracle ordering."""
        prep = self._begin_iteration(now)
        if not prep.has_exec:
            return prep.lifecycle
        self._sync_iteration(self._dispatch_iteration(prep))
        return True

    def _run_clock(self) -> float:
        """Seconds since :meth:`run` started (since construction before the
        first ``run``), in trace seconds (``time_scale``): the wall clock's
        ``now`` and its commit stamps."""
        return (time.perf_counter() - self._run_start) / self._time_scale

    @_spanned(SPAN_PLAN)
    def _begin_iteration(self, now: float) -> _Prepared:
        """Plan one iteration: fault-schedule tick, scheduler plan, packed
        layout. Pure host work — no device dispatch, no host sync — so the
        pipelined loop runs it while the previous iteration's stages are
        still executing on device. Everything here depends only on request
        lengths/phases/arrivals (never token values), which is why it can
        legally run before the previous iteration's tokens are synced."""
        t0 = time.perf_counter()
        self._iter += 1
        if self.faults is not None:
            self.faults.begin_iteration(self._iter)
            d = self.faults.take_slow_delay()
            if d:
                self.stats.slow_fault_s += d
                if self.clock == "modeled":
                    self.vtime += d
                else:
                    time.sleep(min(d, 0.05))
        plan = self.scheduler.plan(now)
        for r in plan.rejected + plan.shed:
            self._tally(r)
        self.stats.preemptions += len(plan.preempted)
        self.stats.recomputed_tokens += plan.recomputed_tokens
        if plan.alloc_faults:
            self.stats.alloc_fault_iters += 1
        # a fault-suppressed iteration must not be mistaken for a stall:
        # run() spins through it (the schedule is finite) instead of raising
        self._fault_blocked = plan.alloc_faults > 0 or (
            self.faults is not None and bool(self.scheduler.waiting)
            and self.faults.blocking())
        lifecycle = bool(plan.rejected or plan.shed or plan.preempted)
        layout = None
        if plan.has_exec:
            self.stats.deferred_steps += len(plan.deferred)
            self.stats.peak_query_tokens = max(self.stats.peak_query_tokens,
                                               plan.query_tokens)
            # whole-iteration packed layout (drives the packed pipeline)
            if self._use_packed:
                layout = plan.packed_layout(self.serve.refresh_slots)
        plan_s = time.perf_counter() - t0
        self.stats.host_plan_s += plan_s
        return _Prepared(now, plan, layout, lifecycle, plan_s)

    @_spanned(SPAN_DISPATCH)
    def _dispatch_iteration(self, prep: _Prepared) -> _Pending:
        """Fill stage buffers and launch every device dispatch for one
        planned iteration, advance the control plane, and return the
        iteration's pending sync (the decode outputs still on device).
        Modeled-clock charges happen here — the same program points the
        synchronous loop charged them at — so vtime sequencing is
        identical whether the sync is deferred or immediate."""
        t0 = time.perf_counter()
        now, plan, layout = prep.now, prep.plan, prep.layout

        hidden_rows: List[jax.Array] = []
        decoded: List[Request] = []
        cap = self.serve.refresh_slots

        # ---- Refresh: ONE fused packed dispatch / padded per-cap chunks ----
        iter_real = iter_exec = 0
        if self._use_packed:
            seg = layout.refresh_fused
            if seg is not None:
                # single fused dispatch across the refresh chunks: the whole
                # iteration's Refresh set is one ragged stream, so launch
                # overhead is paid once per iteration, not once per chunk
                chunk = list(seg.requests)
                t_real = seg.total_tokens
                bh, exec_tokens = self._run_refresh_packed(seg)
                # packed attention cost: the Pallas varlen kernel skips
                # non-intersecting segment tiles, paying Σ Sᵢ² — effective
                # kv length is the token-weighted mean segment length
                # (frontend prefix included). The jnp masked-stream fallback
                # really computes the full [T, T] rectangle and is billed
                # for it — this is the modeled-clock gap the flash kernels
                # close on the packed Refresh stream.
                if self.ctx.use_flash_kernel:
                    kv_len = sum(r.refresh_len ** 2
                                 for r in chunk) // max(t_real, 1)
                else:
                    kv_len = exec_tokens
                hidden_rows.append(bh)
                decoded.extend(chunk)
                self.stats.refresh_steps += len(chunk)
                iter_real += t_real
                iter_exec += exec_tokens
                self._charge("refresh", exec_tokens, kv_len=kv_len,
                             actual_tokens=t_real)
        else:
            for i in range(0, len(plan.refresh), cap):
                chunk = plan.refresh[i: i + cap]
                t_real = sum(r.refresh_len for r in chunk)
                bh, exec_tokens = self._run_refresh(chunk)
                hidden_rows.append(bh)
                decoded.extend(chunk)
                self.stats.refresh_steps += len(chunk)
                iter_real += t_real
                iter_exec += exec_tokens
                self._charge("refresh", exec_tokens,
                             kv_len=self.serve.max_seq_len + self._fe_len,
                             actual_tokens=t_real)

        # ---- Reuse: one ragged block stream (packed) / pow2 batch (oracle) --
        r_real = r_exec = r_gathered = 0
        if plan.reuse:
            r_real = len(plan.reuse) * self.serve.block_size
            if self._use_packed:
                bh, r_exec = self._run_reuse_packed(layout.reuse)
            else:
                bh, r_exec = self._run_reuse(plan.reuse)
            # slots copied out of the pool: every request row's, padding
            # included, unless the Reuse read the pool in place
            if not (self._use_packed and self._reuse_reads_pool()):
                r_gathered = r_exec // self.serve.block_size
            hidden_rows.append(bh)
            decoded.extend(plan.reuse)
            self.stats.reuse_steps += len(plan.reuse)
            self._charge("reuse", r_exec,
                         kv_len=self.ctx.retain + self.serve.block_size,
                         actual_tokens=r_real)

        # ---- budgeted logit stage (C1) over every active block ----
        n_real = n_exec = 0
        ids = conf = None
        if decoded:
            D = self.cfg.d_model
            N = n_real = len(decoded) * self.serve.block_size

            def build_h(b):
                # built INSIDE the dispatch thunk: the stage jits donate
                # their stream buffers, so the concatenated rows must die
                # with the call — and a fault-retried attempt rebuilds the
                # buffer instead of re-passing a donated one
                h = jnp.concatenate([r.reshape(-1, D)
                                     for r in hidden_rows], axis=0)
                return jnp.pad(h, ((0, b - N), (0, 0))) if b != N else h

            with _span(SPAN_LOGITS):
                if self.serve.varlen_pack:
                    # packed: token-bucket rounding + validity mask threaded
                    # into the decode kernel — no pow2 row bucket
                    b = self._logit_bucket(N)
                    valid = np.zeros((b,), bool)
                    valid[:N] = True
                    ids, conf = self._dispatch(
                        "decode", lambda: self._decode_packed_fn(b)(
                            self.params, build_h(b), jnp.asarray(valid)))
                else:
                    b = _bucket(N, lo=self.serve.block_size)
                    ids, conf = self._dispatch(
                        "decode", lambda: self._decode_fn(b)(self.params,
                                                             build_h(b)))
            # C1: serial sub-batches serialize on device; monolithic runs one
            # big call (launch amortized, memory unbounded)
            if self.serve.logit_mode == "monolithic":
                self._charge("decode", b, actual_tokens=N)
                n_exec = b
            else:
                sub = self.serve.max_num_logits
                for off in range(0, b, sub):
                    act = max(0, min(sub, N - off))
                    if act == 0 and self.serve.varlen_pack:
                        break   # a packed engine never launches all-pad chunks
                    self._charge("decode", min(sub, b - off),
                                 actual_tokens=act)
                    n_exec += min(sub, b - off)
            self.stats.logit_tokens_real += n_real
            self.stats.logit_tokens_exec += n_exec

        # control-plane advance at DISPATCH time (value-independent):
        # the scheduler sees this iteration's block completions / finishes
        # before planning the next one, exactly as in the synchronous loop.
        # The modeled clock stamps commits here; the wall clock stamps them
        # at the sync, when their values reach the host
        entries = self._advance_control(
            decoded, self.vtime if self.clock == "modeled" else None)

        # under iter_log_cap the log is a maxlen deque: appending evicts the
        # oldest row in O(1) — the aggregate counters above carry the
        # lifetime totals, so a long modeled-clock run doesn't grow host
        # memory one dict per iteration forever. (The deferred sync backfills
        # ``sync_s`` through the pending reference even after eviction.)
        fill_s = time.perf_counter() - t0
        self.stats.host_fill_s += fill_s
        log_row = dict(
            t=now, q_tokens=plan.query_tokens,
            n_refresh=len(plan.refresh), n_reuse=len(plan.reuse),
            n_logits=len(decoded) * self.serve.block_size,
            refresh_tokens_real=iter_real, refresh_tokens_exec=iter_exec,
            reuse_tokens_real=r_real, reuse_tokens_exec=r_exec,
            reuse_gathered_slots=r_gathered,
            logit_tokens_real=n_real, logit_tokens_exec=n_exec,
            plan_s=prep.plan_s, fill_s=fill_s, sync_s=0.0)
        self.stats.iter_log.append(log_row)
        return _Pending(ids, conf, n_real, entries, log_row)

    def _advance_control(self, decoded: List[Request],
                         t_commit: Optional[float]) -> List[_CommitEntry]:
        """Advance every scheduled request's state machine at dispatch time,
        WITHOUT the committed token values (they are still on device).

        ``diffusion.commit_count`` / ``commit_tokens`` unmask exactly
        ``min(n_commit, masked)`` positions as a function of counts alone —
        never of token values — so block completion, phase transitions,
        FINISHED, and the committed-token stat are all computable here.
        The returned entries carry what :meth:`_sync_iteration` needs to
        land the values once they arrive. ``t_commit`` None leaves the
        commit stamps to the sync."""
        entries: List[_CommitEntry] = []
        for j, r in enumerate(decoded):
            steps_left = self.serve.steps_per_block - r.step_in_block
            n_commit = diffusion.commit_count(r.masked_left, steps_left)
            e = _CommitEntry(req=r, row=j, block_start=r.block_start,
                             block_idx=r.block_idx, n_commit=n_commit,
                             n_act=0, epoch=r.commit_epoch, finished=False,
                             t=t_commit)
            e.n_act = r.advance_control(n_commit, t_commit)
            self.stats.committed_tokens += e.n_act
            e.finished = r.state == State.FINISHED
            if e.finished:
                self.scheduler.finish(r)
                self._tally(r)
            entries.append(e)
        return entries

    @_spanned(SPAN_SYNC)
    def _sync_iteration(self, pending: _Pending) -> None:
        """The iteration's SINGLE deferred host sync: pull the decode
        outputs, land each entry's token values into its recorded block —
        unless a preemption rollback bumped the request's epoch while the
        commit was in flight, in which case the values are discarded (the
        rollback already booked them as recompute debt, and only
        mid-block Reuse residents are preemptible, so a stale epoch always
        refers to the rolled-back block itself). Streaming events fire
        here: this is the first moment the values exist host-side."""
        if pending.ids is None:
            return
        t0 = time.perf_counter()
        # one blocking transfer instead of two per-array host syncs —
        # the engine's SINGLE annotated sync point (docs/analysis.md)
        with _span(SPAN_WAIT):
            ids, conf = jax.device_get(  # lint: allow(host-sync)
                (pending.ids, pending.conf))
        sync_s = time.perf_counter() - t0
        self.stats.sync_wait_s += sync_s
        pending.log_row["sync_s"] = sync_s
        Sb = self.serve.block_size
        # wall clock: every value of this iteration reached the host now
        t_land = self._run_clock() if self.clock == "wall" else None
        with _span(SPAN_LAND):
            for e in pending.entries:
                if e.req.commit_epoch != e.epoch:
                    continue      # preempted while in flight: values dropped
                rid = ids[e.row * Sb: (e.row + 1) * Sb]
                rconf = conf[e.row * Sb: (e.row + 1) * Sb]
                s = e.block_start
                newblk = diffusion.commit_tokens(
                    e.req.tokens[s: s + Sb], rid, rconf, e.n_commit,
                    self.mask_id)
                e.req.tokens[s: s + Sb] = newblk
                if e.t is None:
                    e.t = t_land
                    if e.req.t_first_commit < 0 and e.n_act > 0:
                        e.req.t_first_commit = t_land
                    if e.finished:
                        e.req.t_finished = t_land
                if self._stream_cb is not None:
                    self.stats.streamed_events += 1
                    self._stream_cb(dict(
                        rid=e.req.rid, t=e.t, block_idx=e.block_idx,
                        n_committed=e.n_act, finished=e.finished,
                        tokens=np.array(newblk)))

    # ------------------------------------------------------------------
    def _mesh_ctx(self):
        """Activate the serving mesh around a stage trace: the Pallas
        wrappers (``kernels.ops``) consult ``jax_compat.get_active_mesh()``
        at trace time to shard_map themselves over the model axis. A no-op
        (null context) without a mesh — the no-mesh path stays untouched."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return JC.use_mesh(self.mesh)

    def _dispatch(self, stage: str, thunk):
        """Run one jitted stage call under the fault-injection harness,
        inside the serving-mesh context (see :meth:`_mesh_ctx`).

        An injected (or real) :class:`FaultError` is retried with
        exponential backoff — charged to the modeled clock, slept on wall —
        up to ``ServeConfig.fault_retries`` attempts, after which it
        propagates as permanent. Without a fault plan this is a plain
        call (zero overhead on the no-faults path)."""
        if self.faults is None:
            with self._mesh_ctx():
                return thunk()
        attempt = 0
        while True:
            attempt += 1
            try:
                if self.faults.take_dispatch_fault(stage):
                    raise FaultError(
                        f"injected {stage} dispatch fault "
                        f"(iter {self._iter}, attempt {attempt})")
                with self._mesh_ctx():
                    return thunk()
            except FaultError:
                if attempt >= self.serve.fault_retries:
                    raise
                self.stats.dispatch_retries += 1
                backoff = self.device.launch_s * (2 ** (attempt - 1))
                if self.clock == "modeled":
                    self.vtime += backoff
                else:
                    time.sleep(min(backoff, 0.05))

    def _check_slots(self, reqs: List[Request]) -> None:
        """Slot-handle integrity guard before any pool write/gather: a None
        slot or a generation mismatch means a freed-and-recycled slot is
        about to be touched for a stale holder — always an engine bug (or a
        deliberate test injection), never a recoverable serving condition."""
        for r in reqs:
            if r.slot is None or r.slot_gen is None:
                raise RuntimeError(
                    f"stale slot handle: request {r.rid} scheduled with no "
                    f"slot (state={r.state})")
            gen = self.pool.generation(r.slot)
            if gen != r.slot_gen:
                raise RuntimeError(
                    f"stale slot handle: request {r.rid} holds slot "
                    f"{r.slot}@gen{r.slot_gen} but the pool is at gen "
                    f"{gen} — the slot was freed and recycled under the "
                    f"request")

    def _pool_write(self, chunk: List[Request], cache, n_pad: int) -> None:
        """Land one Refresh batch in the slot pool. With sharing enabled the
        write is content-addressed: each request's Refresh key routes
        through the pool's share ledger (dedup hit -> device write skipped,
        divergence -> COW promote), padding rows (key None) always scatter
        to scratch. Without sharing this is the plain batched scatter."""
        slots = [r.slot for r in chunk] + \
            [self.pool.scratch_slot] * n_pad
        if not self._sharing:
            self.pool.write(slots, cache)
            return
        keys = [r.refresh_key() for r in chunk] + [None] * n_pad
        self.pool.write_shared(slots, cache, keys)

    def _run_refresh(self, chunk: List[Request]) -> Tuple[jax.Array, int]:
        """Padded-oracle Refresh. For modality-frontend archs the embedded
        batch is ``[b, frontend_len + max_seq_len]`` (prefix rows first), so
        validity, block offsets, and the executed-token bill all span the
        full rectangle. Returns (block hidden, executed tokens)."""
        n = len(chunk)
        b = _bucket(n)
        S = self.serve.max_seq_len
        F = self._fe_len
        with _span(SPAN_REFRESH):
            tokens = np.zeros((b, S), np.int32)
            valid = np.zeros((b, F + S), bool)
            bstart = np.zeros((b,), np.int32)
            fe = np.zeros((b, F, self.cfg.frontend_dim), np.float32) \
                if F else None
            for j, r in enumerate(chunk):
                tokens[j] = r.tokens
                valid[j, : F + r.total_len] = True
                bstart[j] = F + r.block_start
                if F:
                    fe[j] = r.frontend
            self._check_slots(chunk)
            out = self._dispatch("refresh", lambda: self._refresh_fn(b)(
                self.params, jnp.asarray(tokens), jnp.asarray(valid),
                jnp.asarray(bstart), jnp.asarray(fe) if F else None))
        self._pool_write(chunk, out.cache, b - n)
        self.stats.padded_refresh_calls += 1
        self.stats.refresh_tokens_real += sum(r.refresh_len for r in chunk)
        self.stats.refresh_tokens_exec += b * (F + S)
        return out.block_hidden[:n], b * (F + S)

    def _run_refresh_packed(self, seg_layout) -> Tuple[jax.Array, int]:
        """Token-packed Refresh (§4.1): one ragged stream bucketed on total
        tokens — real compute pays for real tokens, never a
        ``[B, max_seq_len]`` padded call. The stream offsets come straight
        from the scheduler's :class:`StageSegments` (the plan-level
        cu_seqlens contract drives execution); for vlm/audio each segment
        carries its ``frontend_len`` projected prefix rows ahead of the
        text tokens, already accounted in those offsets. Returns (block
        hidden, executed tokens = the token bucket)."""
        chunk = list(seg_layout.requests)
        n = len(chunk)
        self._check_slots(chunk)
        out, tp, rp = self._refresh_packed(chunk, seg_layout.cu_seqlens)
        self._pool_write(chunk, out.cache, rp - n)
        self.stats.packed_refresh_calls += 1
        self.stats.refresh_tokens_real += seg_layout.total_tokens
        self.stats.refresh_tokens_exec += tp
        return out.block_hidden[:n], tp

    @_spanned(SPAN_REFRESH)
    def _refresh_packed(self, chunk: List[Request], cu_real):
        """Fill one packed Refresh stream for ``chunk`` (segment j starts at
        flat row ``cu_real[j]``) and dispatch it. Returns (RefreshOut,
        token bucket, request bucket); the pool is not touched."""
        n = len(chunk)
        rp = _bucket(n)
        t_real = int(cu_real[n])
        tp = self._token_bucket(t_real)
        F = self._fe_len
        tokens = np.zeros((tp,), np.int32)
        pos = np.zeros((tp,), np.int32)
        seg = np.full((tp,), FV.PAD_SEG, np.int32)
        valid = np.zeros((tp,), bool)
        # padding requests point at the (invalid) tail so their gathers are
        # in-bounds; their caches land in the scratch slot. (Their lens stay
        # 0, which is what keeps embed_inputs_packed from scattering frontend
        # rows over real tokens when the bucket is exactly full.)
        cu = np.full((rp,), max(0, tp - 1), np.int32)
        lens = np.zeros((rp,), np.int32)
        bstart = np.zeros((rp,), np.int32)
        fe = np.zeros((rp, F, self.cfg.frontend_dim), np.float32) \
            if F else None
        for j, r in enumerate(chunk):
            off = int(cu_real[j])
            ln = r.refresh_len            # frontend prefix + text
            assert ln == int(cu_real[j + 1]) - off, "layout/request mismatch"
            # segment = [F projected frontend rows ; total_len text tokens];
            # the prefix token ids are placeholders (embed_inputs_packed
            # overwrites those embedding rows with the projected frontend)
            tokens[off + F: off + ln] = r.tokens[: r.total_len]
            pos[off: off + ln] = np.arange(ln, dtype=np.int32)
            seg[off: off + ln] = j
            valid[off: off + ln] = True
            cu[j] = off
            lens[j] = ln
            bstart[j] = F + r.block_start
            if F:
                fe[j] = r.frontend
        out = self._dispatch("refresh", lambda: self._refresh_packed_fn(
            tp, rp)(
            self.params, jnp.asarray(tokens), jnp.asarray(pos),
            jnp.asarray(seg), jnp.asarray(valid), jnp.asarray(cu),
            jnp.asarray(lens), jnp.asarray(bstart),
            jnp.asarray(fe) if F else None))
        return out, tp, rp

    def _reuse_packed_call(self, rp: int, slots: List[int], n: int, btok,
                           bpos) -> jax.Array:
        """One packed Reuse program call over ``slots`` (the first ``n``
        real). Everything it passes is read when it is called, inside the
        dispatch thunk: a fault-retried attempt must see the pool a later
        ``pool_write`` left (it donates the old buffer), and a gathered
        cache is donated to the program, so each attempt gathers anew."""
        fn = self._reuse_packed_fn(rp)
        if self._reuse_reads_pool():
            return fn(self.params, jnp.asarray(btok), jnp.asarray(bpos),
                      self.pool.cache, jnp.asarray(self.pool.rows(slots)),
                      jnp.asarray([n], jnp.int32))
        return fn(self.params, jnp.asarray(btok), jnp.asarray(bpos),
                  self.pool.gather(slots))

    def refresh_outputs(self, reqs: List[Request]):
        """The packed Refresh stage over ``reqs`` as one stream, through the
        engine's own stage jit — what an iteration that refreshes exactly
        these requests computes — without writing the slot pool or counting
        stats. Returns the RefreshOut (block hidden ``[n, Sb, D]`` first,
        then request-bucket padding). The entry point of the agreement
        checks against the padded oracle and across meshes."""
        cu = np.concatenate([[0], np.cumsum([r.refresh_len for r in reqs])])
        out, _, _ = self._refresh_packed(list(reqs), cu)
        return out

    @_spanned(SPAN_REUSE)
    def _run_reuse(self, reqs: List[Request]) -> Tuple[jax.Array, int]:
        """Padded-oracle Reuse: pow2 request bucket, scratch-slot pad rows.
        Returns (block hidden [n, Sb, D], executed tokens = bucket·Sb)."""
        n = len(reqs)
        b = _bucket(n)
        Sb = self.serve.block_size
        btok = np.zeros((b, Sb), np.int32)
        bpos = np.zeros((b, Sb), np.int32)
        slots = [self.pool.scratch_slot] * b
        F = self._fe_len
        for j, r in enumerate(reqs):
            btok[j] = r.block_tokens()
            bpos[j] = np.arange(F + r.block_start, F + r.block_start + Sb)
            slots[j] = r.slot
        self._check_slots(reqs)
        # gather INSIDE the thunk: the reuse jit donates the gathered cache,
        # so each dispatch attempt (fault retries included) needs its own
        h = self._dispatch("reuse", lambda: self._reuse_fn(b)(
            self.params, jnp.asarray(btok), jnp.asarray(bpos),
            self.pool.gather(slots)))
        self.stats.padded_reuse_calls += 1
        self.stats.reuse_tokens_real += n * Sb
        self.stats.reuse_tokens_exec += b * Sb
        return h[:n], b * Sb

    @_spanned(SPAN_REUSE)
    def _run_reuse_packed(self, seg_layout) -> Tuple[jax.Array, int]:
        """Token-packed Reuse: the iteration's active blocks run as one
        ragged ``[R·Sb]`` query stream against their slot caches — R is
        rounded only to the token-bucket granularity (scratch slots back
        the padding segments), never a pow2 batch bucket. Returns (block
        hidden [n, Sb, D], executed tokens = rp·Sb)."""
        reqs = seg_layout.requests
        n = len(reqs)
        Sb = self.serve.block_size
        rp = self._reuse_bucket(n)
        tq = rp * Sb
        btok = np.zeros((tq,), np.int32)
        bpos = np.zeros((tq,), np.int32)
        slots = [self.pool.scratch_slot] * rp
        F = self._fe_len
        for j, r in enumerate(reqs):
            off = int(seg_layout.cu_seqlens[j])
            btok[off: off + Sb] = r.block_tokens()
            bpos[off: off + Sb] = np.arange(F + r.block_start,
                                            F + r.block_start + Sb)
            slots[j] = r.slot
        self._check_slots(list(reqs))
        h = self._dispatch("reuse", lambda: self._reuse_packed_call(
            rp, slots, n, btok, bpos))
        self.stats.packed_reuse_calls += 1
        if self._reuse_reads_pool():
            self.stats.reuse_inplace_calls += 1
        self.stats.reuse_tokens_real += n * Sb
        self.stats.reuse_tokens_exec += tq
        return h.reshape(rp, Sb, -1)[:n], tq
