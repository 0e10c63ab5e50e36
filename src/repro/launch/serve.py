"""Serving launcher: run the dLLM-Serve engine over a synthetic workload.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch llada-8b --hbm-gb 16 \
      --system dllm-serve --workload burst --rps 2.0 --n 12

Mesh serving: ``--mesh 1,2`` (or ``REPRO_MESH=1,2`` in the environment) runs
the whole packed pipeline tensor-parallel on a (data, model) device mesh —
the host must expose the devices (CPU repro:
``XLA_FLAGS=--xla_force_host_platform_device_count=2``); a mesh that cannot
be built fails loudly instead of collapsing to one device, and the result
JSON records ``mesh_devices`` so harnesses can assert it. ``--mesh none``
forces the single-device engine even when ``REPRO_MESH`` is set.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional, Tuple

import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import ServeConfig
from repro.core.baselines import size_slots, system_profiles
from repro.core.budgeting import plan_memory
from repro.core.engine import Engine
from repro.core.faults import FaultPlan
from repro.core.request import State
from repro.data.workloads import make_trace, prefix_share_factor, \
    trace_prompts
from repro.launch.mesh import parse_mesh_env


def device_memory_bytes() -> int:
    """Allocatable bytes of the first device, as the runtime reports them
    (``memory_stats()["bytes_limit"]``). A backend that reports no limit
    (XLA:CPU) is an error — slot sizing never guesses."""
    import jax
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    if "bytes_limit" not in stats:
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no memory "
            f"limit; pass the slot-sizing budget explicitly (hbm_bytes)")
    return int(stats["bytes_limit"])


def run_serve(arch: str, system: str, workload: str, rps: float, n: int,
              use_reduced: bool = True, seed: int = 0,
              max_seq_len: int = 256, block_size: int = 8,
              steps_per_block: int = 8, max_slots: int = 12,
              max_num_batched_tokens: int = 1024, max_num_logits: int = 128,
              time_scale: float = 1.0, length_scale: float = 0.15,
              size_by_profiler: bool = True,
              hbm_bytes: Optional[int] = None,
              clock: str = "modeled", quiet: bool = True,
              mesh_shape: Optional[Tuple[int, ...]] = None,
              queue_cap: int = 0, queue_policy: str = "reject",
              deadline_slack: float = float("inf"),
              preempt_starvation_s: float = 0.0,
              fault_seed: Optional[int] = None,
              kernels: Optional[bool] = None,
              prefix_sharing: bool = False,
              kv_quant: str = "none",
              pipeline: bool = True,
              stream: bool = False) -> dict:
    """Serve one synthetic trace and return the stats as a dict.

    ``size_by_profiler`` clamps ``max_slots`` to what the offline profiler
    (§4.2) fits in one device's memory for the config that runs, billed in
    its own dtype: ``hbm_bytes`` when given (hosts whose runtime reports no
    memory limit, such as XLA:CPU, must give it), else the device's own
    ``bytes_limit``. On a mesh the plan is per device."""
    import dataclasses
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    base = ServeConfig(
        max_num_batched_tokens=max_num_batched_tokens,
        max_num_logits=max_num_logits, block_size=block_size,
        steps_per_block=steps_per_block, max_seq_len=max_seq_len,
        max_slots=max_slots, max_refresh_per_iter=4,
        mesh_shape=tuple(mesh_shape) if mesh_shape else None,
        queue_cap=queue_cap, queue_policy=queue_policy,
        preempt_starvation_s=preempt_starvation_s,
        prefix_sharing=prefix_sharing, kv_quant=kv_quant,
        pipeline=pipeline)
    serve = system_profiles(base)[system]
    if kernels:
        # Pallas hot paths on top of the system profile (shard_mapped per
        # model shard under a mesh — validated at engine construction, no
        # silent fallback); kernels=False pins the jnp fallback paths
        serve = dataclasses.replace(serve, use_flash_kernel=True,
                                    logit_mode="fused")
    elif kernels is not None:
        serve = dataclasses.replace(serve, use_flash_kernel=False,
                                    logit_mode="chunked")
    # trace first: the profiler's sharing-aware sizing reads the trace's
    # measured share factor (a pure function of the trace, drawn before any
    # engine state exists — sizing cannot perturb the workload stream)
    trace = make_trace(workload, n, rps, seed=seed, scale=length_scale,
                       deadline_slack=deadline_slack)
    share = prefix_share_factor(trace) if serve.prefix_sharing else 1.0
    plan = None
    if size_by_profiler:
        # monolithic logit reservations and dense caches buy fewer KV slots
        # — the paper's capacity coupling. The mesh_shape rides along, so an
        # N-device mesh is sized by its per-device arithmetic. Sharing and
        # int8 KV lift the plan's capacity (docs/memory.md); the engine's
        # allocation clamps to PHYSICAL capacity (size_slots).
        budget = hbm_bytes if hbm_bytes is not None \
            else device_memory_bytes()
        plan = plan_memory(cfg, serve, budget, share_factor=share)
        serve = size_slots(cfg, serve, budget, share_factor=share)
    faults = FaultPlan.seeded(fault_seed) if fault_seed is not None else None
    stream_cb = None
    if stream:
        # per-commit streaming: one event per request per iteration, fired
        # at the deferred sync — the first host-side moment the token
        # values exist. The launcher prints a compact line per event (the
        # JSON still carries the aggregate streamed_events count).
        def stream_cb(ev):
            if not quiet:
                tok = ev["tokens"][:4]
                print(f"  stream rid={ev['rid']} block={ev['block_idx']} "
                      f"+{ev['n_committed']} tok "
                      f"{'FIN ' if ev['finished'] else ''}{tok}...")
    eng = Engine(cfg, serve, seed=seed, clock=clock, faults=faults,
                 stream_cb=stream_cb)
    if mesh_shape and not quiet:
        print(f"mesh: {eng.mesh_devices} devices "
              f"({'x'.join(map(str, serve.mesh_shape))})")
    warmup_s = eng.warmup()      # AOT compile outside the measured window
    prompts = trace_prompts(trace, cfg.vocab_size, seed=seed)
    reqs = []
    for i, (t, p) in enumerate(zip(trace, prompts)):
        gl = min(t.gen_len, max_seq_len - len(p) - block_size)
        gl = max(block_size, gl)
        pl = min(len(p), max_seq_len - gl - block_size)
        reqs.append(eng.submit(p[:pl], gen_len=gl, arrival=t.arrival, rid=i,
                               deadline=t.deadline))
    t_run0 = time.perf_counter()
    stats = eng.run(time_scale=time_scale, quiet=quiet)
    host_elapsed_s = time.perf_counter() - t_run0
    # latency percentiles over FINISHED requests only — shed/rejected
    # requests have no completion time and must not skew (or zero) the tail
    fin = [r for r in reqs if r.state == State.FINISHED]
    lats = np.array([r.latency for r in fin]) if fin else np.zeros(1)
    # time to the first committed tokens reaching the host (dispatch time
    # under the modeled clock), over every request that committed any
    firsts = [r.t_first_commit - r.arrival for r in reqs
              if r.t_first_commit >= 0] or [0.0]
    # goodput: tokens of requests that finished BEFORE their deadline —
    # shedding (or blowing deadlines) can't masquerade as throughput
    good_tokens = sum(r.gen_len for r in fin if r.met_deadline)
    out = dict(
        system=system, workload=workload, rps=rps, n=n,
        throughput_tok_s=stats.throughput,
        goodput_tok_s=good_tokens / max(stats.wall_time, 1e-9),
        committed_tokens=stats.committed_tokens,
        wall_time=stats.wall_time,
        n_submitted=stats.submitted,
        n_finished=stats.finished,
        n_shed=stats.shed,
        n_rejected=stats.rejected,
        shed_deadline=stats.shed_deadline,
        shed_queue=stats.shed_queue,
        rejected_oversized=stats.rejected_oversized,
        rejected_queue_full=stats.rejected_queue_full,
        n_preemptions=stats.preemptions,
        recomputed_tokens=stats.recomputed_tokens,
        dispatch_retries=stats.dispatch_retries,
        alloc_fault_iters=stats.alloc_fault_iters,
        avg_latency=float(lats.mean()),
        p50_latency=float(np.percentile(lats, 50)),
        p99_latency=float(np.percentile(lats, 99)),
        p50_first_commit=float(np.percentile(firsts, 50)),
        p90_first_commit=float(np.percentile(firsts, 90)),
        latency_std=float(lats.std()),
        tail_span=float(lats.max() - lats.min()),
        refresh_steps=stats.refresh_steps,
        reuse_steps=stats.reuse_steps,
        deferred=stats.deferred_steps,
        peak_query_tokens=stats.peak_query_tokens,
        refresh_tokens_real=stats.refresh_tokens_real,
        refresh_tokens_exec=stats.refresh_tokens_exec,
        refresh_waste=stats.refresh_waste,
        reuse_tokens_real=stats.reuse_tokens_real,
        reuse_tokens_exec=stats.reuse_tokens_exec,
        reuse_waste=stats.reuse_waste,
        logit_tokens_real=stats.logit_tokens_real,
        logit_tokens_exec=stats.logit_tokens_exec,
        logit_waste=stats.logit_waste,
        packed_refresh_calls=stats.packed_refresh_calls,
        padded_refresh_calls=stats.padded_refresh_calls,
        packed_reuse_calls=stats.packed_reuse_calls,
        padded_reuse_calls=stats.padded_reuse_calls,
        reuse_inplace_calls=stats.reuse_inplace_calls,
        warmup_s=warmup_s,
        # retrace sentinel (docs/analysis.md): per-entry compile counts and
        # the post-warmup budget — 0 on the padded path, lazily-compiled
        # sub-buckets only on the packed path
        compile_counts=dict(stats.compile_counts),
        compiles_warmup=stats.compiles_warmup,
        compiles_post_warmup=stats.compiles_post_warmup,
        # pipelined-loop accounting (docs/engine.md): the modeled clock
        # prices device work (throughput_tok_s above); these price the HOST
        # side — per-stage gaps and how much of them the dispatch-ahead
        # loop hid. wall_clock_s is true host elapsed around Engine.run, so
        # wall_tok_s is the end-to-end rate this process actually achieved.
        clock=clock,
        pipeline=serve.pipeline,
        iterations=stats.iterations,
        wall_clock_s=host_elapsed_s,
        wall_tok_s=stats.committed_tokens / max(host_elapsed_s, 1e-9),
        host_plan_s=stats.host_plan_s,
        host_fill_s=stats.host_fill_s,
        sync_wait_s=stats.sync_wait_s,
        overlapped_host_s=stats.overlapped_host_s,
        overlap_frac=stats.overlap_frac,
        dispatched_ahead=stats.dispatched_ahead,
        streamed_events=stats.streamed_events,
        host_profile=int(os.environ.get("REPRO_HOST_PROFILE", "0") or "0"),
        max_slots=serve.max_slots,
        # memory-footprint multipliers (docs/memory.md): what ran, what the
        # ledger measured, and what the profiler planned from the trace
        prefix_sharing=serve.prefix_sharing,
        kv_quant=serve.kv_quant,
        share_factor=share,
        shared_hits=stats.shared_hits,
        shared_cow_promotes=stats.shared_cow_promotes,
        phys_slots_peak=stats.phys_slots_peak,
        plan_slots_logical=plan.max_slots if plan else None,
        plan_slots_phys=plan.phys_slots if plan else None,
        plan_slot_bytes=plan.slot_bytes if plan else None,
        mesh_shape=list(serve.mesh_shape) if serve.mesh_shape else None,
        mesh_devices=eng.mesh_devices,
        # True when the Pallas hot paths served this run (under a mesh they
        # dispatched per-shard — the engine validates at construction and
        # never silently falls back to the jnp paths)
        kernels_active=eng.kernels_active,
        # per-device executed tokens under the engine's ACTUAL work split:
        # the sharded TP fraction (1.0 when no dim divides — an indivisible
        # mesh must not deflate this metric) × the data-axis replica streams
        refresh_tokens_exec_per_device=stats.refresh_tokens_exec
        / eng.work_split,
        reuse_tokens_exec_per_device=stats.reuse_tokens_exec
        / eng.work_split,
        logit_tokens_exec_per_device=stats.logit_tokens_exec
        / eng.work_split,
    )
    return out


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llada-8b")
    ap.add_argument("--system", default="dllm-serve",
                    choices=["dllm-serve", "sparse-dllm", "fast-dllm",
                             "dllm-cache"])
    ap.add_argument("--workload", default="livebench")
    ap.add_argument("--rps", type=float, default=1.0)
    ap.add_argument("--n", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="use the full config (CPU-hostile; default reduced)")
    ap.add_argument("--mesh", default="env",
                    help="serving mesh: 'd,m' shape, 'none', or 'env' "
                         "(default: honor REPRO_MESH)")
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="bounded waiting queue (0 = unbounded)")
    ap.add_argument("--queue-policy", default="reject",
                    choices=["reject", "evict"],
                    help="full-queue backpressure: reject new vs evict oldest")
    ap.add_argument("--deadline", type=float, default=float("inf"),
                    help="per-request deadline slack in trace seconds "
                         "(inf = none); expired waiters are shed")
    ap.add_argument("--preempt-starvation", type=float, default=0.0,
                    help="starvation threshold (s) that triggers "
                         "preempt-and-requeue (0 = disabled)")
    ap.add_argument("--faults", type=int, default=None, metavar="SEED",
                    help="run under a seeded FaultPlan (chaos mode)")
    ap.add_argument("--kernels", action="store_true",
                    help="force the Pallas hot paths (use_flash_kernel + "
                         "logit_mode=fused) on top of the system profile; "
                         "shard_mapped per model shard under a mesh")
    ap.add_argument("--sharing", action="store_true",
                    help="content-addressed prefix sharing in the KV pool "
                         "(COW on divergence; token output bit-identical "
                         "to sharing off — docs/memory.md)")
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="KV slot storage dtype (int8: per-slot abs-max "
                         "scales, dequantized at the Reuse KV load)")
    ap.add_argument("--clock", default="modeled",
                    choices=["modeled", "wall"],
                    help="iteration clock: 'modeled' prices device work on "
                         "the paper's cost model (deterministic, the "
                         "default); 'wall' timestamps with the host clock "
                         "so throughput reflects this machine")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="run the synchronous oracle loop (sync every "
                         "iteration) instead of the dispatch-ahead "
                         "pipelined loop; token output is bit-identical")
    ap.add_argument("--stream", action="store_true",
                    help="print a per-request commit event at each "
                         "iteration's deferred sync (first host-side "
                         "sight of the token values)")
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="slot-sizing budget per device in GiB (default: "
                         "the device's reported memory limit; required on "
                         "hosts that report none, such as the CPU)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.mesh == "env":
        mesh_shape = parse_mesh_env()
    elif args.mesh in ("none", ""):
        mesh_shape = None
    else:
        mesh_shape = tuple(int(x) for x in args.mesh.split(","))
    res = run_serve(args.arch, args.system, args.workload, args.rps, args.n,
                    use_reduced=not args.full, seed=args.seed, quiet=False,
                    mesh_shape=mesh_shape, queue_cap=args.queue_cap,
                    queue_policy=args.queue_policy,
                    deadline_slack=args.deadline,
                    preempt_starvation_s=args.preempt_starvation,
                    fault_seed=args.faults,
                    kernels=True if args.kernels else None,
                    prefix_sharing=args.sharing, kv_quant=args.kv_quant,
                    clock=args.clock, pipeline=not args.no_pipeline,
                    stream=args.stream,
                    hbm_bytes=None if args.hbm_gb is None
                    else int(args.hbm_gb * (1 << 30)))
    print(json.dumps(res, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2)


if __name__ == "__main__":
    main()
