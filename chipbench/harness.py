"""Serve one cell through the engine and measure it from the client's side.

One process, one ``Engine`` on the normal path: the ``dllm-serve`` profile
with the Pallas kernels and the fused logit stage, the pipelined loop on the
wall clock, slots sized from the device's memory, the benchmark's weights.
The cell's requests are submitted with their due times and served with
``Engine.run``. The engine's streaming callback fires where a step's
committed tokens reach the host; there the harness stamps, on its own
clock, each block whose last masked position has landed. When the window
closes the callback raises :class:`WindowClosed`, which ends ``run``.

After the window: the device's peak memory is read, the engine is released,
and the plain reference (``reference.py``) replays a sample of the finished
requests to decide ``correct``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from chipbench import config as C
from chipbench import measure as M
from chipbench import spec as SP
from chipbench import traffic as TR


class WindowClosed(Exception):
    """Raised from the streaming callback once the window has closed."""


@dataclass
class Event:
    t: float                # harness clock, when the values reached the host
    block: int
    step: int               # denoising step within the block
    n: int                  # positions committed at this step
    tokens: np.ndarray      # the block after this step


class Recorder:
    """The engine's streaming callback: stamps every commit event."""

    def __init__(self, mask_id: int):
        self.mask_id = mask_id
        self.on = False
        self.t_end = math.inf
        self.events: Dict[int, List[Event]] = defaultdict(list)
        self.running_peak = 0
        self.scheduler = None       # sampled for slot occupancy, if present

    def __call__(self, ev: dict) -> None:
        t = time.perf_counter()
        if not self.on:
            return
        if t > self.t_end:
            raise WindowClosed
        evs = self.events[ev["rid"]]
        step = sum(1 for e in evs if e.block == ev["block_idx"])
        evs.append(Event(t, ev["block_idx"], step, int(ev["n_committed"]),
                         np.asarray(ev["tokens"])))
        running = getattr(self.scheduler, "running", None)
        if running is not None:
            self.running_peak = max(self.running_peak, len(running))


@dataclass
class Run:
    """Everything a metric reader may read about one run."""
    cell: SP.Cell
    dims: dict
    serve: object               # the program's ServeConfig, as run
    seconds: float
    t0: float                   # window opens (first request due)
    t_end: float                # window closes
    setup: Dict[str, float]     # set-up parts, seconds
    reqs: List[M.ReqRecord]
    events: Dict[int, List[Event]]
    iters: List[dict]           # engine iter_log rows dispatched in window
    admitted: Dict[int, float]  # rid -> admission, engine clock
    arrival: Dict[int, float]   # rid -> due, engine clock
    slots_allocated: int
    running_peak: int
    compiles_in_window: int
    retain: int                 # the engine's retained length per slot
    late_s: float = 0.0         # window open to the engine's loop starting
    compiled_in_window: List[str] = field(default_factory=list)
    trace: Optional[object] = None      # tracefile.Summary with --trace 1
    peaks: Optional[dict] = None        # the device's row of peaks.json

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


def serve_config(cfg, cell: SP.Cell, budget: int):
    """The dllm-serve profile with kernels, fused logits and the wall clock,
    at the cell's settings and the mix's denoising schedule, slots sized
    for ``budget`` bytes of device memory."""
    from repro.configs.base import ServeConfig
    from repro.core.baselines import size_slots, system_profiles
    s, tr = cell.cell["serve"], cell.traffic
    base = ServeConfig(
        max_num_batched_tokens=s["max_num_batched_tokens"],
        max_num_logits=s["max_num_logits"], block_size=tr["block_size"],
        steps_per_block=tr["steps_per_block"], max_seq_len=s["max_seq_len"],
        max_slots=s["max_slots"],
        max_refresh_per_iter=s["max_refresh_per_iter"],
        token_bucket=s["token_bucket"], vocab_tile=s["vocab_tile"],
        clock="wall")
    serve = dataclasses.replace(
        system_profiles(base)["dllm-serve"], use_flash_kernel=True,
        logit_mode="fused", refresh_interval=tr["refresh_interval"])
    lo, hi = TR.length_range(cell.traffic)
    if hi > serve.max_seq_len or hi > serve.max_num_batched_tokens:
        raise ValueError(f"{cell.name}: a request of {hi} tokens would be "
                         f"rejected (max_seq_len {serve.max_seq_len}, "
                         f"budget {serve.max_num_batched_tokens})")
    return size_slots(cfg, serve, budget)


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def warm_refresh_shapes(eng, serve, traffic: dict) -> int:
    """Compile every packed-Refresh stream shape the mix can produce: each
    token bucket a set of 1 to ``refresh_slots`` requests of the mix's
    lengths can fill, through the engine's public ``refresh_outputs``."""
    import jax
    from repro.core.request import Request
    lo, hi = TR.length_range(traffic)
    g = traffic["gen_len"]
    tb, budget = serve.token_bucket, serve.max_num_batched_tokens
    done = set()
    for n in range(1, serve.refresh_slots + 1):
        rp = _pow2(n)
        top = min(n * hi, budget)
        for T in range(tb, -(-top // tb) * tb + 1, tb):
            total = min(max(T, n * lo), top)
            if not (T - tb < total <= T) or (T, rp) in done:
                continue
            done.add((T, rp))
            lens = [total // n + (1 if i < total % n else 0)
                    for i in range(n)]
            reqs = [Request(rid=-1 - i, prompt=np.ones(L - g, np.int32),
                            gen_len=g, arrival=0.0, cfg=serve,
                            mask_id=eng.mask_id)
                    for i, L in enumerate(lens)]
            jax.block_until_ready(eng.refresh_outputs(reqs).block_hidden)
    return len(done)


def warm_traffic(eng, serve, traffic: dict) -> None:
    """Drive the engine through bursts of the mix's shortest requests so the
    Reuse and logit shapes of every residency the cell can reach compile
    before the window: one request at a time up to a Reuse bucket's width,
    then every slot at once."""
    lo, _ = TR.length_range(traffic)
    sb = traffic["block_size"]
    g = traffic["gen_len"]
    p = np.ones(lo - g, np.int32)
    for n in range(1, max(1, serve.token_bucket // sb) + 1):
        for _ in range(n):
            eng.submit(p, gen_len=sb, arrival=0.0)
        eng.run()
    for _ in range(serve.max_slots):
        eng.submit(p, gen_len=2 * sb, arrival=0.0)
    eng.run()


def warm_eager_ops(eng, serve) -> int:
    """Compile the small eager operations the engine runs between its stage
    programs, for every (Refresh, Reuse) residency the cell can reach: the
    slices of each stage's block rows, their concatenation into the logit
    stage's stream and its padding to the token bucket. Each compiles once
    per shape; unwarmed, they would compile inside the window. The engine
    has no public entry that reaches every residency, so these shapes
    mirror its glue; should the glue change, its compiles land inside the
    window, where ``check.check_run`` judges the run not correct."""
    import jax
    import jax.numpy as jnp
    from repro.core.budgeting import token_bucket_round
    sb, D = serve.block_size, eng.cfg.d_model
    dt = jnp.dtype(eng.cfg.dtype)
    cap = serve.refresh_slots
    rb = max(1, serve.token_bucket // sb)
    r_cap = max(1, min(serve.max_slots,
                       serve.max_num_batched_tokens // sb))
    rows_r = {n: jnp.zeros((_pow2(n), sb, D), dt)[:n].reshape(-1, D)
              for n in range(1, cap + 1)}
    rows_u = {n: jnp.zeros((token_bucket_round(n, rb) * sb, D), dt)
              .reshape(token_bucket_round(n, rb), sb, -1)[:n].reshape(-1, D)
              for n in range(1, r_cap + 1)}
    k = 0
    for nr in range(cap + 1):
        for nu in range(r_cap + 1):
            parts = [p for p in (rows_r.get(nr), rows_u.get(nu))
                     if p is not None]
            if not parts:
                continue
            h = jnp.concatenate(parts, axis=0)
            n = (nr + nu) * sb
            b = token_bucket_round(n, serve.token_bucket)
            if b != n:
                h = jnp.pad(h, ((0, b - n), (0, 0)))
            k += 1
    jax.block_until_ready(h)
    return k


class CompileCounter:
    """Counts XLA compiles, loads from the persistent cache included, from
    jax's own monitoring events while ``on``; and the persistent cache's
    hits and misses (a miss is a program compiled and written)."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        self.hits = self.misses = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._ev)
        jax.monitoring.register_event_listener(self._cache_ev)

    def _ev(self, event: str, duration: float, **kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.names.append(f"{kw.get('fun_name', '?')} "
                              f"{duration:.3f}s")

    def _cache_ev(self, event: str, **kw) -> None:
        if not self.on:
            return
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def reset(self) -> None:
        self.n = self.hits = self.misses = 0
        self.names = []


def _annotate(obj, attr: str, label: str) -> None:
    import jax
    f = getattr(obj, attr, None)
    if f is None:
        return

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(label):
            return f(*a, **k)
    setattr(obj, attr, wrapped)


class _AnnotatedTime:
    """Stands in for the engine module's ``time``: its ``sleep`` (the wait
    for the next arrival) is a named host span."""

    def __init__(self, mod):
        self._mod = mod

    def __getattr__(self, name):
        return getattr(self._mod, name)

    def sleep(self, s):
        import jax
        with jax.profiler.TraceAnnotation("chipbench.arrival_wait"):
            self._mod.sleep(s)


@dataclass
class Built:
    """One cell's engine, warmed, with the benchmark's weights."""
    cell: SP.Cell
    cfg: object
    dims: dict
    serve: object
    params: dict
    eng: object
    rec: Recorder
    compiles: CompileCounter
    times: Dict[str, float]

    def ref_serve(self) -> dict:
        """What the reference and the schedule check need of the serving
        algorithm: the denoising schedule from the mix's data, the
        retained length, pooling window and mask id from the engine."""
        tr = self.cell.traffic
        return dict(block_size=tr["block_size"],
                    steps_per_block=tr["steps_per_block"],
                    refresh_interval=tr["refresh_interval"],
                    retain=self.eng.ctx.retain,
                    kernel_size=self.serve.kernel_size,
                    mask_id=self.eng.mask_id)


def build(cell: SP.Cell, seed: int, *, hbm_bytes: Optional[int] = None,
          cfg_overrides: Optional[dict] = None) -> Built:
    """Weights from the seed, the engine, and every shape the cell uses
    compiled: the engine's own warmup, each Refresh stream shape of the mix,
    warm-up bursts of the mix's requests and the eager operations between
    the stages."""
    import jax
    from repro.core.engine import Engine
    from chipbench import weights as W

    t_imp = time.perf_counter()
    compiles = CompileCounter()
    compiles.on = True
    dev = jax.devices()[0]
    arch = cell.arch
    cfg = C.model_config(cell.config, arch, **(cfg_overrides or {}))
    dims = C.dims_of(cfg, cell.config, arch)
    if not cfg_overrides and dims != C.dims(cell.config, arch):
        raise ValueError(f"{cfg.name}: the program's ModelConfig differs "
                         f"from the configuration file")
    budget = hbm_bytes
    if budget is None:
        budget = int((dev.memory_stats() or {})["bytes_limit"])
    serve = serve_config(cfg, cell, budget)
    params = W.make_params(dims, seed)
    jax.block_until_ready(params)
    t_par = time.perf_counter()
    rec = Recorder(mask_id=cfg.vocab_size - 1)
    eng = Engine(cfg, serve, params=params, seed=seed, stream_cb=rec)
    if eng.mask_id != rec.mask_id:
        raise ValueError(f"the engine's mask id {eng.mask_id} is not the "
                         f"last id {rec.mask_id}")
    rec.scheduler = eng.scheduler
    eng.warmup()
    t_wu = time.perf_counter()
    n_refresh = warm_refresh_shapes(eng, serve, cell.traffic)
    warm_traffic(eng, serve, cell.traffic)
    n_eager = warm_eager_ops(eng, serve)
    t_wt = time.perf_counter()
    compiles.on = False
    times = dict(t_imp=t_imp, import_s=t_imp, params_s=t_par - t_imp,
                 warmup_s=t_wu - t_par, warm_traffic_s=t_wt - t_wu,
                 t_ready=t_wt, refresh_shapes=n_refresh,
                 eager_shapes=n_eager, compiles=compiles.n,
                 cache_hits=compiles.hits, cache_misses=compiles.misses)
    compiles.reset()
    return Built(cell, cfg, dims, serve, params, eng, rec, compiles, times)


def window(b: Built, reqs: List[TR.Req], seconds: float, *,
           trace: bool = False, trace_dir: Optional[Path] = None,
           drain: bool = False) -> Run:
    """Submit ``reqs`` with their due times, open the window and serve. The
    window closes ``seconds`` after it opens; with ``drain`` the engine then
    serves on, unrecorded, until every request has ended (the tools use
    this to reuse one engine)."""
    import jax
    import repro.core.engine as engine_mod
    eng, rec = b.eng, b.rec
    rec.events = defaultdict(list)
    rec.running_peak = 0
    b.compiles.reset()
    handles = [eng.submit(r.prompt, gen_len=r.gen_len, arrival=r.due)
               for r in reqs]
    it0 = len(eng.stats.iter_log)
    if trace:
        for attr, label in (("_begin_iteration", "chipbench.plan"),
                            ("_dispatch_iteration", "chipbench.dispatch"),
                            ("_sync_iteration", "chipbench.sync")):
            _annotate(eng, attr, label)
        engine_mod.time = _AnnotatedTime(time)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # python calls untraced: host
        opts.host_tracer_level = 1        # spans only, at little cost
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t0 = time.perf_counter()
    rec.t_end = math.inf if drain else t0 + seconds
    rec.on = b.compiles.on = True
    if trace:
        with jax.profiler.TraceAnnotation("chipbench.window_open"):
            pass
    t_call = time.perf_counter()
    try:
        eng.run()
    except WindowClosed:
        pass
    if drain:
        rec.t_end = t0 + seconds
    t_left = rec.t_end - time.perf_counter()
    if t_left > 0:            # every request finished early: wait it out
        time.sleep(t_left)
    rec.on = b.compiles.on = False
    t_end = rec.t_end
    if trace:
        with jax.profiler.TraceAnnotation("chipbench.window_close"):
            pass
        jax.profiler.stop_trace()
        engine_mod.time = time
        for attr in ("_begin_iteration", "_dispatch_iteration",
                     "_sync_iteration"):
            eng.__dict__.pop(attr, None)
    iters = list(eng.stats.iter_log)[it0:]
    records = []
    for r, h in zip(reqs, handles):
        evs = rec.events.get(h.rid, [])
        done = [e.t for e in evs if not (e.tokens == eng.mask_id).any()]
        failed = h.outcome is not None and h.outcome.value != "finished"
        records.append(M.ReqRecord(h.rid, t0 + r.due, h.n_blocks,
                                   len(r.prompt), failed=failed,
                                   blocks=done))
    tm = b.times
    return Run(
        compiles_in_window=b.compiles.n,
        compiled_in_window=list(b.compiles.names),
        cell=b.cell, dims=b.dims, serve=b.serve, seconds=seconds, t0=t0,
        t_end=t_end,
        setup=dict(import_s=tm["t_imp"] - tm["t_proc0"],
                   params_s=tm["params_s"], warmup_s=tm["warmup_s"],
                   warm_traffic_s=tm["warm_traffic_s"],
                   submit_s=t0 - tm["t_ready"],
                   setup_s=t0 - tm["t_proc0"],
                   refresh_shapes=tm["refresh_shapes"],
                   eager_shapes=tm["eager_shapes"],
                   compiles=tm["compiles"], cache_hits=tm["cache_hits"],
                   cache_misses=tm["cache_misses"]),
        late_s=t_call - t0,
        reqs=records, events=dict(rec.events), iters=iters,
        admitted={h.rid: h.t_admitted for h in handles if h.t_admitted >= 0},
        arrival={h.rid: h.arrival for h in handles},
        slots_allocated=b.serve.max_slots, running_peak=rec.running_peak,
        retain=b.eng.ctx.retain)


def serve_and_measure(cell: SP.Cell, seed: int, seconds: float, trace: bool,
                      *, t_proc0: float, hbm_bytes: Optional[int] = None,
                      cfg_overrides: Optional[dict] = None,
                      trace_dir: Optional[Path] = None) -> dict:
    """One run of ``cell``: build, one window, the device's peak memory,
    the engine released, then the check. Returns ``dict(run, device,
    check)``."""
    import jax
    from chipbench import check as CK
    b = build(cell, seed, hbm_bytes=hbm_bytes, cfg_overrides=cfg_overrides)
    b.times["t_proc0"] = t_proc0
    reqs = TR.generate(cell.traffic, cell.cell, seconds, seed,
                       b.cfg.vocab_size, b.eng.mask_id)
    run = window(b, reqs, seconds, trace=trace, trace_dir=trace_dir)
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()),
                  memory_peak_bytes=int(mem.get("peak_bytes_in_use", 0)),
                  bytes_limit=int(mem.get("bytes_limit", 0)))
    prompts = {rec.rid: r.prompt for r, rec in zip(reqs, run.reqs)}
    ref_serve = b.ref_serve()
    params = b.params
    b.eng = b.params = None
    del b
    gc.collect()
    check = CK.check_run(run, prompts, params, ref_serve, seed,
                         cell.cell["check"])
    return dict(run=run, device=device, check=check)
