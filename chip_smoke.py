"""Chip smoke: the packed serving path, once, on a TPU at published widths.

    python chip_smoke.py               # one chip: llada-8b-1chip (16 layers)
    python chip_smoke.py --four-chips  # four chips: the tensor-parallel path

One process holds the chip and starts no children. A few requests, all
arriving at t=0, are served through ``Engine`` (the scheduler, the slot
pool, the packed Refresh/Reuse stages and the fused logit stage, Pallas
kernels compiled by Mosaic) with random weights from ``--seed``, on the wall
clock. Before serving, the first iteration's logits of the kernel path are
checked against the padded jnp path (the repository's correctness oracle)
on the same chip and the same inputs.

``--four-chips`` runs only what exists across chips: the full 32-layer
llada-8b on a (1, 4) mesh, which one chip cannot hold, serving the same
requests; and, at the 16-layer cut, the one-chip engine against the (1, 4)
engine on the same trace, comparing first-iteration logits and caches.

Earlier lines report what ran; the last line is one JSON object,
``{"ok": true, "device": {...}}``. Any failed check exits non-zero. Where
JAX finds no TPU, the script fails before serving anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# First-iteration agreement, kernel path vs padded jnp path, both bf16 on
# the same chip: relative L2 error of the logits over every block row. In
# float32 the two paths agree to ~1e-6 (the same mathematics); in bf16 they
# round at different points (f32 online-softmax tiles against a bf16
# softmax, other reduction orders). Each layer then perturbs the residual
# stream by about two bf16 roundoffs (2u, u = 2^-8), independently across
# layers, so the logits drift by ~2u·sqrt(L): measured 1.1e-2, 1.5e-2 and
# 2.2e-2 at 2, 4 and 8 layers of the published widths on the CPU. The bound
# is twice that, 4u·sqrt(L) (6.25e-2 at 16 layers); a format coarser than
# bf16 (fp8 e4m3, u = 2^-4) would miss it eightfold.
def logit_tol(n_layers: int) -> float:
    return 4 * 2.0 ** -8 * n_layers ** 0.5


# The fused logit kernel against jnp on the same hidden rows: the softmax
# probability of the argmax agrees to this absolute error, and the kernel's
# token has a logit within LOGIT_TIE_TOL of the row maximum (random weights
# put near-ties in many rows, so equal ids are not demanded).
CONF_ABS_TOL = 2e-3
LOGIT_TIE_TOL = 1e-2
# One chip against the (1, 4) mesh at the 16-layer cut: a bf16 all-reduce
# legally reorders the partial sums of every row-parallel matmul, so logits
# and the retained K/V differ by rounding, bounded by logit_tol. Head-score
# selection flips on near-ties (random weights tie often: 0.91 of retained
# positions agreed on the chip), so K/V are compared where both engines
# kept the same position. A misplaced shard shows in that K/V error; the
# share of agreeing positions only has to keep the comparison from being
# vacuous.
MESH_POS_AGREE_MIN = 0.5

BLOCK = 32                 # LLaDA's block length
GEN_BLOCKS = 2             # each request decodes at least two blocks


def chip_serve_config(cfg, *, n_requests: int, mesh_shape=None,
                      hbm_bytes=None):
    """The dllm-serve profile with the Pallas kernels and the wall clock,
    slots sized from the device's memory for ``cfg`` in its own dtype."""
    from repro.configs.base import ServeConfig
    from repro.core.baselines import size_slots, system_profiles
    from repro.launch.serve import device_memory_bytes
    base = ServeConfig(
        max_num_batched_tokens=1024, max_num_logits=256, block_size=BLOCK,
        steps_per_block=8, max_seq_len=256, max_slots=n_requests,
        max_refresh_per_iter=4, mesh_shape=mesh_shape, clock="wall")
    serve = dataclasses.replace(system_profiles(base)["dllm-serve"],
                                use_flash_kernel=True, logit_mode="fused")
    budget = hbm_bytes if hbm_bytes is not None else device_memory_bytes()
    return size_slots(cfg, serve, budget), budget


def _rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def first_iteration(eng, reqs, *, oracle: bool) -> dict:
    """The first iteration of ``reqs`` (a Refresh of block 0 each) through
    the engine's kernel path; with ``oracle`` also through the padded jnp
    path on the same inputs, and the fused logit kernel against jnp on the
    kernel path's hidden rows. Returns host arrays and the errors."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import jax_compat as JC
    from repro.models import backbone as BB
    from repro.models import lm_head as LM

    cfg, serve, n = eng.cfg, eng.serve, len(reqs)
    logits = JC.jit(lambda e, h: LM.logits_monolithic(e, cfg, h))
    out = eng.refresh_outputs(reqs)
    h_k = out.block_hidden[:n].reshape(n * serve.block_size, cfg.d_model)
    logits_k = np.asarray(logits(eng.params["embed"], h_k))
    res = dict(logits=logits_k,
               cache=jax.tree.map(lambda x: np.asarray(x[:, :n]), out.cache),
               logits_finite=bool(np.isfinite(logits_k).all()))
    if not oracle:
        return res

    S = serve.max_seq_len
    tokens = np.zeros((n, S), np.int32)
    valid = np.zeros((n, S), bool)
    bstart = np.zeros((n,), np.int32)
    for j, r in enumerate(reqs):
        tokens[j] = r.tokens
        valid[j, : r.total_len] = True
        bstart[j] = r.block_start
    ctx = dataclasses.replace(eng.ctx, use_flash_kernel=False,
                              use_flash_refresh=False)
    padded = JC.jit(lambda p, t, b, v: BB.serve_refresh(
        p, cfg, t, b, ctx, token_valid=v).block_hidden)
    h_r = padded(eng.params, jnp.asarray(tokens), jnp.asarray(bstart),
                 jnp.asarray(valid)).reshape(n * serve.block_size,
                                             cfg.d_model)
    logits_r = np.asarray(logits(eng.params["embed"], h_r))
    res["logit_rel_l2"] = _rel_l2(logits_k, logits_r)
    res["logit_max_abs"] = float(np.abs(logits_k - logits_r).max())

    decode = JC.jit(lambda e, h: LM.decode_tokens(
        e, cfg, h, max_num_logits=serve.max_num_logits, mode="fused",
        vocab_tile=serve.vocab_tile))
    with eng._mesh_ctx():
        ids, conf = jax.device_get(decode(eng.params["embed"], h_k))
    z = logits_k
    zmax = z.max(axis=1)
    p_max = 1.0 / np.exp(z - zmax[:, None]).sum(axis=1)
    res["fused_conf_max_abs"] = float(np.abs(conf - p_max).max())
    res["fused_id_gap_max"] = float(
        (zmax - z[np.arange(len(ids)), ids]).max())
    return res


def serve_and_compare(cfg, *, n_requests: int = 8, seed: int = 0,
                      mesh_shape=None, hbm_bytes=None, warmup: bool = True,
                      oracle: bool = True) -> dict:
    """Serve ``n_requests`` seeded requests (arrivals at t=0, each
    ``GEN_BLOCKS`` blocks long) through a fresh kernel-path ``Engine`` and
    check the first iteration (see :func:`first_iteration`). The engine is
    released before returning; everything returned lives on the host."""
    import jax
    import numpy as np

    from repro.core.engine import Engine
    from repro.core.request import State

    serve, budget = chip_serve_config(cfg, n_requests=n_requests,
                                      mesh_shape=mesh_shape,
                                      hbm_bytes=hbm_bytes)
    eng = Engine(cfg, serve, seed=seed)
    rng = np.random.default_rng(seed)
    gen = GEN_BLOCKS * BLOCK
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size - 1,
                                    int(rng.integers(64, 129))),
                       gen_len=gen, arrival=0.0, rid=i)
            for i in range(n_requests)]
    first = first_iteration(eng, reqs, oracle=oracle)
    warmup_s = eng.warmup() if warmup else None
    stats = eng.run()
    outs = [r.output_tokens() for r in reqs]
    ids_ok = all(len(o) == gen and o.min() >= 0 and o.max() < cfg.vocab_size
                 and not (o == eng.mask_id).any() for o in outs)
    devices = eng.mesh.devices.flat if eng.mesh is not None \
        else [jax.devices()[0]]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    res = dict(
        arch=cfg.name, n_layers=cfg.n_layers,
        mesh_shape=list(mesh_shape) if mesh_shape else None,
        budget_bytes=budget, max_slots=serve.max_slots,
        kernels_active=eng.kernels_active,
        packed_refresh_calls=stats.packed_refresh_calls,
        packed_reuse_calls=stats.packed_reuse_calls,
        padded_calls=stats.padded_refresh_calls + stats.padded_reuse_calls,
        submitted=stats.submitted, finished=stats.finished,
        shed=stats.shed, rejected=stats.rejected,
        conserved=stats.conserved(),
        all_finished=all(r.state == State.FINISHED for r in reqs),
        committed_tokens=stats.committed_tokens, iterations=stats.iterations,
        warmup_s=warmup_s, compiles_warmup=stats.compiles_warmup,
        compiles_post_warmup=stats.compiles_post_warmup,
        peak_bytes_in_use=max(peaks) if None not in peaks else None,
        outputs_valid=ids_ok, outputs=outs, first=first)
    del eng, reqs, stats
    gc.collect()
    return res


def serve_failures(res: dict, *, oracle: bool) -> list:
    """Every check a served run must pass; empty when all hold."""
    bad = []

    def need(ok, what):
        if not ok:
            bad.append(what)

    need(res["kernels_active"], "kernels_active is false")
    need(res["packed_refresh_calls"] > 0, "no packed Refresh ran")
    need(res["packed_reuse_calls"] > 0, "no packed Reuse ran")
    need(res["padded_calls"] == 0, "a padded stage ran")
    need(res["all_finished"] and res["finished"] == res["submitted"],
         "not every request finished")
    need(res["conserved"], "submitted != finished + shed + rejected")
    need(res["first"]["logits_finite"], "non-finite first-iteration logits")
    need(res["outputs_valid"], "an output holds a mask or out-of-range id")
    if oracle:
        f = res["first"]
        tol = logit_tol(res["n_layers"])
        need(f["logit_rel_l2"] <= tol,
             f"logit rel L2 {f['logit_rel_l2']} > {tol}")
        need(f["fused_conf_max_abs"] <= CONF_ABS_TOL,
             f"fused conf error {f['fused_conf_max_abs']} > {CONF_ABS_TOL}")
        need(f["fused_id_gap_max"] <= LOGIT_TIE_TOL,
             f"fused id logit gap {f['fused_id_gap_max']} > "
             f"{LOGIT_TIE_TOL}")
    return bad


def compare_engines(a: dict, b: dict) -> dict:
    """First-iteration logits and retained caches of two served runs of
    the same trace, plus the share of equal output ids."""
    import numpy as np
    ca, cb = a["first"]["cache"], b["first"]["cache"]
    same = (ca.pos == cb.pos) & ca.valid & cb.valid
    both = ca.valid | cb.valid
    outs = [np.mean(x == y) for x, y in zip(a["outputs"], b["outputs"])]
    return dict(
        logit_rel_l2=_rel_l2(a["first"]["logits"], b["first"]["logits"]),
        pos_agree=float(same.sum() / max(both.sum(), 1)),
        k_rel_l2=_rel_l2(ca.k[same], cb.k[same]),
        v_rel_l2=_rel_l2(ca.v[same], cb.v[same]),
        id_agree=float(np.mean(outs)))


def _report(tag: str, res: dict) -> None:
    keep = ("arch", "n_layers", "mesh_shape", "max_slots", "budget_bytes",
            "kernels_active", "packed_refresh_calls", "packed_reuse_calls",
            "submitted", "finished", "shed", "rejected", "conserved",
            "committed_tokens", "iterations", "warmup_s", "compiles_warmup",
            "compiles_post_warmup", "peak_bytes_in_use")
    line = {k: res[k] for k in keep}
    line.update({k: v for k, v in res["first"].items()
                 if isinstance(v, (bool, float))})
    print(f"{tag}: {json.dumps(line)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the (1, 4) tensor-parallel path and its "
                         "one-chip comparison only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import CacheEvents, enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    import jax
    from repro.configs import get_config

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    n_dev = 4 if args.four_chips else 1
    if len(jax.devices()) < n_dev:
        print(f"chip_smoke: needs {n_dev} chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    print(f"device_kind: {dev.device_kind}  devices: {len(jax.devices())}  "
          f"compile_cache: {cache_dir}", flush=True)

    t0 = time.perf_counter()
    failures = []
    if not args.four_chips:
        res = serve_and_compare(get_config("llada-8b-1chip"), seed=args.seed)
        _report("llada-8b-1chip", res)
        failures += serve_failures(res, oracle=True)
    else:
        full = serve_and_compare(get_config("llada-8b"), seed=args.seed,
                                 mesh_shape=(1, 4), warmup=False,
                                 oracle=False)
        _report("llada-8b (1,4)", full)
        failures += [f"(1,4) 32 layers: {m}"
                     for m in serve_failures(full, oracle=False)]
        del full
        cut = get_config("llada-8b-1chip")
        one = serve_and_compare(cut, seed=args.seed, warmup=False,
                                oracle=False)
        _report("llada-8b-1chip one chip", one)
        failures += [f"one chip: {m}"
                     for m in serve_failures(one, oracle=False)]
        four = serve_and_compare(cut, seed=args.seed, mesh_shape=(1, 4),
                                 warmup=False, oracle=False)
        _report("llada-8b-1chip (1,4)", four)
        failures += [f"(1,4) 16 layers: {m}"
                     for m in serve_failures(four, oracle=False)]
        cmp = compare_engines(one, four)
        print(f"one chip vs (1,4): {json.dumps(cmp)}", flush=True)
        tol = logit_tol(cut.n_layers)
        if cmp["logit_rel_l2"] > tol:
            failures.append(f"mesh logit rel L2 {cmp['logit_rel_l2']}")
        if max(cmp["k_rel_l2"], cmp["v_rel_l2"]) > tol:
            failures.append(f"mesh cache rel L2 {cmp['k_rel_l2']}, "
                            f"{cmp['v_rel_l2']}")
        if cmp["pos_agree"] < MESH_POS_AGREE_MIN:
            failures.append(f"mesh retained positions agree "
                            f"{cmp['pos_agree']}")
    print(f"compile_cache_hits: {cache.hits}  misses: {cache.misses}  "
          f"total_s: {time.perf_counter() - t0:.1f}", flush=True)
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
