import os
if "XLA_FLAGS" not in os.environ:   # honor a user-exported XLA_FLAGS as-is
    os.environ["XLA_FLAGS"] = os.environ.get(
        "REPRO_XLA_FLAGS", "--xla_force_host_platform_device_count=2")

# --- 1-device-vs-N-device serving agreement (the sharding oracle) ----------
#
# The two lines above run before ANY other import (jax locks the device count
# on first init) — same precedent as the dry-run cells. This harness serves
# the SAME trace twice inside one process:
#
#   1. reference: the single-device engine (no mesh — the bit-identical
#      anchor of every padded-vs-packed oracle),
#   2. candidate: the identical engine under a REPRO_MESH device mesh
#      (params placed by Rules.params, slot pool sharded by Rules.cache,
#      vocab-parallel logit stage),
#
# and demands agreement on the three things that define serving correctness:
# committed token ids (exact), the captured slot-pool caches (allclose — TP
# all-reduces legally reorder float sums), and the final EngineStats token
# counters (exact: identical iteration plans must execute identical token
# geometry). All requests arrive at t=0 so planning depends only on
# budget/slot state, never on the clock — the two runs schedule identically
# by construction and any divergence is a sharding bug, not timing noise.
#
# Usage (CPU, 2 host devices):
#   XLA_FLAGS=--xla_force_host_platform_device_count=2 REPRO_MESH=1,2 \
#       python -m repro.launch.shard_check --arch llada-8b
#
# Exit code 0 + {"ok": true} JSON on agreement; non-zero otherwise.

import argparse
import dataclasses
import json

import numpy as np

from repro.configs import ARCHS, reduced
from repro.configs.base import ServeConfig
from repro.core.engine import Engine
from repro.launch.mesh import parse_mesh_env

COUNTERS = ("committed_tokens", "iterations", "refresh_steps", "reuse_steps",
            "refresh_tokens_real", "refresh_tokens_exec",
            "reuse_tokens_real", "reuse_tokens_exec",
            "logit_tokens_real", "logit_tokens_exec")


def serve_trace(cfg, serve, n: int, seed: int, warmup: bool,
                duplicate: bool = False):
    eng = Engine(cfg, serve, seed=seed)
    if warmup:
        eng.warmup()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size - 1, int(rng.integers(8, 48)))
               for _ in range(n)]
    if duplicate:
        # pair requests onto identical prompts so content-addressed sharing
        # engages; the rng stream is drawn in full first, so the duplicated
        # trace differs from the unique one only by the aliasing
        prompts = [prompts[i // 2] for i in range(n)]
    reqs = [eng.submit(p, gen_len=16, arrival=0.0, rid=i)
            for i, p in enumerate(prompts)]
    stats = eng.run()
    return eng, reqs, stats


def check(arch: str, mesh_shape, n: int = 5, seed: int = 0,
          varlen: bool = True, warmup: bool = False,
          kernels: bool = False, sharing: bool = False) -> dict:
    import jax
    cfg = reduced(ARCHS[arch])
    serve = ServeConfig(
        max_num_batched_tokens=512, max_num_logits=64, block_size=8,
        steps_per_block=8, max_seq_len=128, max_slots=8,
        max_refresh_per_iter=2, logit_mode="chunked",
        varlen_pack=varlen, token_bucket=64, prefix_sharing=sharing)
    if kernels:
        # Pallas hot paths on BOTH runs: the reference is the 1-device
        # kernel run, so agreement proves the shard_mapped kernels (not a
        # jnp fallback) reproduce it bit-for-bit on token ids
        serve = dataclasses.replace(serve, use_flash_kernel=True,
                                    logit_mode="fused")
    # reference FIRST: the sharding policy a mesh engine installs must not
    # retroactively touch the single-device anchor. Under --sharing both
    # runs serve duplicated prompts, so agreement additionally proves the
    # refcounted pool (dedup hits, COW promotes, promote-on-release target
    # choice) is device-count invariant.
    eng_ref, r_ref, st_ref = serve_trace(cfg, serve, n, seed, warmup=False,
                                         duplicate=sharing)
    mesh_serve = dataclasses.replace(serve, mesh_shape=tuple(mesh_shape))
    eng, r_mesh, st_mesh = serve_trace(cfg, mesh_serve, n, seed,
                                       warmup=warmup, duplicate=sharing)
    out = dict(arch=arch, varlen=varlen, mesh=list(mesh_shape),
               mesh_devices=eng.mesh_devices, n=n, kernels=kernels,
               kernels_active=eng.kernels_active, sharing=sharing,
               shared_hits=st_mesh.shared_hits,
               shared_cow_promotes=st_mesh.shared_cow_promotes,
               packed_reuse_calls=st_mesh.packed_reuse_calls,
               reuse_inplace_calls=st_mesh.reuse_inplace_calls,
               ok=True, diffs=[])
    if sharing:
        for name in ("shared_hits", "shared_cow_promotes",
                     "phys_slots_peak"):
            va, vb = getattr(st_ref, name), getattr(st_mesh, name)
            if va != vb:
                out["diffs"].append(f"stats.{name}: {va} != {vb}")
        if st_mesh.shared_hits == 0:
            out["diffs"].append("sharing requested but no dedup hits — "
                                "the check proved nothing")
    if eng.mesh_devices != int(np.prod(mesh_shape)):
        out["diffs"].append("mesh collapsed to "
                            f"{eng.mesh_devices} devices")
    for a, b in zip(r_ref, r_mesh):
        if not np.array_equal(a.output_tokens(), b.output_tokens()):
            out["diffs"].append(f"token ids diverge on rid={a.rid}")
    for name in COUNTERS:
        va, vb = getattr(st_ref, name), getattr(st_mesh, name)
        if va != vb:
            out["diffs"].append(f"stats.{name}: {va} != {vb}")
    # captured caches: compare the slot pools leaf-by-leaf. A data-sharded
    # candidate pool may carry padded tail slots (so its slot axis divides
    # the data axis); they are never written — compare the common
    # real+scratch slot range only.
    ref_pool = jax.device_get(eng_ref.pool.cache)
    mesh_pool = jax.device_get(eng.pool.cache)
    ns = eng_ref.serve.max_slots + 1
    for i, (la, lb) in enumerate(zip(jax.tree.leaves(ref_pool),
                                     jax.tree.leaves(mesh_pool))):
        la, lb = la[:, :ns], lb[:, :ns]
        if la.shape != lb.shape:
            out["diffs"].append(f"pool leaf {i} shape {la.shape}!={lb.shape}")
        elif not np.allclose(np.asarray(la, np.float32),
                             np.asarray(lb, np.float32),
                             atol=1e-5, rtol=1e-5):
            err = float(np.abs(np.asarray(la, np.float32)
                               - np.asarray(lb, np.float32)).max())
            out["diffs"].append(f"pool leaf {i} max err {err:.2e}")
    out["ok"] = not out["diffs"]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llada-8b")
    ap.add_argument("--mesh", default=None,
                    help="'d,m' (default: REPRO_MESH, else 1,2)")
    ap.add_argument("--n", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--padded", action="store_true",
                    help="check the padded-oracle path instead of packed")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-warm the mesh engine first (audits sharded "
                         "warmup buckets too)")
    ap.add_argument("--kernels", action="store_true",
                    help="Pallas hot paths on both runs (use_flash_kernel + "
                         "logit_mode=fused): proves the shard_mapped "
                         "kernels match the 1-device kernel run")
    ap.add_argument("--sharing", action="store_true",
                    help="refcounted prefix sharing on both runs over "
                         "duplicated prompts: proves the ledger (hits, COW "
                         "promotes) is device-count invariant")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    mesh = (tuple(int(x) for x in args.mesh.split(","))
            if args.mesh else (parse_mesh_env() or (1, 2)))
    res = check(args.arch, mesh, n=args.n, seed=args.seed,
                varlen=not args.padded, warmup=args.warmup,
                kernels=args.kernels, sharing=args.sharing)
    print(json.dumps(res, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
