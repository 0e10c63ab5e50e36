"""Run one cell of the on-chip benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout. ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` runs its own window under the profiler and prints
the per-layer metrics. Earlier lines say what ran; the last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared beside its limit), and the last lines of
standard error repeat the checks. Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits non-zero.

JAX's persistent compilation cache is kept in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"


def _paths() -> None:
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: Path = ROOT, t_proc0: float = T_PROC0,
            hbm_bytes=None, cfg_overrides=None, cell_overrides=None,
            trace_dir=None) -> dict:
    """Serve, measure and check one run; print the earlier lines, the
    checks on standard error and the result line last. Returns the result
    object. The device check is the caller's (:func:`main`)."""
    _paths()
    from chipbench import report
    from chipbench import spec as SP
    from chipbench.harness import serve_and_measure
    cell = SP.load_cell(workload, root)
    if cell_overrides:
        cell = cell_overrides(cell)
    keep = trace_dir is not None
    if trace and trace_dir is None:
        trace_dir = root / "chipbench_out" / "trace" / \
            f"{workload}.{seed}"
    out = serve_and_measure(cell, seed, seconds, trace, t_proc0=t_proc0,
                            hbm_bytes=hbm_bytes,
                            cfg_overrides=cfg_overrides,
                            trace_dir=trace_dir)
    return report.emit(out, trace, trace_dir, keep_trace=keep)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    _paths()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import spec as SP
    need = SP.load_cell(args.workload).chips
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"chipbench: {args.workload} needs {need} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    res = execute(args.workload, args.seed, args.seconds,
                  bool(args.trace))
    return 0 if res is not None else 1


if __name__ == "__main__":
    sys.exit(main())
