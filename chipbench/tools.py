"""Shared set-up of the benchmark's one-process tools (``sweep.py``,
``calibrate.py``): the TPU check, the compile cache and one built cell
whose engine serves many windows, draining between them."""
from __future__ import annotations

import gc
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def setup(workload: str, seed: int, need_tpu: bool = True, **build_kw):
    """(cell, built): the TPU checked, the cache at ``<checkout>/.jax_cache``
    and the cell built once with the weights of ``seed``."""
    t_proc0 = time.perf_counter()
    if need_tpu:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if need_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    from chipbench import harness as H
    from chipbench import spec as SP
    cell = SP.load_cell(workload, ROOT)
    b = H.build(cell, seed, **build_kw)
    b.times["t_proc0"] = t_proc0
    return cell, b


def swap_weights(b, seed: int) -> None:
    """Serve the next windows with the weights of ``seed``: the engine's
    programs take the weights as an argument, so nothing recompiles."""
    import jax
    from chipbench import weights as W
    b.eng.params = b.params = None
    gc.collect()
    b.params = W.make_params(b.dims, seed)
    jax.block_until_ready(b.params)
    b.eng.params = b.params
