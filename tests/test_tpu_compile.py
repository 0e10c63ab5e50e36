"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than attached. Each test lowers one kernel at the published
widths of a configuration the repository serves (llada-8b: 32 heads of 128;
mamba2-130m: 24 SSD heads, P=64, N=128; llada's 126,464-row vocab) with
Mosaic — not the interpreter — and compiles it, so a block shape or layout
the chip refuses fails here instead of on the chip. Nothing runs.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_varlen as FV
from repro.kernels import logit_argmax as LA
from repro.kernels import select_pack as SP
from repro.kernels import ssm_scan as SS

BF, F32, I32, BOOL = jnp.bfloat16, jnp.float32, jnp.int32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described v5e chip, with the
    persistent compile cache off (its entries cannot be read back without
    the chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_with_mosaic(lowered) -> None:
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


# (K query/KV heads, GQA group, tokens, q_tile, kv_tile, causal, window):
# llada-8b's packed Refresh stream at two token buckets, plus the GQA,
# causal and windowed variants the kernel serves for other families
@pytest.mark.parametrize("K,g,T,q_tile,kv_tile,causal,window", [
    (32, 1, 1024, 256, 512, False, 0),
    (32, 1, 128, 128, 128, False, 0),
    (8, 4, 512, 256, 512, True, 0),
    (16, 2, 512, 256, 256, False, 128),
])
def test_flash_varlen_compiles(spec, K, g, T, q_tile, kv_tile, causal,
                               window):
    dh = 128
    _compiled_with_mosaic(FV.flash_varlen_call.lower(
        spec((K, T * g, dh), BF), spec((K, T, dh), BF), spec((K, T, dh), BF),
        spec((T,), I32), spec((T,), I32), spec((T,), BOOL),
        spec((1,), BOOL), q_tile=q_tile, kv_tile=kv_tile, causal=causal,
        window=window, interpret=False))


# packed Reuse: R blocks of 32 queries against R·(retain + 32) KV rows
@pytest.mark.parametrize("R,retain,kv_tile", [(8, 128, 256), (3, 224, 32)])
def test_flash_varlen_cross_compiles(spec, R, retain, kv_tile):
    K, dh, Sb = 32, 128, 32
    Tq, Tkv = R * Sb, R * (retain + Sb)
    _compiled_with_mosaic(FV.flash_varlen_cross_call.lower(
        spec((K, Tq, dh), BF), spec((K, Tkv, dh), BF),
        spec((K, Tkv, dh), BF), spec((Tq,), I32), spec((K, Tkv), I32),
        spec((Tq,), I32), spec((Tkv,), I32), spec((K, Tkv), BOOL),
        spec((1,), BOOL), q_tile=128, kv_tile=kv_tile, interpret=False))


# packed Reuse in place: R blocks of 32 queries against the retained K/V of
# an [L, S, K, Cr, dh] pool; the chat cell's 16 x 13 x 32 x 608 x 128 pool
# (retained axis whole), and a GQA, windowed pool whose axis takes 2 tiles
@pytest.mark.parametrize("Lyr,S,K,g,Cr,n_c,R,window", [
    (16, 13, 32, 1, 608, 1, 4, 0),
    (4, 9, 8, 4, 1024, 2, 3, 128),
])
def test_flash_varlen_pool_compiles(spec, Lyr, S, K, g, Cr, n_c, R, window):
    dh, Sb = 128, 32
    _compiled_with_mosaic(FV.flash_varlen_pool_call.lower(
        spec((K, R * Sb * g, dh), BF), spec((K, R * Sb, dh), BF),
        spec((K, R * Sb, dh), BF), spec((R * Sb,), I32),
        spec((Lyr, S, K, Cr, dh), BF), spec((Lyr, S, K, Cr, dh), BF),
        spec((Lyr, R, K, n_c, 1, Cr // n_c), I32), spec((R,), I32),
        spec((1,), I32), spec((1,), I32), spec((1,), BOOL), window=window,
        interpret=False))


@pytest.mark.parametrize("T,chunk,R", [(1024, 64, 4), (256, 128, 8)])
def test_ssm_segment_scan_compiles(spec, T, chunk, R):
    H, P, N = 24, 64, 128                     # mamba2-130m
    _compiled_with_mosaic(SS.ssm_segment_scan_call.lower(
        spec((T, H, P), F32), spec((T, H), F32), spec((T, N), F32),
        spec((T, N), F32), spec((T,), F32), spec((R,), I32), chunk=chunk,
        interpret=False))


@pytest.mark.parametrize("R,T,s_tile", [(4, 1024, 512), (8, 128, 128)])
def test_head_score_varlen_compiles(spec, R, T, s_tile):
    K, dh, Sb = 32, 128, 32
    _compiled_with_mosaic(SP.head_score_varlen_call.lower(
        spec((R, K, Sb, dh), BF), spec((K, T, dh), BF), spec((T,), I32),
        s_tile=s_tile, interpret=False))


@pytest.mark.parametrize("T,w_layout", [(256, "dv"), (512, "dv"),
                                        (384, "vd")])
def test_fused_logit_argmax_compiles(spec, T, w_layout):
    D, V = 4096, 126_464                      # llada-8b
    w = (D, V) if w_layout == "dv" else (V, D)
    _compiled_with_mosaic(LA.fused_logit_argmax_call.lower(
        spec((T, D), BF), spec(w, BF), spec((T,), BOOL),
        t_tile=128 if T == 384 else 256, v_tile=512, w_layout=w_layout,
        interpret=False))
