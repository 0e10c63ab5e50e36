"""Fused online logit→token kernel — the TPU-native form of paper C1.

The paper's Logit Decomposition splits the output projection into serial
token-axis sub-batches and frees each ``[chunk, V]`` buffer before the next.
XLA has no ``free()``; the TPU-native equivalent is to *never materialize*
``[chunk, V]``: tile the vocabulary axis through VMEM and carry only the
O(chunk) online-argmax/online-softmax state across tiles. Peak activation for
the output stage drops from ``chunk × V × 2B`` (paper) to
``T_tile × V_tile × 4B`` (here) — e.g. for LLaDA-8B (V=126,464),
2048×126464×2B ≈ 494 MB → 256×512×4B ≈ 0.5 MB per core-step.

Grid: ``(T // T_tile, V // V_tile)`` — the V axis iterates innermost
(sequentially on a TPU core), accumulating into revisited output blocks:

  * ``m``   — running max logit           [T, 1]
  * ``idx`` — running argmax index        [T, 1]
  * ``s``   — running Σ exp(z − m)        [T, 1]  (online softmax)

Per-row state is kept as ``[T_tile, 1]`` columns (TPU block rule: a rank-1
block must equal its array or tile it by the dtype's tiling, which a
token-bucketed stream cannot promise).

``conf = 1/s`` (softmax probability of the argmax) is formed in ``ops.py``.

MXU alignment: the matmul is ``[T_tile, D] × [D, V_tile]`` with T_tile, V_tile
multiples of 128 and D the full model dim (bf16-friendly; accumulation f32).
VMEM at defaults (T_tile=256, V_tile=512, D=8192): q-block 4 MB + w-block
8 MB + acc < 13 MB — under the 16 MB/core budget for the largest arch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import jax_compat as JC


def _kernel(any_ref, h_ref, w_ref, idx_ref, m_ref, s_ref, *, softcap: float,
            v_tile: int, w_layout: str):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    # whole-iteration packing: the hidden stream is token-bucketed, so a
    # trailing T-tile can be all bucket padding — skip its entire V loop
    # (the matmul never runs; outputs keep their init values and the wrapper
    # masks them). Within a mixed tile padding rows just ride along. The
    # per-tile "any valid row" flags are reduced in XLA and read from SMEM.
    @pl.when(any_ref[i] != 0)
    def _compute():
        h = h_ref[...]                 # [T_tile, D]
        w = w_ref[...]                 # [D, V_tile] ("dv") | [V_tile, D] ("vd")
        if w_layout == "vd":
            # tied-embedding layout: contract over the last dim of both — the
            # MXU takes either orientation; this avoids transposing the whole
            # [V, D] table in HBM.
            z = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        else:
            z = jnp.dot(h, w, preferred_element_type=jnp.float32)  # [T_tile, V_tile]
        if softcap:
            z = softcap * jnp.tanh(z / softcap)

        local_m = jnp.max(z, axis=1, keepdims=True)            # [T_tile, 1]
        # lowest index attaining the max (argmax's tie-break)
        col = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
        local_i = jnp.min(jnp.where(z == local_m, col, v_tile), axis=1,
                          keepdims=True) + j * v_tile

        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, local_m)
        s_ref[...] = (s_ref[...] * jnp.exp(m_old - m_new)
                      + jnp.sum(jnp.exp(z - m_new), axis=1, keepdims=True))
        idx_ref[...] = jnp.where(local_m > m_old, local_i, idx_ref[...])
        m_ref[...] = m_new


@functools.partial(JC.jit, static_argnames=("softcap", "t_tile", "v_tile",
                                             "interpret", "w_layout"))
def fused_logit_argmax_call(
    h: jax.Array,          # [T, D]
    w: jax.Array,          # [D, V] (w_layout="dv") or [V, D] ("vd", tied)
    valid: jax.Array,      # [T] bool (False on bucket-padding rows)
    *,
    interpret: bool,
    softcap: float = 0.0,
    t_tile: int = 256,
    v_tile: int = 512,
    w_layout: str = "dv",
):
    """Returns (idx [T] i32, m [T] f32, s [T] f32): the argmax, its logit
    and the softmax denominator relative to it."""
    T, D = h.shape
    V = w.shape[1] if w_layout == "dv" else w.shape[0]
    t_tile = min(t_tile, T)
    v_tile = min(v_tile, V)
    assert T % t_tile == 0 and V % v_tile == 0, (T, t_tile, V, v_tile)
    n_t, n_v = T // t_tile, V // v_tile

    kern = functools.partial(_kernel, softcap=softcap, v_tile=v_tile,
                             w_layout=w_layout)
    w_spec = (pl.BlockSpec((D, v_tile), lambda i, j: (0, j))
              if w_layout == "dv"
              else pl.BlockSpec((v_tile, D), lambda i, j: (j, 0)))
    row = pl.BlockSpec((t_tile, 1), lambda i, j: (i, 0))
    idx, m, s = pl.pallas_call(
        kern,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((t_tile, D), lambda i, j: (i, 0)),
            w_spec,
        ],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((T, 1), jnp.int32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
        ],
        interpret=interpret,
    )(valid.reshape(n_t, t_tile).any(axis=1).astype(jnp.int32), h, w)
    return idx[:, 0], m[:, 0], s[:, 0]
