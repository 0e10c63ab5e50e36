"""Flash attention for the Refresh phase (full-sequence bidirectional).

The roofline baseline showed the Refresh-phase jnp attention writes its
``[*, q_chunk, S]`` f32 score tensors to HBM — 30.8 TB/device/step for
qwen2.5-14b×prefill_32k, 76% of the memory term. This kernel is the classic
2-D-grid flash forward: scores/probs never leave VMEM; online-softmax state
(m, s) is carried across KV tiles in revisited output blocks.

Grid ``(B, K, n_q, n_kv)`` (KV innermost). Per (batch, kv-head, q-tile):
  q rows = q_tile × G (GQA groups flattened), online accumulation over KV
  tiles, final normalization fused into the last KV step.

Masking: built in-kernel from position tiles — bidirectional (diffusion
default), optional causal, optional sliding window (gemma2 local layers via a
runtime ``is_local`` scalar), and a KV validity mask. No [S, S] bias ever
exists.

VMEM at (q_tile=256, G=8, dh=128, kv_tile=512): q 1 MB + k/v 2×0.5 MB +
acc f32 1 MB + scores 2 MB ≈ 5 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from repro import jax_compat as JC


def _kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, kvalid_ref, loc_ref,
            o_ref, m_ref, s_ref,
            *, scale: float, softcap: float, g: int, causal: bool,
            window: int, n_kv: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)

    q = q_ref[0, 0]            # [R, dh]  (R = q_tile * G)
    k = k_ref[0, 0]            # [Tk, dh]
    v = v_ref[0, 0]
    qp = qpos_ref[0]           # [q_tile]
    kp = kpos_ref[0]           # [Tk]
    kv = kvalid_ref[0]         # [Tk]

    z = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [R, Tk]
    if softcap:
        z = softcap * jnp.tanh(z / softcap)
    ok = kv[None, :]
    if causal:
        ok = ok & (qp[:, None] >= kp[None, :])
    if window:
        # is_local arrives as a runtime flag (gemma2 alternates per layer)
        loc = loc_ref[0]
        ok = ok & ((jnp.abs(qp[:, None] - kp[None, :]) <= window) | ~loc)
    # broadcast the [q_tile, Tk] mask over the G group heads
    R, Tk = z.shape
    zm = jnp.where(ok[:, None, :], z.reshape(R // g, g, Tk), -1e30)
    z = zm.reshape(R, Tk)

    m_old = m_ref[0, 0]
    m_new = jnp.maximum(m_old, jnp.max(z, axis=1))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(z - m_new[:, None])
    s_new = s_ref[0, 0] * alpha + jnp.sum(p, axis=1)
    o_new = (o_ref[0, 0] * alpha[:, None]
             + jnp.dot(p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32))
    m_ref[0, 0] = m_new
    s_ref[0, 0] = s_new

    @pl.when(j == n_kv - 1)
    def _final():
        o_ref[0, 0] = o_new / jnp.maximum(s_new, 1e-30)[:, None]

    @pl.when(j < n_kv - 1)
    def _accum():
        o_ref[0, 0] = o_new


@functools.partial(JC.jit, static_argnames=(
    "softcap", "causal", "window", "q_tile", "kv_tile", "interpret"))
def flash_refresh_call(
    q: jax.Array,        # [B, K, S*G, dh] row-flat GQA layout
    k: jax.Array,        # [B, K, S, dh]
    v: jax.Array,        # [B, K, S, dh]
    q_pos: jax.Array,    # [B, S] int32
    kv_pos: jax.Array,   # [B, S] int32
    kv_valid: jax.Array,  # [B, S] bool
    is_local: jax.Array,  # [1] bool (runtime: gemma2 alternating layers)
    *,
    interpret: bool,
    softcap: float = 0.0,
    causal: bool = False,
    window: int = 0,
    q_tile: int = 256,
    kv_tile: int = 512,
):
    B, K, RG, dh = q.shape
    S = k.shape[2]                 # KV length
    Sq = q_pos.shape[1]            # query length (may be a seq-shard of S)
    g = RG // Sq
    q_tile = min(q_tile, Sq)
    kv_tile = min(kv_tile, S)
    assert Sq % q_tile == 0 and S % kv_tile == 0, (Sq, S, q_tile, kv_tile)
    n_q, n_kv = Sq // q_tile, S // kv_tile
    kern = functools.partial(
        _kernel, scale=dh ** -0.5, softcap=softcap, g=g, causal=causal,
        window=window, n_kv=n_kv)
    out, m, s = pl.pallas_call(
        kern,
        grid=(B, K, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, q_tile * g, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_tile, dh), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, kv_tile, dh), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, q_tile), lambda b, h, i, j: (b, i)),
            pl.BlockSpec((1, kv_tile), lambda b, h, i, j: (b, j)),
            pl.BlockSpec((1, kv_tile), lambda b, h, i, j: (b, j)),
            pl.BlockSpec((1,), lambda b, h, i, j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q_tile * g, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, q_tile * g), lambda b, h, i, j: (b, h, i)),
            pl.BlockSpec((1, 1, q_tile * g), lambda b, h, i, j: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, RG, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, K, RG), jnp.float32),
            jax.ShapeDtypeStruct((B, K, RG), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, q_pos, kv_pos, kv_valid, is_local)
    return out
