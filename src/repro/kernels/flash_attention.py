"""Packed-KV flash attention — the Reuse-phase hot loop (paper C2/C3).

Computes attention of active-block queries over the *head-centric dense
packed* KV cache (plus the live block KV appended by the caller). Because the
paper's C3 packs retained tokens contiguously at Refresh time, this kernel
reads K/V tiles with plain sequential DMA — no gather, no indirection — which
is exactly the property the paper trades per-head top-k flexibility for.

Contract (matches ``transformer._attend_packed``):
  q    [B, K, R, dh]   R = Sb·G query rows per KV head (GQA groups flattened)
  k,v  [B, K, T, dh]   head-major packed KV (+ live block appended)
  mask [B, K, Sb, T]   validity/window/causality (broadcast over the G axis)
  out  [B, K, R, dh] f32 (unnormalized; ops.py divides by the softmax sum)

Grid ``(B, K, T//T_tile)``: online-softmax accumulation across KV tiles into
revisited output blocks, flash-attention style. m/s carried as [B, K, R]
outputs (portable across interpret/TPU; no scratch dependence).

VMEM per step at (Sb=32, G=8 → R=256, dh=256, T_tile=512):
q 128 KB + k/v 2·256 KB + acc 256 KB + mask 16 KB ≈ 1 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from repro import jax_compat as JC


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, s_ref,
            *, scale: float, softcap: float, g: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)

    q = q_ref[0, 0]          # [R, dh]
    k = k_ref[0, 0]          # [Tt, dh]
    v = v_ref[0, 0]          # [Tt, dh]
    mk = mask_ref[0, 0]      # [Sb, Tt] bool

    z = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [R, Tt]
    if softcap:
        z = softcap * jnp.tanh(z / softcap)
    R, Tt = z.shape
    zm = z.reshape(R // g, g, Tt)
    zm = jnp.where(mk[:, None, :], zm, -1e30)
    z = zm.reshape(R, Tt)

    m_old = m_ref[0, 0]                       # [R]
    local_m = jnp.max(z, axis=1)
    m_new = jnp.maximum(m_old, local_m)
    alpha = jnp.exp(m_old - m_new)            # rescale previous accumulators
    p = jnp.exp(z - m_new[:, None])           # [R, Tt]
    s_ref[0, 0] = s_ref[0, 0] * alpha + jnp.sum(p, axis=1)
    o_ref[0, 0] = (o_ref[0, 0] * alpha[:, None]
                   + jnp.dot(p.astype(v.dtype), v,
                             preferred_element_type=jnp.float32))
    m_ref[0, 0] = m_new


@functools.partial(JC.jit, static_argnames=("softcap", "t_tile", "interpret"))
def packed_flash_attention_call(
    q: jax.Array,        # [B, K, R, dh]
    k: jax.Array,        # [B, K, T, dh]
    v: jax.Array,        # [B, K, T, dh]
    mask: jax.Array,     # [B, K, Sb, T] bool
    *,
    interpret: bool,
    softcap: float = 0.0,
    t_tile: int = 512,
):
    B, K, R, dh = q.shape
    T = k.shape[2]
    Sb = mask.shape[2]
    g = R // Sb
    t_tile = min(t_tile, T)
    assert T % t_tile == 0, (T, t_tile)
    n_t = T // t_tile
    kern = functools.partial(_kernel, scale=dh ** -0.5, softcap=softcap, g=g)
    out, m, s = pl.pallas_call(
        kern,
        grid=(B, K, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, R, dh), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, t_tile, dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, t_tile, dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, Sb, t_tile), lambda b, h, j: (b, h, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, R, dh), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda b, h, j: (b, h, 0)),
            pl.BlockSpec((1, 1, R), lambda b, h, j: (b, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, R, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, K, R), jnp.float32),
            jax.ShapeDtypeStruct((B, K, R), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask)
    return out, m, s
