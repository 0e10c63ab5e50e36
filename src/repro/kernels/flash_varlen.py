"""Ragged (varlen) flash attention over a token-packed stream.

The paper's flattened engine (§4.1) packs every Refresh request of an
iteration into one ragged ``[T_total, ...]`` token stream so compute scales
with *actual* tokens instead of ``batch_bucket × max_seq_len`` padding. This
kernel is the attention side of that contract: one flat stream, per-token
segment ids (request index, ascending; padding uses a large sentinel), and
in-kernel segment masking — a query attends to a key iff both tokens belong
to the same request. No cross-request attention, and no ``[S, S]`` bias is
ever materialized.

Grid ``(K, n_q, n_kv)`` (KV innermost), flash online-softmax accumulation
with the running max/sum in VMEM scratch, plus a **tile-skip**: segment ids
are ascending along the stream, so a KV tile whose segment range does not
intersect the query tile's range is skipped entirely (only the
init/normalize bookkeeping runs). That is what makes packed-attention FLOPs
track ``Σ S_i²`` rather than ``T_total²`` at tile granularity. The per-tile
segment ranges are reduced in XLA and read from SMEM as scalars.

Masking inputs are per-token: ``pos`` (position *within* the request —
drives causal and sliding-window masks), ``seg`` (request id), ``valid``
(False on bucket padding). GQA rows are token-major flattened (row = t·G +
g), so the query-side arrays are expanded to one entry per row. Layouts
follow the TPU block rules: query-side arrays are ``[n_q, rows, 1]``
columns, KV-side arrays ``[..., n_kv, 1, kv_tile]`` rows, so every block's
last two dims equal the array's.

The cross-attention variant (the Reuse phase) runs the same kernel with
distinct query/KV streams and per-KV-head KV positions/validity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import jax_compat as JC

# Segment id for bucket-padding tokens. Must sort after every real request id
# so the ascending-stream tile-skip stays valid.
PAD_SEG = (1 << 30)


def _kernel(qrng_ref, krng_ref, loc_ref, q_ref, k_ref, v_ref, qpos_ref,
            qseg_ref, kpos_ref, kseg_ref, kvalid_ref, o_ref, m_sc, s_sc,
            *, scale: float, softcap: float, causal: bool, window: int,
            n_kv: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        s_sc[...] = jnp.zeros_like(s_sc)

    # tile-skip: streams are segment-ascending, so disjoint id ranges cannot
    # share a request — skip the matmul + softmax update entirely.
    overlap = (qrng_ref[0, i] <= krng_ref[1, j]) & \
        (krng_ref[0, j] <= qrng_ref[1, i])

    @pl.when(overlap)
    def _compute():
        q = q_ref[0]               # [R, dh]  (R = q_tile * G rows)
        k = k_ref[0]               # [Tk, dh]
        v = v_ref[0]
        z = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            z = softcap * jnp.tanh(z / softcap)
        qp = qpos_ref[0]           # [R, 1]
        kp = kpos_ref[0, 0]        # [1, Tk]
        ok = (qseg_ref[0] == kseg_ref[0]) & (kvalid_ref[0, 0] != 0)
        if causal:
            ok = ok & (qp >= kp)
        if window:
            # is_local is a runtime per-layer flag: a global layer widens
            # the window past any position difference
            win = window + (1 - loc_ref[0]) * (1 << 30)
            ok = ok & (jnp.abs(qp - kp) <= win)
        z = jnp.where(ok, z, -1e30)

        m_old = m_sc[...]          # [R, 1]
        m_new = jnp.maximum(m_old, jnp.max(z, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(z - m_new)
        s_sc[...] = s_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        o_ref[0] = (o_ref[0] * alpha
                    + jnp.dot(p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32))
        m_sc[...] = m_new

    @pl.when(j == n_kv - 1)
    def _final():
        o_ref[0] = o_ref[0] / jnp.maximum(s_sc[...], 1e-30)


def tile_ranges(seg: jax.Array, tile: int) -> jax.Array:
    """[2, n_tiles] (min, max) segment id of each ``tile``-long stretch."""
    t = seg.reshape(-1, tile)
    return jnp.stack([t.min(axis=1), t.max(axis=1)])


def _varlen_attention(q, k, v, q_pos, q_seg, kv_pos, kv_seg, kv_valid,
                      is_local, *, softcap, causal, window, q_tile, kv_tile,
                      interpret):
    """Shared pallas_call of the self- and cross-attention entry points.
    ``kv_pos``/``kv_valid`` are ``[Kp, Tkv]`` with Kp = 1 (shared by every
    head) or K (per KV head)."""
    K, RG, dh = q.shape
    Tq, Tkv = q_pos.shape[0], k.shape[1]
    g = RG // Tq
    q_tile = min(q_tile, Tq)
    kv_tile = min(kv_tile, Tkv)
    assert Tq % q_tile == 0 and Tkv % kv_tile == 0, (Tq, q_tile, Tkv,
                                                      kv_tile)
    n_q, n_kv = Tq // q_tile, Tkv // kv_tile
    rt = q_tile * g
    per_head = kv_pos.shape[0] > 1

    def q_rows(x):                 # [Tq] -> [n_q, rt, 1] (GQA rows)
        return jnp.repeat(x.astype(jnp.int32), g).reshape(n_q, rt, 1)

    def kv_row(x):                 # [Kp, Tkv] -> [Kp, n_kv, 1, kv_tile]
        return x.astype(jnp.int32).reshape(x.shape[0], n_kv, 1, kv_tile)

    kvh = (lambda h: h) if per_head else (lambda h: 0)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(
        _kernel, scale=dh ** -0.5, softcap=softcap, causal=causal,
        window=window, n_kv=n_kv)
    return pl.pallas_call(
        kern,
        grid=(K, n_q, n_kv),
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((1, rt, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, kv_tile, dh), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, kv_tile, dh), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, rt, 1), lambda h, i, j: (i, 0, 0)),
            pl.BlockSpec((1, rt, 1), lambda h, i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1, kv_tile),
                         lambda h, i, j: (kvh(h), j, 0, 0)),
            pl.BlockSpec((1, 1, kv_tile), lambda h, i, j: (j, 0, 0)),
            pl.BlockSpec((1, 1, 1, kv_tile),
                         lambda h, i, j: (kvh(h), j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rt, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((K, RG, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rt, 1), jnp.float32),
                        pltpu.VMEM((rt, 1), jnp.float32)],
        interpret=interpret,
    )(tile_ranges(q_seg.astype(jnp.int32), q_tile),
      tile_ranges(kv_seg.astype(jnp.int32), kv_tile),
      is_local.astype(jnp.int32).reshape(1),
      q, k, v, q_rows(q_pos), q_rows(q_seg), kv_row(kv_pos),
      kv_seg.astype(jnp.int32).reshape(n_kv, 1, kv_tile), kv_row(kv_valid))


@functools.partial(JC.jit, static_argnames=(
    "softcap", "causal", "window", "q_tile", "kv_tile", "interpret"))
def flash_varlen_call(
    q: jax.Array,         # [K, T*G, dh] row-flat GQA layout (token-major)
    k: jax.Array,         # [K, T, dh]
    v: jax.Array,         # [K, T, dh]
    pos: jax.Array,       # [T] int32 position within the owning request
    seg: jax.Array,       # [T] int32 ascending request id (PAD_SEG on pad)
    kv_valid: jax.Array,  # [T] bool
    is_local: jax.Array,  # [1] bool (gemma2 alternating local layers)
    *,
    interpret: bool,
    softcap: float = 0.0,
    causal: bool = False,
    window: int = 0,
    q_tile: int = 256,
    kv_tile: int = 512,
):
    """Segment-masked self-attention over one packed stream; returns the
    normalized output ``[K, T*G, dh]`` f32."""
    return _varlen_attention(
        q, k, v, pos, seg, pos[None], seg, kv_valid[None], is_local,
        softcap=softcap, causal=causal, window=window, q_tile=q_tile,
        kv_tile=kv_tile, interpret=interpret)


@functools.partial(JC.jit, static_argnames=(
    "softcap", "causal", "window", "q_tile", "kv_tile", "interpret"))
def flash_varlen_cross_call(
    q: jax.Array,          # [K, Tq*G, dh] row-flat GQA layout (token-major)
    k: jax.Array,          # [K, Tkv, dh]
    v: jax.Array,          # [K, Tkv, dh]
    q_pos: jax.Array,      # [Tq] int32 absolute position of each query token
    kv_pos: jax.Array,     # [K, Tkv] int32 per-head original token positions
    q_seg: jax.Array,      # [Tq] int32 ascending reuse-request id (PAD_SEG pad)
    kv_seg: jax.Array,     # [Tkv] int32 ascending owner id (head-independent)
    kv_valid: jax.Array,   # [K, Tkv] bool (False on unselected cache slots)
    is_local: jax.Array,   # [1] bool
    *,
    interpret: bool,
    softcap: float = 0.0,
    causal: bool = False,
    window: int = 0,
    q_tile: int = 128,
    kv_tile: int = 512,
):
    """Ragged cross-attention dispatch (bidirectional dLLM Reuse mask by
    default; ``causal=True`` for the hybrid family's causal shared block).

    The query stream is the iteration's packed active blocks (Tq = Σ block
    tokens, segment id = reuse-request index); the KV stream is the
    per-request ``[retain ; live block]`` slice of the slot pool (Tkv =
    R·(retain + Sb), same segment ids). KV positions and validity carry a
    leading KV-head axis because head-centric selection (C3) retains an
    independent token set per head. Both streams are segment-ascending, so
    the same tile-skip applies: a KV tile owned by other requests never
    reaches the MXU.
    """
    return _varlen_attention(
        q, k, v, q_pos, q_seg, kv_pos, kv_seg, kv_valid, is_local,
        softcap=softcap, causal=causal, window=window, q_tile=q_tile,
        kv_tile=kv_tile, interpret=interpret)
