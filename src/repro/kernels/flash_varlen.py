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

The cross-attention variant runs the same kernel with distinct query/KV
streams and per-KV-head KV positions/validity (the hybrid family's Reuse
over a gathered cache). The pool variant (``flash_varlen_pool_call``, the
attention families' Reuse) shares its mask and online-softmax update but
reads each request's retained K/V in place from the slot pool through a
scalar-prefetched slot table, with the live block's K/V as a second input.
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


def _position_mask(ok, qp, kp, loc, *, causal: bool, window: int):
    """Narrow ``ok`` by the causal and sliding-window masks of query
    positions ``qp`` ([R, 1]) against key positions ``kp`` ([1, Tk])."""
    if causal:
        ok = ok & (qp >= kp)
    if window:
        # is_local is a runtime per-layer flag: a global layer widens the
        # window past any position difference
        win = window + (1 - loc) * (1 << 30)
        ok = ok & (jnp.abs(qp - kp) <= win)
    return ok


def _online_update(q, k, v, ok, o, m_sc, s_sc, *, scale: float,
                   softcap: float):
    """One flash online-softmax step: fold the keys ``k`` / values ``v``
    that ``ok`` allows into query rows ``q``'s running (o, max, sum). The
    max and sum live in VMEM scratch; returns the new unnormalized o."""
    z = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        z = softcap * jnp.tanh(z / softcap)
    z = jnp.where(ok, z, -1e30)
    m_old = m_sc[...]          # [R, 1]
    m_new = jnp.maximum(m_old, jnp.max(z, axis=1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(z - m_new)
    s_sc[...] = s_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_sc[...] = m_new
    return o * alpha + jnp.dot(p.astype(v.dtype), v,
                               preferred_element_type=jnp.float32)


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
        qp = qpos_ref[0]           # [R, 1]  (R = q_tile * G rows)
        kp = kpos_ref[0, 0]        # [1, Tk]
        ok = (qseg_ref[0] == kseg_ref[0]) & (kvalid_ref[0, 0] != 0)
        ok = _position_mask(ok, qp, kp, loc_ref[0], causal=causal,
                            window=window)
        o_ref[0] = _online_update(q_ref[0], k_ref[0], v_ref[0], ok,
                                  o_ref[0], m_sc, s_sc, scale=scale,
                                  softcap=softcap)

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


def _pool_kernel(rows_ref, live_ref, layer_ref, loc_ref, q_ref, kb_ref,
                 vb_ref, qpos_ref, bpos_ref, k_ref, v_ref, kpos_ref, o_ref,
                 m_sc, s_sc, *, scale: float, softcap: float, window: int,
                 n_c: int):
    r, c = pl.program_id(0), pl.program_id(2)
    live = r < live_ref[0]
    qp = qpos_ref[...]             # [rows, 1]
    mask = functools.partial(_position_mask, qp=qp, loc=loc_ref[0],
                             causal=False, window=window)
    update = functools.partial(_online_update, scale=scale, softcap=softcap)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        s_sc[...] = jnp.zeros_like(s_sc)

    @pl.when(live & (c == 0))
    def _block():
        # the live block first: every query sees its own key, so the running
        # max is finite before a retained tile can be wholly masked
        bp = bpos_ref[...]         # [1, Sb]
        o_ref[...] = update(q_ref[...], kb_ref[...], vb_ref[...],
                            mask(bp >= 0, kp=bp),  # every live key is valid
                            o_ref[...], m_sc, s_sc)

    @pl.when(live)
    def _retained():
        kp = kpos_ref[...]         # [1, ct], -1 where nothing is retained
        o_ref[...] = update(q_ref[...], k_ref[...], v_ref[...],
                            mask(kp >= 0, kp=kp), o_ref[...], m_sc, s_sc)

    @pl.when(c == n_c - 1)
    def _final():
        o_ref[...] = o_ref[...] / jnp.maximum(s_sc[...], 1e-30)


@functools.partial(JC.jit, static_argnames=("softcap", "window", "interpret"))
def flash_varlen_pool_call(
    q: jax.Array,          # [K, R*Sb*G, dh] row-flat GQA layout (token-major)
    k_blk: jax.Array,      # [K, R*Sb, dh] the live blocks' keys
    v_blk: jax.Array,      # [K, R*Sb, dh]
    q_pos: jax.Array,      # [R*Sb] int32 absolute position of each query
    pool_k: jax.Array,     # [L, S, K, Cr, dh] the slot pool's whole leaf
    pool_v: jax.Array,     # [L, S, K, Cr, dh]
    kv_pos: jax.Array,     # [L, R, K, n_c, 1, ct] int32 (retained_positions)
    rows: jax.Array,       # [R] int32 pool row of request r
    n_live: jax.Array,     # [1] int32 requests [0, n_live) are real
    layer: jax.Array,      # [1] int32 layer index into the pool's [L] axis
    is_local: jax.Array,   # [1] bool
    *,
    interpret: bool,
    softcap: float = 0.0,
    window: int = 0,
):
    """Bidirectional packed-Reuse cross attention that reads each request's
    retained K/V in place from the slot pool through the slot table
    ``rows``.

    Grid ``(R, K, n_c)``: request r's ``Sb·G`` query rows of head h attend
    to their own live block (``k_blk``/``v_blk``, folded first) and then to
    the ``n_c`` retained tiles of pool row ``rows[r]`` in ``layer``, which
    the BlockSpec index maps fetch straight from HBM. Nothing is gathered
    or concatenated. Requests at or past ``n_live`` are padding: every
    index map points them at the last real request's final blocks, so the
    pipeline issues no DMA for them, and the kernel does no matmul; their
    output rows are zero. Returns the normalized output ``[K, R*Sb*G, dh]``
    f32.
    """
    K, RG, dh = q.shape
    R = rows.shape[0]
    rq, Sb = RG // R, k_blk.shape[1] // R
    n_c, ct = kv_pos.shape[3], kv_pos.shape[5]

    def own(r, h, c, live_ref):
        """Padding requests re-read the last real request's last blocks."""
        live = r < live_ref[0]
        last = jnp.maximum(live_ref[0] - 1, 0)
        return (jnp.where(live, r, last), jnp.where(live, h, K - 1),
                jnp.where(live, c, n_c - 1))

    def pool_map(r, h, c, rows_ref, live_ref, layer_ref, loc_ref):
        r, h, c = own(r, h, c, live_ref)
        return layer_ref[0], rows_ref[r], h, c, 0

    def pos_map(r, h, c, rows_ref, live_ref, layer_ref, loc_ref):
        r, h, c = own(r, h, c, live_ref)
        return layer_ref[0], r, h, c, 0, 0

    def head_map(r, h, c, rows_ref, live_ref, layer_ref, loc_ref):
        r, h, _ = own(r, h, c, live_ref)
        return h, r, 0

    def row_map(r, h, c, rows_ref, live_ref, layer_ref, loc_ref):
        r, _, _ = own(r, h, c, live_ref)
        return r, 0, 0

    kern = functools.partial(
        _pool_kernel, scale=dh ** -0.5, softcap=softcap, window=window,
        n_c=n_c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R, K, n_c),
        in_specs=[
            pl.BlockSpec((None, rq, dh), head_map),
            pl.BlockSpec((None, Sb, dh), head_map),
            pl.BlockSpec((None, Sb, dh), head_map),
            pl.BlockSpec((None, rq, 1), row_map),
            pl.BlockSpec((None, 1, Sb), row_map),
            pl.BlockSpec((None, None, None, ct, dh), pool_map),
            pl.BlockSpec((None, None, None, ct, dh), pool_map),
            pl.BlockSpec((None, None, None, None, 1, ct), pos_map),
        ],
        out_specs=pl.BlockSpec((None, rq, dh),
                               lambda r, h, c, *_: (h, r, 0)),
        scratch_shapes=[pltpu.VMEM((rq, 1), jnp.float32),
                        pltpu.VMEM((rq, 1), jnp.float32)],
    )
    g = rq // Sb
    q_rows = jnp.repeat(q_pos.astype(jnp.int32), g).reshape(R, rq, 1)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, RG, dh), jnp.float32),
        interpret=interpret,
    )(rows.astype(jnp.int32), n_live.astype(jnp.int32),
      layer.astype(jnp.int32), is_local.astype(jnp.int32).reshape(1),
      q, k_blk, v_blk, q_rows, q_pos.astype(jnp.int32).reshape(R, 1, Sb),
      pool_k, pool_v, kv_pos)
