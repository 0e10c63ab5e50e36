"""Per-head importance scoring kernel — the Refresh-phase side of paper C3.

Computes the raw per-KV-head alignment scores
``raw[b, k, s] = max_{q in block, g in group} (Q_{b,q,k,g} · K_{b,s,k})``
— the inner product of paper eq.(6) before local max-pooling. The pooling
(kernel size w, a [B,K,S] stencil) and the top-k + single gather run as
cheap XLA ops in ``ops.py``; the O(S·Sb·G·dh) matmul is the hot part and
lives here.

Grid ``(B, K, S//S_tile)``; each step is a ``[R, dh] × [dh, S_tile]`` MXU
matmul followed by a column max — no cross-step state, fully parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import jax_compat as JC
from repro.kernels.flash_varlen import tile_ranges


def _kernel(q_ref, k_ref, s_ref):
    q = q_ref[0, 0]        # [R, dh] block queries (Sb·G rows)
    k = k_ref[0, 0]        # [S_tile, dh]
    z = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [R, S_tile]
    s_ref[0, 0, 0] = jnp.max(z, axis=0, keepdims=True)


def _varlen_kernel(krng_ref, q_ref, k_ref, seg_ref, s_ref):
    """Varlen scoring over the flat token-packed stream (whole-iteration
    packing): request r's block queries score ONLY the key tiles whose
    segment-id range contains r — the select/pack analogue of the attention
    kernel's tile-skip (ranges read from SMEM). Non-owned positions score
    ``-inf`` (the same sentinel the padded path uses for invalid rows), so
    the downstream max-pool can never leak a neighbour request's relevance
    across a boundary."""
    r, j = pl.program_id(0), pl.program_id(2)
    overlap = (krng_ref[0, j] <= r) & (r <= krng_ref[1, j])

    @pl.when(overlap)
    def _compute():
        q = q_ref[0, 0]               # [R, dh]
        k = k_ref[0]                  # [S_tile, dh]
        z = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s_ref[0, 0, 0] = jnp.where(seg_ref[0] == r,
                                   jnp.max(z, axis=0, keepdims=True),
                                   -jnp.inf)

    @pl.when(~overlap)
    def _skip():
        s_ref[0, 0, 0] = jnp.full_like(s_ref[0, 0, 0], -jnp.inf)


@functools.partial(JC.jit, static_argnames=("s_tile", "interpret"))
def head_score_call(
    q: jax.Array,     # [B, K, R, dh]  block queries, groups flattened
    k: jax.Array,     # [B, K, S, dh]  full-sequence keys, head-major
    *,
    interpret: bool,
    s_tile: int = 512,
):
    B, K, R, dh = q.shape
    S = k.shape[2]
    s_tile = min(s_tile, S)
    assert S % s_tile == 0, (S, s_tile)
    n_s = S // s_tile
    # scores leave as [.., n_s, 1, s_tile] rows (TPU block rule: the last
    # two block dims equal the array's); the reshape back is free
    out = pl.pallas_call(
        _kernel,
        grid=(B, K, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, R, dh), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, s_tile, dh), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, 1, s_tile),
                               lambda b, h, j: (b, h, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, n_s, 1, s_tile), jnp.float32),
        interpret=interpret,
    )(q, k)
    return out.reshape(B, K, S)


@functools.partial(JC.jit, static_argnames=("s_tile", "interpret"))
def head_score_varlen_call(
    q: jax.Array,     # [R, K, Rq, dh]  block queries per request, groups flat
    k: jax.Array,     # [K, T, dh]      flat packed-stream keys, head-major
    seg: jax.Array,   # [T] int32       ascending owner id (PAD_SEG on pad)
    *,
    interpret: bool,
    s_tile: int = 512,
):
    """Raw per-KV-head scores of every request against the FLAT stream:
    ``out[r, k, t] = max_q(Q_{r,q,k} · K_t)`` where ``seg[t] == r``, else
    ``-inf``. Replaces the padded per-request ``[R, max_seq_len]`` K gather
    of the packed Refresh path — selection reads the stream in place."""
    R, K, Rq, dh = q.shape
    T = k.shape[1]
    s_tile = min(s_tile, T)
    assert T % s_tile == 0, (T, s_tile)
    n_s = T // s_tile
    seg = seg.astype(jnp.int32)
    out = pl.pallas_call(
        _varlen_kernel,
        grid=(R, K, n_s),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, Rq, dh), lambda r, h, j: (r, h, 0, 0)),
            pl.BlockSpec((1, s_tile, dh), lambda r, h, j: (h, j, 0)),
            pl.BlockSpec((1, 1, s_tile), lambda r, h, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, 1, s_tile),
                               lambda r, h, j: (r, h, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, K, n_s, 1, s_tile), jnp.float32),
        interpret=interpret,
    )(tile_ranges(seg, s_tile), q, k, seg.reshape(n_s, 1, s_tile))
    return out.reshape(R, K, T)
