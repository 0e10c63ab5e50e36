"""Segment-reset SSD scan over a token-packed stream (Pallas).

The varlen side of the Mamba2 SSD recurrence: one ragged ``[T_total]`` token
stream carries every Refresh request of an iteration (delimited by
cu_seqlens; ``reset`` marks each request's first token) and the kernel runs
the chunked state-space scan with the recurrent state zeroed at every
segment boundary — the scan-family analogue of the segment-masked varlen
attention kernel. Compute stays the blocked SSD math (intra-chunk quadratic
term as MXU matmuls + an O(1)-state inter-chunk recurrence carried across
grid steps), so FLOPs scale with real tokens instead of the padded
``batch_bucket × max_seq_len`` rectangle.

Grid is ``(H, n_chunks)``: one state head per outer step, the stream's
chunks sequentially inside it (the head's state carry lives in an output
block revisited by every chunk step, like the flash kernels'
accumulators). Every in-kernel value is 2-D, as the TPU's vector unit
wants: each head's decay cumsums arrive both as a ``[c, 1]`` column and a
``[1, c]`` row, and ``x·dt`` both as ``[c, P]`` and transposed ``[P, c]``,
all prepared by XLA outside the kernel.
Segment resets are handled by a *reset-count* mask, NOT by a −inf decay
injection: a pair (j → i) contributes iff no reset falls in ``(j, i]``
(``cnt[i] == cnt[j]`` for the inclusive reset prefix-count), which keeps the
decay cumsums free of sentinel values — a −1e30 sentinel would absorb every
subsequent f32 cumsum term and zero the post-reset decays entirely.

Per-request state capture happens **in-kernel**: ``cap_rows[r]`` names the
flat row after which request r's recurrent state must be read (−1 → zero
state, e.g. a block at position 0). The owning chunk computes the masked
partial state ``Σ_{j≤idx} exp(cs[idx]−cs[j])·b_j + gate·exp(cs[idx])·state``
and accumulates it into the ``[R, H, P, N]`` capture output — no
``[T, H, P, N]`` per-token state tensor is ever materialized (that is the
jnp associative-scan fallback's memory cost, see
:func:`repro.models.ssm.varlen_ssd_scan`).

The in-chunk cumulative sums (of ``dA`` and of the reset flags) are
computed in XLA before the kernel, in exact f32 adds. All exponents are ≤ 0
on unmasked lanes (dA = dt·A < 0), so nothing overflows where it matters;
masked lanes may hit ``inf`` before the ``where`` discards them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import jax_compat as JC


def _kernel(cap_ref, x_ref, xt_ref, csc_ref, csr_ref, cntc_ref, cntr_ref,
            b_ref, c_ref, y_ref, cap_out_ref, state_ref, *, c: int,
            r_cap: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)
        cap_out_ref[...] = jnp.zeros_like(cap_out_ref)

    x = x_ref[0]              # [c, P] f32  (x · dt)
    xt = xt_ref[0, 0]         # [P, c]      (the same, transposed)
    csc = csc_ref[0]          # [c, 1]      in-chunk inclusive Σ dA
    csr = csr_ref[0, 0]       # [1, c]
    cntc = cntc_ref[...]      # [c, 1]      in-chunk inclusive reset count
    cntr = cntr_ref[0]        # [1, c]
    Bm = b_ref[...]           # [c, N]
    Cm = c_ref[...]           # [c, N]
    state_in = state_ref[0]   # [P, N]
    nt = (((1,), (1,)), ((), ()))

    # 1) intra-chunk quadratic term: (j → i) decays exp(cs_i − cs_j) and is
    # masked out when a reset falls in (j, i] (different inclusive counts)
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    L = jnp.where((ii >= jj) & (cntc == cntr), jnp.exp(csc - csr), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, nt,
                                 preferred_element_type=jnp.float32)
    y_diag = jnp.dot(scores * L, x, preferred_element_type=jnp.float32)

    # 2) incoming-state term: token i sees the carried state iff no reset ≤ i
    c_st = jax.lax.dot_general(Cm, state_in, nt,
                               preferred_element_type=jnp.float32)  # [c, P]
    y_ref[0] = y_diag + c_st * jnp.where(cntc == 0.0, jnp.exp(csc), 0.0)

    # 3) per-request state capture (state AFTER flat row cap_rows[r]); only
    # the chunk that owns row cap_rows[r] contributes
    jr = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    for r in range(r_cap):
        loc = cap_ref[r] - i * c

        @pl.when((loc >= 0) & (loc < c))
        def _capture():
            at = jr == loc
            cs_at = jnp.sum(jnp.where(at, csr, 0.0), axis=1, keepdims=True)
            cnt_at = jnp.sum(jnp.where(at, cntr, 0.0), axis=1, keepdims=True)
            w = jnp.where((jr <= loc) & (cntr == cnt_at),
                          jnp.exp(cs_at - csr), 0.0)                # [1, c]
            contrib = jnp.dot(xt * w, Bm,
                              preferred_element_type=jnp.float32)   # [P, N]
            base = jnp.where(cnt_at == 0.0, jnp.exp(cs_at), 0.0)    # [1, 1]
            cap_out_ref[0, r] += contrib + base * state_in

    # 4) chunk-end state for the inter-chunk recurrence
    cs_end = csr[:, c - 1:]                                         # [1, 1]
    cnt_end = cntr[:, c - 1:]
    dec = jnp.where(cntr == cnt_end, jnp.exp(cs_end - csr), 0.0)    # [1, c]
    delta = jnp.dot(xt * dec, Bm, preferred_element_type=jnp.float32)
    keep = jnp.where(cnt_end == 0.0, jnp.exp(cs_end), 0.0)
    state_ref[0] = state_in * keep + delta


@functools.partial(JC.jit, static_argnames=("chunk", "interpret"))
def ssm_segment_scan_call(
    xdt: jax.Array,       # [T, H, P] f32  pre-multiplied x · dt
    dA: jax.Array,        # [T, H]    f32  dt · A (negative)
    Bm: jax.Array,        # [T, N]    f32
    Cm: jax.Array,        # [T, N]    f32
    reset: jax.Array,     # [T]       f32  1.0 at segment-start tokens
    cap_rows: jax.Array,  # [R]       i32  flat row of each capture (−1: zero)
    *,
    interpret: bool,
    chunk: int = 64,
):
    """Returns (y [T, H, P] f32, captured states [R, H, P, N] f32,
    final state [H, P, N] f32)."""
    T, H, P = xdt.shape
    N = Bm.shape[1]
    R = cap_rows.shape[0]
    assert T % chunk == 0, (T, chunk)
    n = T // chunk
    f32 = jnp.float32
    x_h = xdt.astype(f32).transpose(1, 0, 2)                   # [H, T, P]
    xt_h = x_h.reshape(H, n, chunk, P).transpose(0, 1, 3, 2)   # [H, n, P, c]
    cs = jnp.cumsum(dA.astype(f32).T.reshape(H, n, chunk), axis=2)
    cnt = jnp.cumsum(reset.astype(f32).reshape(n, chunk), axis=1)
    kern = functools.partial(_kernel, c=chunk, r_cap=R)
    y, cap, state = pl.pallas_call(
        kern,
        grid=(H, n),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, P), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, P, chunk), lambda h, i: (h, i, 0, 0)),
            pl.BlockSpec((1, chunk, 1), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda h, i: (h, i, 0, 0)),
            pl.BlockSpec((chunk, 1), lambda h, i: (i, 0)),
            pl.BlockSpec((1, 1, chunk), lambda h, i: (i, 0, 0)),
            pl.BlockSpec((chunk, N), lambda h, i: (i, 0)),
            pl.BlockSpec((chunk, N), lambda h, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, R, P, N), lambda h, i: (h, 0, 0, 0)),
            pl.BlockSpec((1, P, N), lambda h, i: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, T, P), f32),
            jax.ShapeDtypeStruct((H, R, P, N), f32),
            jax.ShapeDtypeStruct((H, P, N), f32),
        ],
        interpret=interpret,
    )(cap_rows.astype(jnp.int32), x_h, xt_h,
      cs.reshape(H, T, 1), cs.reshape(H, n, 1, chunk),
      cnt.reshape(T, 1), cnt.reshape(n, 1, chunk),
      Bm.astype(f32), Cm.astype(f32))
    return y.transpose(1, 0, 2), cap.transpose(1, 0, 2, 3), state
