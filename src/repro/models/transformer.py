"""Bidirectional diffusion transformer backbone (dense / moe / vlm / audio).

Two execution paths, mirroring the paper's two phases (§2.3):

* :func:`forward_full` — **Refresh**: full-sequence bidirectional forward.
  Optionally (serve mode) performs head-centric selection + packing *inside*
  the layer scan, emitting the dense packed KV cache without ever
  materializing the full KV stack across layers.
* :func:`forward_block` — **Reuse**: active-block queries attend to
  ``[packed cache ; live block KV]``; nothing is written back to the cache.

Layers are stacked on a leading ``[L, ...]`` axis and driven by ``lax.scan``
so the HLO stays small (critical for 80-layer configs) and remat policies
apply per layer.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models.sparse_select import (PackedKV, select_and_pack,
                                        select_and_pack_varlen)


@dataclass(frozen=True)
class ServeContext:
    """Per-step serving metadata threaded through the layer scan."""
    block_size: int
    retain: int
    kernel_size: int = 3
    selection: str = "head"        # head | uniform | none
    q_chunk: int = L.DEFAULT_Q_CHUNK
    use_flash_kernel: bool = False  # Pallas packed-KV attention in Reuse steps
    reuse_concat: bool = False      # paper-naive single [cache;block] dispatch
    use_flash_refresh: bool = False  # Pallas flash kernel in Refresh steps
    max_seq_len: int = 0            # per-request L cap (varlen-packed Refresh)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer_stack(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    nl, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 12)
    p = {
        "attn_norm": jnp.zeros((nl, D), dtype),
        "mlp_norm": jnp.zeros((nl, D), dtype),
        "wq": L.dense_init(ks[0], (nl, D, H, dh), dtype),
        "wk": L.dense_init(ks[1], (nl, D, K, dh), dtype),
        "wv": L.dense_init(ks[2], (nl, D, K, dh), dtype),
        "wo": L.dense_init(ks[3], (nl, H, dh, D), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nl, H, dh), dtype)
        p["bk"] = jnp.zeros((nl, K, dh), dtype)
        p["bv"] = jnp.zeros((nl, K, dh), dtype)
    if cfg.is_moe:
        p.update(moe_lib.init_moe_stack(cfg, ks[4], dtype))
    else:
        p["w_gate"] = L.dense_init(ks[5], (nl, D, F), dtype)
        p["w_up"] = L.dense_init(ks[6], (nl, D, F), dtype)
        p["w_down"] = L.dense_init(ks[7], (nl, F, D), dtype)
    return p


# ---------------------------------------------------------------------------
# one transformer layer
# ---------------------------------------------------------------------------

def _qkv(p, x, cfg: ModelConfig, cos, sin):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dke->bske", x, p["wk"])
    v = jnp.einsum("bsd,dke->bske", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    return q, k, v


def _mlp(p, x, cfg: ModelConfig):
    """Returns (y, aux_loss). Dense MLPs have zero aux."""
    if cfg.is_moe:
        return moe_lib.moe_ffn(p, x, cfg)
    y = L.gated_mlp(x, p["w_gate"], p["w_up"], p["w_down"], cfg.activation)
    return y, jnp.float32(0.0)


def _layer_full(
    p: dict,
    x: jax.Array,              # [B, S, D]
    cfg: ModelConfig,
    positions: jax.Array,      # [B, S]
    cos, sin,
    is_local: jax.Array,       # scalar bool
    token_valid: jax.Array,    # [B, S]
    mask_mode: str,
    serve: Optional[ServeContext],
    block_start: Optional[jax.Array],   # [B] int32
) -> Tuple[jax.Array, Optional[PackedKV]]:
    x = L.constrain(x, "act3d")
    h = L.rms_norm(x, p["attn_norm"], cfg.rms_eps)
    q, k, v = _qkv(p, h, cfg, cos, sin)
    attn_out = L.attention(
        q, k, v, q_pos=positions, kv_pos=positions,
        kv_valid=token_valid, mask_mode=mask_mode,
        window=cfg.sliding_window, is_local=is_local,
        attn_softcap=cfg.attn_softcap,
        q_chunk=serve.q_chunk if serve else L.DEFAULT_Q_CHUNK,
        use_kernel=bool(serve and serve.use_flash_refresh))
    x = x + jnp.einsum("bshe,hed->bsd", attn_out, p["wo"])
    h2 = L.rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    y, aux = _mlp(p, h2, cfg)
    x = L.constrain(x + y, "act3d")

    packed = None
    if serve is not None:
        Sb = serve.block_size
        B, S = positions.shape
        # slice the active block's queries (per-request block offsets)
        qb = jax.vmap(
            lambda qi, st: jax.lax.dynamic_slice_in_dim(qi, st, Sb, axis=0)
        )(q, block_start)
        ar = jnp.arange(S, dtype=jnp.int32)
        in_block = (ar[None] >= block_start[:, None]) & \
                   (ar[None] < block_start[:, None] + Sb)
        packed = select_and_pack(
            qb, k, v,
            retain=serve.retain, kernel_size=serve.kernel_size,
            mode=serve.selection, exclude=in_block | ~token_valid,
            token_valid=token_valid)
    return x, packed, aux


# ---------------------------------------------------------------------------
# full-sequence (Refresh / train) forward over the layer stack
# ---------------------------------------------------------------------------

def forward_full(
    stack: dict,
    cfg: ModelConfig,
    x: jax.Array,                      # [B, S, D] embedded input
    positions: jax.Array,              # [B, S] int32
    *,
    token_valid: Optional[jax.Array] = None,
    mask_mode: str = "bidirectional",
    serve: Optional[ServeContext] = None,
    block_start: Optional[jax.Array] = None,
    remat: bool = False,
) -> Tuple[jax.Array, Optional[PackedKV]]:
    B, S, D = x.shape
    if token_valid is None:
        token_valid = jnp.ones((B, S), bool)
    cos, sin = L.rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)
    flags = L.layer_flags(cfg)

    def body(carry, scanned):
        p, is_local = scanned
        out, packed, aux = _layer_full(
            p, carry, cfg, positions, cos, sin, is_local,
            token_valid, mask_mode, serve, block_start)
        return out, (packed, aux)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    x, (packed, aux) = jax.lax.scan(body, x, (stack, flags))
    # packed: PackedKV with leading [L] axis (or None); aux: mean over layers
    return x, packed, jnp.mean(aux)


# ---------------------------------------------------------------------------
# token-packed (varlen) Refresh forward — the paper's flattened engine (§4.1)
# ---------------------------------------------------------------------------

def _attend_packed_stream(
    q: jax.Array,              # [1, T, H, dh]
    k: jax.Array,              # [1, T, K, dh]
    v: jax.Array,              # [1, T, K, dh]
    positions: jax.Array,      # [1, T]
    seg_ids: jax.Array,        # [1, T]
    token_valid: jax.Array,    # [1, T]
    cfg: ModelConfig,
    is_local: jax.Array,
    serve: ServeContext,
    mask_mode: str = "bidirectional",
) -> jax.Array:
    """Segment-masked attention over the flat packed stream (jnp fallback to
    the Pallas varlen kernel).

    Requests are contiguous in the stream and at most ``max_seq_len`` long,
    so a ``q_chunk`` query slab can only share a segment with tokens inside a
    ``q_chunk + 2·max_seq_len`` window around it. Each chunk attends to that
    window only — the XLA-level analogue of the kernel's tile-skip, keeping
    fallback FLOPs ~ ``T·(c + 2L)`` instead of ``T²``.
    """
    _, T_len, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    c = min(serve.q_chunk, T_len)
    win = min(T_len, c + 2 * serve.max_seq_len)
    if T_len % c or win >= T_len:
        # window covers everything (or ragged chunking): plain segment path
        return L.attention(
            q, k, v, q_pos=positions, kv_pos=positions,
            kv_valid=token_valid, q_seg=seg_ids, kv_seg=seg_ids,
            mask_mode=mask_mode, window=cfg.sliding_window,
            is_local=is_local, attn_softcap=cfg.attn_softcap, q_chunk=c)
    nq = T_len // c
    scale = dh ** -0.5
    # window start: the first token of the chunk's first segment, clamped so
    # the static-size slice stays in bounds. seg start = chunk_start - pos.
    starts = jnp.arange(nq, dtype=jnp.int32) * c
    seg_start = starts - positions[0, starts]
    w0 = jnp.clip(seg_start, 0, T_len - win)
    qg = q[0].reshape(nq, c, K, G, dh)
    qp = positions[0].reshape(nq, c)
    qs = seg_ids[0].reshape(nq, c)

    def chunk(args):
        qc, qpc, qsc, w = args
        kc = jax.lax.dynamic_slice_in_dim(k[0], w, win, axis=0)
        vc = jax.lax.dynamic_slice_in_dim(v[0], w, win, axis=0)
        kpc = jax.lax.dynamic_slice_in_dim(positions[0], w, win, axis=0)
        ksc = jax.lax.dynamic_slice_in_dim(seg_ids[0], w, win, axis=0)
        kvc = jax.lax.dynamic_slice_in_dim(token_valid[0], w, win, axis=0)
        z = jnp.einsum("qkgd,skd->kgqs", qc, kc).astype(jnp.float32) * scale
        if cfg.attn_softcap:
            z = cfg.attn_softcap * jnp.tanh(z / cfg.attn_softcap)
        ok = (qsc[:, None] == ksc[None, :]) & kvc[None, :]
        if mask_mode == "causal":
            ok = ok & (qpc[:, None] >= kpc[None, :])
        if cfg.sliding_window:
            dist = jnp.abs(qpc[:, None] - kpc[None, :])
            ok = ok & jnp.where(is_local, dist <= cfg.sliding_window, True)
        z = jnp.where(ok[None, None], z, -1e30)
        p = jax.nn.softmax(z, axis=-1).astype(vc.dtype)
        return jnp.einsum("kgqs,skd->qkgd", p, vc)

    out = jax.lax.map(chunk, (qg, qp, qs, w0))     # [nq, c, K, G, dh]
    return out.reshape(1, T_len, H, dh).astype(q.dtype)


def _layer_full_packed(
    p: dict,
    x: jax.Array,              # [1, T, D] flat packed stream
    cfg: ModelConfig,
    positions: jax.Array,      # [1, T] position within the owning request
    seg_ids: jax.Array,        # [1, T] ascending request id (sentinel on pad)
    token_valid: jax.Array,    # [1, T]
    cos, sin,
    is_local: jax.Array,
    serve: ServeContext,
    cu_seqlens: jax.Array,     # [R] int32 flat start offset per request
    gather_rows: jax.Array,    # [R, S_sel] flat row of request r's token s
    valid_sel: jax.Array,      # [R, S_sel]
    block_rows: jax.Array,     # [R, Sb] flat rows of each active block
    in_block: jax.Array,       # [R, S_sel]
    mask_mode: str = "bidirectional",
) -> Tuple[jax.Array, PackedKV, jax.Array]:
    x = L.constrain(x, "act3d")
    h = L.rms_norm(x, p["attn_norm"], cfg.rms_eps)
    q, k, v = _qkv(p, h, cfg, cos, sin)
    if serve.use_flash_refresh or serve.use_flash_kernel:
        from repro.kernels import ops as kops
        attn_out = kops.flash_varlen_attention(
            q[0], k[0], v[0], seg_ids=seg_ids[0], positions=positions[0],
            kv_valid=token_valid[0], window=cfg.sliding_window,
            is_local=is_local, causal=mask_mode == "causal",
            softcap=cfg.attn_softcap)[None]
    else:
        attn_out = _attend_packed_stream(
            q, k, v, positions, seg_ids, token_valid, cfg, is_local, serve,
            mask_mode=mask_mode)
    x = x + jnp.einsum("bshe,hed->bsd", attn_out, p["wo"])
    h2 = L.rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    y, aux = _mlp(p, h2, cfg)
    x = L.constrain(x + y, "act3d")

    # head-centric select/pack reads the flat stream in place: scoring is
    # segment-masked on the stream (kernel tile-skip / chunked jnp) and only
    # the `retain` winners are gathered into the per-slot dense cache — the
    # padded [R, max_seq_len, K, dh] K/V views are never materialized.
    qb = q[0][block_rows]          # [R, Sb, H, dh]
    packed = select_and_pack_varlen(
        qb, k[0], v[0], seg_ids[0], cu_seqlens, gather_rows, valid_sel,
        retain=serve.retain, kernel_size=serve.kernel_size,
        mode=serve.selection, exclude=in_block | ~valid_sel,
        use_kernel=bool(serve.use_flash_refresh or serve.use_flash_kernel))
    return x, packed, aux


def packed_block_rows(cu_seqlens, block_start, block_size: int,
                      total_len: int):
    """Flat stream rows of each request's active block ([R, Sb], clipped so
    padding requests gather in-bounds)."""
    return jnp.clip(
        cu_seqlens[:, None] + block_start[:, None]
        + jnp.arange(block_size, dtype=jnp.int32)[None], 0, total_len - 1)


def packed_refresh_geometry(cu_seqlens, seq_lens, block_start, total_len,
                            serve: ServeContext):
    """Per-request gather geometry of a packed Refresh stream, shared by the
    attention and hybrid packed forwards: the select/pack view rows
    (``gather_rows``/``valid_sel``), each active block's flat rows, and the
    in-block exclusion mask. Returns
    (gather_rows [R, S_sel], valid_sel [R, S_sel], block_rows [R, Sb],
    in_block [R, S_sel])."""
    S_sel = serve.max_seq_len
    Sb = serve.block_size
    ar = jnp.arange(S_sel, dtype=jnp.int32)
    gather_rows = jnp.clip(cu_seqlens[:, None] + ar[None], 0, total_len - 1)
    valid_sel = ar[None] < seq_lens[:, None]
    block_rows = packed_block_rows(cu_seqlens, block_start, Sb, total_len)
    in_block = (ar[None] >= block_start[:, None]) & \
               (ar[None] < block_start[:, None] + Sb)
    return gather_rows, valid_sel, block_rows, in_block


def forward_full_packed(
    stack: dict,
    cfg: ModelConfig,
    x: jax.Array,                  # [1, T, D] embedded packed stream
    positions: jax.Array,          # [1, T] int32
    seg_ids: jax.Array,            # [1, T] int32
    token_valid: jax.Array,        # [1, T] bool
    cu_seqlens: jax.Array,         # [R] int32 flat start offset per request
    seq_lens: jax.Array,           # [R] int32 true length per request
    block_start: jax.Array,        # [R] int32 block offset within the request
    serve: ServeContext,
) -> Tuple[jax.Array, PackedKV, jax.Array]:
    """Token-packed Refresh over the layer stack.

    One ragged ``[T, ...]`` stream replaces the padded ``[B, S]`` batch;
    requests are delimited by ``cu_seqlens``/``seg_ids`` and attention is
    segment-masked (kernel or chunked-jnp — never an [S, S] bias). The
    stream is family-agnostic: for the modality-frontend archs the caller
    (``backbone.serve_refresh_packed``) embeds each segment as
    ``[frontend prefix ; text]`` and widens ``serve.max_seq_len`` by
    ``frontend_len`` — prefix rows are ordinary stream rows here (they
    attend, score, and are retainable). Returns (flat hidden [1, T, D],
    per-request PackedKV with leading [L] axis, aux).
    """
    assert serve.max_seq_len > 0, "packed path needs ServeContext.max_seq_len"
    _, T, _ = x.shape
    cos, sin = L.rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)
    flags = L.layer_flags(cfg)
    gather_rows, valid_sel, block_rows, in_block = packed_refresh_geometry(
        cu_seqlens, seq_lens, block_start, T, serve)

    def body(carry, scanned):
        p, is_local = scanned
        out, packed, aux = _layer_full_packed(
            p, carry, cfg, positions, seg_ids, token_valid, cos, sin,
            is_local, serve, cu_seqlens, gather_rows, valid_sel, block_rows,
            in_block)
        return out, (packed, aux)

    x, (packed, aux) = jax.lax.scan(body, x, (stack, flags))
    return x, packed, jnp.mean(aux)


# ---------------------------------------------------------------------------
# block (Reuse) forward over a packed cache
# ---------------------------------------------------------------------------

def forward_block(
    stack: dict,
    cfg: ModelConfig,
    xb: jax.Array,                 # [B, Sb, D] embedded active block
    block_positions: jax.Array,    # [B, Sb] int32
    cache: PackedKV,               # leading [L] axis on every field
    *,
    serve: ServeContext,
    mask_mode: str = "bidirectional",
) -> jax.Array:
    cos, sin = L.rope_tables(block_positions, cfg.resolved_head_dim, cfg.rope_theta)
    flags = L.layer_flags(cfg)

    def body(carry, scanned):
        p, is_local, ck, cv, cpos, cvalid = scanned
        x = reuse_attention_layer(p, carry, cfg, cos, sin, block_positions,
                                  is_local, ck, cv, cpos, cvalid, mask_mode,
                                  use_kernel=serve.use_flash_kernel,
                                  concat=serve.reuse_concat)
        h2 = L.rms_norm(x, p["mlp_norm"], cfg.rms_eps)
        y, _ = _mlp(p, h2, cfg)
        return x + y, None

    xb, _ = jax.lax.scan(
        body, xb, (stack, flags, cache.k, cache.v, cache.pos, cache.valid))
    return xb


def forward_block_packed(
    stack: dict,
    cfg: ModelConfig,
    xb: jax.Array,                 # [R, Sb, D] embedded active blocks
    block_positions: jax.Array,    # [R, Sb] int32 absolute positions
    cache: PackedKV,               # leading [L] axis; see rows
    *,
    serve: ServeContext,
    rows: Optional[jax.Array] = None,     # [R] int32 slot table
    n_live: Optional[jax.Array] = None,   # [1] int32 real requests
) -> jax.Array:
    """Token-packed Reuse over the layer stack (whole-iteration packing).

    The iteration's R active blocks form one ragged ``[R·Sb]`` query stream
    (R is rounded to the token-bucket granularity by the engine — never a
    pow2 batch bucket). With ``use_flash_kernel`` each layer runs ONE
    cross-attention dispatch that reads each request's retained K/V in
    place: ``cache`` is the whole slot pool (``[L, S, ...]``), ``rows`` the
    slot table and ``n_live`` the count of leading real requests; with
    ``rows`` None, ``cache`` is a gathered one whose row r is request r.
    The cache leaves are closed over and the layer is indexed inside the
    kernel, so no layer's cache is sliced, concatenated or transposed (a
    scanned Pallas operand would be a per-layer copy of every slot).
    Without the kernel (``rows`` must be None), the layer falls back to the
    exact split-attention math batched over the same R — identical FLOPs,
    XLA-level dispatch. Bidirectional only (the attention families are
    bidirectional diffusion LMs; the causal hybrid family has its own
    packed Reuse in :func:`repro.models.hybrid.forward_block_packed`)."""
    R, Sb, D = xb.shape
    cos, sin = L.rope_tables(block_positions, cfg.resolved_head_dim,
                             cfg.rope_theta)
    flags = L.layer_flags(cfg)

    def mlp(x, p):
        h2 = L.rms_norm(x, p["mlp_norm"], cfg.rms_eps)
        y, _ = _mlp(p, h2, cfg)
        return x + y

    if not serve.use_flash_kernel:
        assert rows is None, "the split-attention path reads a gathered cache"

        def body(carry, scanned):
            p, is_local, ck, cv, cpos, cvalid = scanned
            x = reuse_attention_layer(p, carry, cfg, cos, sin,
                                      block_positions, is_local, ck, cv,
                                      cpos, cvalid, "bidirectional",
                                      concat=serve.reuse_concat)
            return mlp(x, p), None

        xb, _ = jax.lax.scan(
            body, xb, (stack, flags, cache.k, cache.v, cache.pos,
                       cache.valid))
        return xb

    from repro.kernels import ops as kops
    if rows is None:
        rows = jnp.arange(R, dtype=jnp.int32)
        n_live = jnp.full((1,), R, jnp.int32)
    kv_pos = kops.retained_positions(cache.pos, cache.valid, rows)
    q_pos = block_positions.reshape(-1)

    def body(carry, scanned):
        p, is_local, layer = scanned
        h = L.rms_norm(carry, p["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(p, h, cfg, cos, sin)
        H, K, dh = q.shape[2], k.shape[2], q.shape[3]
        out = kops.flash_varlen_pool_attention(
            q.reshape(R * Sb, H, dh), k.reshape(R * Sb, K, dh),
            v.reshape(R * Sb, K, dh), cache.k, cache.v, kv_pos, rows=rows,
            n_live=n_live, layer=layer, q_pos=q_pos,
            window=cfg.sliding_window, is_local=is_local,
            softcap=cfg.attn_softcap)
        x = carry + jnp.einsum("bshe,hed->bsd", out.reshape(R, Sb, H, dh),
                               p["wo"])
        return mlp(x, p), None

    layers = jnp.arange(cache.k.shape[0], dtype=jnp.int32)
    xb, _ = jax.lax.scan(body, xb, (stack, flags, layers))
    return xb


def _reuse_attention_layer_flat(p, x, cfg: ModelConfig, cos, sin,
                                block_positions, is_local, ck, cv, cpos,
                                cvalid, q_seg, kv_seg,
                                mask_mode: str = "bidirectional"):
    """One packed-Reuse attention sublayer as a single flat varlen dispatch
    over a gathered cache (the hybrid family's causal shared block).

    x: [R, Sb, D]; ck/cv: [R, K, Cr, dh] gathered slot caches. The KV stream
    interleaves each request's retained cache with its live block KV —
    requests stay contiguous (segment-ascending), so the cross kernel's
    tile-skip bounds compute by Σ (retain + Sb) per owning request."""
    R, Sb, _ = x.shape
    K, Cr, dh = ck.shape[1], ck.shape[2], ck.shape[3]
    h = L.rms_norm(x, p["attn_norm"], cfg.rms_eps)
    q, k, v = _qkv(p, h, cfg, cos, sin)
    H = q.shape[2]
    kb = k.transpose(0, 2, 1, 3)          # [R, K, Sb, dh]
    vb = v.transpose(0, 2, 1, 3)
    bpos_hm = jnp.broadcast_to(block_positions[:, None], (R, K, Sb))
    k_all = jnp.concatenate([ck, kb], axis=2)      # [R, K, Cr+Sb, dh]
    v_all = jnp.concatenate([cv, vb], axis=2)
    pos_all = jnp.concatenate([cpos, bpos_hm], axis=2)
    valid_all = jnp.concatenate(
        [cvalid, jnp.ones((R, K, Sb), bool)], axis=2)
    Tkv = R * (Cr + Sb)
    k_s = k_all.transpose(1, 0, 2, 3).reshape(K, Tkv, dh)
    v_s = v_all.transpose(1, 0, 2, 3).reshape(K, Tkv, dh)
    pos_s = pos_all.transpose(1, 0, 2).reshape(K, Tkv)
    valid_s = valid_all.transpose(1, 0, 2).reshape(K, Tkv)
    from repro.kernels import ops as kops
    out = kops.flash_varlen_cross_attention(
        q.reshape(R * Sb, H, dh), k_s, v_s,
        q_seg=q_seg, q_pos=block_positions.reshape(-1),
        kv_seg=kv_seg, kv_pos=pos_s, kv_valid=valid_s,
        window=cfg.sliding_window, is_local=is_local,
        causal=mask_mode == "causal", softcap=cfg.attn_softcap)
    attn_out = out.reshape(R, Sb, H, dh)
    return x + jnp.einsum("bshe,hed->bsd", attn_out, p["wo"])


def reuse_attention_layer(p, x, cfg: ModelConfig, cos, sin, block_positions,
                          is_local, ck, cv, cpos, cvalid, mask_mode,
                          use_kernel: bool = False, concat: bool = False):
    """One Reuse-phase attention sublayer over [packed cache ; live block KV].

    Default (``concat=False``): **split attention** — one pass over the
    packed cache, one over the live block KV, merged exactly with flash-style
    (m, s) statistics. This is the TPU adaptation of the paper's single
    varlen dispatch: concatenating the live block onto a *sharded* retained
    axis forces XLA to gather the whole cache (measured: +17 GiB/device on
    decode_32k); two attentions + an exact merge keep the cache sharded.
    ``concat=True`` keeps the paper-naive single dispatch for comparison.
    """
    h = L.rms_norm(x, p["attn_norm"], cfg.rms_eps)
    q, k, v = _qkv(p, h, cfg, cos, sin)
    kb = k.transpose(0, 2, 1, 3)      # [B, K, Sb, dh]
    vb = v.transpose(0, 2, 1, 3)
    bpos_hm = jnp.broadcast_to(block_positions[:, None], kb.shape[:3])
    if concat:
        k_all = jnp.concatenate([ck, kb], axis=2)   # [B, K, R+Sb, dh]
        v_all = jnp.concatenate([cv, vb], axis=2)
        pos_all = jnp.concatenate([cpos, bpos_hm], axis=2)
        valid_all = jnp.concatenate(
            [cvalid, jnp.ones(kb.shape[:3], bool)], axis=2)
        attn_out = _attend_packed(q, k_all, v_all, pos_all, valid_all,
                                  block_positions, is_local, cfg, mask_mode,
                                  use_kernel=use_kernel)
    else:
        ok_c = _reuse_mask(cvalid, cpos, block_positions, is_local, cfg,
                           mask_mode)
        ok_b = _reuse_mask(jnp.ones(kb.shape[:3], bool), bpos_hm,
                           block_positions, is_local, cfg, mask_mode)
        if use_kernel:
            from repro.kernels import ops as kops
            B, Sb, H, dh = q.shape
            K = ck.shape[1]
            G = H // K
            qr = (q.reshape(B, Sb, K, G, dh).transpose(0, 2, 1, 3, 4)
                  .reshape(B, K, Sb * G, dh))
            o1, m1, s1 = kops.packed_flash_attention_stats(
                qr, ck, cv, ok_c, softcap=cfg.attn_softcap)
            o1 = o1.reshape(B, K, Sb, G, dh)
            m1 = m1.reshape(B, K, Sb, G)
            s1 = s1.reshape(B, K, Sb, G)
            m1 = m1.transpose(0, 1, 3, 2)
            s1 = s1.transpose(0, 1, 3, 2)
            o1 = o1.transpose(0, 1, 3, 2, 4)
        else:
            o1, m1, s1 = _attend_stats(q, ck, cv, ok_c, cfg)
        o2, m2, s2 = _attend_stats(q, kb, vb, ok_b, cfg)
        m = jnp.maximum(m1, m2)
        a1 = jnp.exp(m1 - m)[..., None]
        a2 = jnp.exp(m2 - m)[..., None]
        den = s1[..., None] * a1 + s2[..., None] * a2
        out = (o1 * a1 + o2 * a2) / jnp.maximum(den, 1e-30)
        B, Sb, H, dh = q.shape
        K = ck.shape[1]
        attn_out = (out.transpose(0, 3, 1, 2, 4)     # [B,Sb,K,G,dh]
                    .reshape(B, Sb, H, dh).astype(q.dtype))
    return x + jnp.einsum("bshe,hed->bsd", attn_out, p["wo"])


def _reuse_mask(valid, pos_hm, q_pos, is_local, cfg: ModelConfig, mask_mode):
    """[B, K, Sb, T] boolean mask for one side of the split attention."""
    ok = valid[:, :, None, :]
    if mask_mode == "causal":
        ok = ok & (q_pos[:, None, :, None] >= pos_hm[:, :, None, :])
    if cfg.sliding_window:
        dist = jnp.abs(q_pos[:, None, :, None] - pos_hm[:, :, None, :])
        ok = ok & jnp.where(is_local, dist <= cfg.sliding_window, True)
    return ok


def _attend_stats(q, k_hm, v_hm, ok, cfg: ModelConfig):
    """Unnormalized flash statistics for exact merging.

    q: [B, Sb, H, dh]; k_hm/v_hm: [B, K, T, dh]; ok: [B, K, Sb, T].
    Returns (o [B,K,G,Sb,dh] f32 unnormalized, m [B,K,G,Sb], s [B,K,G,Sb]).
    """
    B, Sb, H, dh = q.shape
    K = k_hm.shape[1]
    G = H // K
    scale = dh ** -0.5
    qg = q.reshape(B, Sb, K, G, dh)
    z = jnp.einsum("bqkgd,bktd->bkgqt", qg, k_hm).astype(jnp.float32) * scale
    if cfg.attn_softcap:
        z = cfg.attn_softcap * jnp.tanh(z / cfg.attn_softcap)
    z = jnp.where(ok[:, :, None], z, -jnp.inf)
    m = jnp.max(z, axis=-1)                       # [B,K,G,Sb]
    msafe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(z - msafe[..., None])
    p = jnp.where(jnp.isfinite(z), p, 0.0)
    s = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgqt,bktd->bkgqd", p.astype(v_hm.dtype), v_hm)
    return o.astype(jnp.float32), jnp.where(jnp.isfinite(m), m, -1e30), s


def _attend_packed(q, k_all, v_all, pos_all, valid_all, q_pos, is_local,
                   cfg: ModelConfig, mask_mode: str = "bidirectional",
                   use_kernel: bool = False):
    """Reuse-phase attention: [B,Sb,H,dh] queries over head-major packed KV.

    k_all/v_all: [B, K, T, dh]; pos_all/valid_all: [B, K, T].
    ``use_kernel`` dispatches to the Pallas flash kernel (same contract).
    """
    B, Sb, H, dh = q.shape
    K = k_all.shape[1]
    G = H // K
    ok = valid_all[:, :, None, :]                       # [B, K, 1, T]
    if mask_mode == "causal":
        ok = ok & (q_pos[:, None, :, None] >= pos_all[:, :, None, :])
    if cfg.sliding_window:
        dist = jnp.abs(q_pos[:, None, :, None] - pos_all[:, :, None, :])
        ok = ok & jnp.where(is_local, dist <= cfg.sliding_window, True)
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.packed_flash_attention(
            q, k_all, v_all, ok, softcap=cfg.attn_softcap)
    scale = dh ** -0.5
    qg = q.reshape(B, Sb, K, G, dh)
    s = jnp.einsum("bqkgd,bktd->bkgqt", qg, k_all).astype(jnp.float32) * scale
    if cfg.attn_softcap:
        s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
    s = jnp.where(ok[:, :, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v_all.dtype)
    out = jnp.einsum("bkgqt,bktd->bqkgd", p, v_all)
    return out.reshape(B, Sb, H, dh)
