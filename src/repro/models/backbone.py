"""Unified per-family model API used by the engine, trainer, and dry-run.

  * :func:`init_params`   — build the param pytree for any assigned arch.
  * :func:`train_forward` — full-sequence forward for the masked-diffusion
    training loss. Returns (normed hidden, moe aux loss).
  * :func:`serve_refresh` — the paper's **Refresh** phase: full forward,
    capture the serving cache (packed sparse KV / SSM state), return the
    active block's hidden states.
  * :func:`serve_reuse`   — the paper's **Reuse** phase: active-block forward
    over the cached context.

VLM (`internvl2-76b`) and audio (`musicgen-medium`) archs take a stub
frontend: precomputed patch/frame embeddings occupying the first
``frontend_len`` positions (projected by a learned matrix); the LM backbone
is real. Diffusion decoding operates on the text region. On the
token-packed serving path the frontend rows ride as a fixed-length prefix
of each request's segment in the flat stream (:func:`embed_inputs_packed`),
so vlm/audio pack like every other family — no padded-oracle fallback.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import hybrid as HY
from repro.models import layers as L
from repro.models import lm_head as LM
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.sparse_select import PackedKV

ATTN_FAMILIES = ("dense", "moe", "vlm", "audio")


def mask_mode(cfg: ModelConfig) -> str:
    """Diffusion LMs are bidirectional; SSM-bearing archs are causal."""
    return "causal" if cfg.family in ("ssm", "hybrid") else "bidirectional"


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    k_e, k_s, k_f = jax.random.split(key, 3)
    params = {
        "embed": LM.init_embed(cfg, k_e, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.family in ATTN_FAMILIES:
        params["stack"] = T.init_layer_stack(cfg, k_s, dtype)
    elif cfg.family == "ssm":
        params["stack"] = S.init_ssm_stack(cfg, k_s, dtype)
    elif cfg.family == "hybrid":
        params["stack"] = HY.init_hybrid_params(cfg, k_s, dtype)
    else:
        raise ValueError(cfg.family)
    if cfg.frontend_dim:
        params["frontend"] = {
            "proj": L.dense_init(k_f, (cfg.frontend_dim, cfg.d_model), dtype)}
    return params


def embed_inputs(params: dict, cfg: ModelConfig, tokens: jax.Array,
                 frontend: Optional[jax.Array] = None) -> jax.Array:
    """tokens: [B, S_text]; frontend: [B, F, F_dim] or None -> [B, S, D]."""
    x = LM.embed_tokens(params["embed"], tokens)
    if cfg.frontend_dim:
        assert frontend is not None, f"{cfg.name} needs frontend embeddings"
        fe = jnp.einsum("bfe,ed->bfd", frontend.astype(x.dtype),
                        params["frontend"]["proj"])
        x = jnp.concatenate([fe, x], axis=1)
    return L.constrain(x, "act3d")


def embed_inputs_packed(
    params: dict,
    cfg: ModelConfig,
    flat_tokens: jax.Array,              # [T] int32 packed token stream
    cu_seqlens: jax.Array,               # [R] int32 segment start per request
    seq_lens: jax.Array,                 # [R] int32 true segment length (0=pad)
    frontend: Optional[jax.Array] = None,   # [R, F, F_dim]
) -> jax.Array:
    """Packed-stream counterpart of :func:`embed_inputs` -> [T, D].

    Each request's segment in the flat stream is ``[frontend prefix ; text]``
    (the frontend rows are a FIXED-LENGTH prefix of length
    ``cfg.frontend_len``); the projected frontend embeddings are scattered
    onto the prefix rows at ``cu_seqlens[r] + [0, F)``, overwriting the
    placeholder token embeddings the engine wrote there. Padding requests
    (``seq_lens == 0``) scatter nowhere — their rows are redirected out of
    bounds and dropped, so a bucket-exact stream's real tail rows are never
    clobbered. Text-only archs (``frontend_dim == 0``) reduce to a plain
    embedding lookup."""
    x = LM.embed_tokens(params["embed"], flat_tokens)          # [T, D]
    if cfg.frontend_dim:
        assert frontend is not None, f"{cfg.name} needs frontend embeddings"
        n_rows, D = x.shape
        F = cfg.frontend_len
        fe = jnp.einsum("rfe,ed->rfd", frontend.astype(x.dtype),
                        params["frontend"]["proj"])            # [R, F, D]
        rows = cu_seqlens[:, None] + jnp.arange(F, dtype=jnp.int32)[None]
        rows = jnp.where((seq_lens > 0)[:, None], rows, n_rows)  # pad -> OOB
        x = x.at[rows.reshape(-1)].set(fe.reshape(-1, D), mode="drop")
    return x


def _final(params, cfg, h):
    return L.rms_norm(h, params["final_norm"], cfg.rms_eps)


def _serve_chunk_cfg(cfg: ModelConfig, block_size: int) -> ModelConfig:
    """SSM chunk must divide block boundaries for state capture."""
    if cfg.family in ("ssm", "hybrid"):
        c = math.gcd(cfg.ssm_chunk, block_size)
        if c != cfg.ssm_chunk:
            return dataclasses.replace(cfg, ssm_chunk=c)
    return cfg


# ---------------------------------------------------------------------------
# training forward
# ---------------------------------------------------------------------------

def train_forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    frontend: Optional[jax.Array] = None,
    *,
    remat: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    x = embed_inputs(params, cfg, tokens, frontend)
    B, Sq, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    aux = jnp.float32(0.0)
    if cfg.family in ATTN_FAMILIES:
        h, _, aux = T.forward_full(
            params["stack"], cfg, x, positions,
            mask_mode=mask_mode(cfg), remat=remat)
    elif cfg.family == "ssm":
        body = lambda c, p: (S.mamba_block(p, c, cfg), None)
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        h, _ = jax.lax.scan(body, x, params["stack"])
    else:  # hybrid
        h, _ = HY.forward_full(params["stack"], cfg, x, positions, remat=remat)
    return _final(params, cfg, h), aux


# ---------------------------------------------------------------------------
# serving: Refresh
# ---------------------------------------------------------------------------

class RefreshOut(NamedTuple):
    block_hidden: jax.Array      # [B, Sb, D] (final-normed)
    cache: object                # PackedKV | SSMCache | HybridCache


def _slice_block(h: jax.Array, block_start: jax.Array, Sb: int) -> jax.Array:
    return jax.vmap(
        lambda hi, st: jax.lax.dynamic_slice_in_dim(hi, st, Sb, axis=0)
    )(h, block_start)


def serve_refresh(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,               # [B, S_text]
    block_start: jax.Array,          # [B] int32 (position in the FULL sequence)
    serve: T.ServeContext,
    frontend: Optional[jax.Array] = None,
    token_valid: Optional[jax.Array] = None,   # [B, S_total]
) -> RefreshOut:
    x = embed_inputs(params, cfg, tokens, frontend)
    B, Sq, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    if token_valid is None:
        token_valid = jnp.ones((B, Sq), bool)
    if cfg.family in ATTN_FAMILIES:
        h, packed, _ = T.forward_full(
            params["stack"], cfg, x, positions, token_valid=token_valid,
            mask_mode=mask_mode(cfg), serve=serve, block_start=block_start)
        cache = packed
    elif cfg.family == "ssm":
        ccfg = _serve_chunk_cfg(cfg, serve.block_size)

        def body(c, p):
            out, st, hi = S.mamba_block(p, c, ccfg, capture_at=block_start)
            return out, (st, hi)

        h, (st, hi) = jax.lax.scan(body, x, params["stack"])
        cache = S.SSMCache(state=st, conv=hi)
    else:  # hybrid
        ccfg = _serve_chunk_cfg(cfg, serve.block_size)
        h, cache = HY.forward_full(
            params["stack"], ccfg, x, positions, token_valid=token_valid,
            serve=serve, block_start=block_start)
    bh = _slice_block(_final(params, cfg, h), block_start, serve.block_size)
    return RefreshOut(block_hidden=bh, cache=cache)


def serve_refresh_packed(
    params: dict,
    cfg: ModelConfig,
    flat_tokens: jax.Array,      # [T] int32 ragged token-packed stream
    positions: jax.Array,        # [T] int32 position within owning request
    seg_ids: jax.Array,          # [T] int32 ascending request id
    token_valid: jax.Array,      # [T] bool (False on bucket padding)
    cu_seqlens: jax.Array,       # [R] int32 flat start offset per request
    seq_lens: jax.Array,         # [R] int32 true SEGMENT length per request
    block_start: jax.Array,      # [R] int32 block offset within the SEGMENT
    serve: T.ServeContext,
    frontend: Optional[jax.Array] = None,   # [R, F, F_dim] (vlm/audio)
) -> RefreshOut:
    """Token-packed Refresh (§4.1 flattened engine): one flat ``[T, ...]``
    stream replaces the padded ``[B, S]`` batch, so compute scales with real
    tokens. Attention families run the segment-masked varlen attention
    stream; SSM/hybrid families run the segment-reset varlen SSD scan (jnp
    associative-scan fallback or the Pallas ``kernels/ssm_scan`` kernel).
    Modality-frontend archs (vlm/audio) pack too: each request's segment is
    ``[frontend prefix ; text]`` (:func:`embed_inputs_packed` scatters the
    projected frontend rows onto the fixed-length prefix), so ``seq_lens``,
    ``positions``, and ``block_start`` are all expressed over the full
    prefix+text segment and the whole segment attends/selects as one
    sequence — exactly the padded oracle's geometry, minus the rectangle.
    Emits the identical per-request ``RefreshOut`` contract as
    :func:`serve_refresh` (block hidden [R, Sb, D] + per-slot cache), which
    is kept as the correctness oracle for every family on this path."""
    if cfg.frontend_dim:
        # segments are up to frontend_len longer than the text cap: widen
        # the per-request length bound that drives the select/pack gather
        # view and the windowed jnp attention fallback
        serve = dataclasses.replace(
            serve, max_seq_len=serve.max_seq_len + cfg.frontend_len)
    x = embed_inputs_packed(params, cfg, flat_tokens, cu_seqlens, seq_lens,
                            frontend)[None]                   # [1, T, D]
    x = L.constrain(x, "act3d")
    if cfg.family in ATTN_FAMILIES:
        h, cache, _ = T.forward_full_packed(
            params["stack"], cfg, x, positions[None], seg_ids[None],
            token_valid[None], cu_seqlens, seq_lens, block_start, serve)
    elif cfg.family == "ssm":
        ccfg = _serve_chunk_cfg(cfg, serve.block_size)
        use_k = bool(serve.use_flash_refresh or serve.use_flash_kernel)

        def body(c, p):
            out, st, hi = S.mamba_block_packed(
                p, c, ccfg, seg_ids, positions, cu_seqlens, block_start,
                use_kernel=use_k)
            return out, (st, hi)

        h, (st, hi) = jax.lax.scan(body, x, params["stack"])
        cache = S.SSMCache(state=st, conv=hi)
    else:  # hybrid
        ccfg = _serve_chunk_cfg(cfg, serve.block_size)
        h, cache = HY.forward_full_packed(
            params["stack"], ccfg, x, positions[None], seg_ids[None],
            token_valid[None], cu_seqlens, seq_lens, block_start, serve)
    # pin the packed hidden stream at the stage boundary: under a serving
    # mesh GSPMD otherwise inherits the vocab-sharded embedding layout into
    # the [T, D] stream and the select/pack gathers downstream of it
    hn = L.constrain(_final(params, cfg, h)[0], "packed_h")   # [T, D]
    rows = T.packed_block_rows(cu_seqlens, block_start, serve.block_size,
                               hn.shape[0])
    return RefreshOut(block_hidden=hn[rows], cache=cache)


# ---------------------------------------------------------------------------
# serving: Reuse
# ---------------------------------------------------------------------------

def _ssm_reuse(params: dict, cfg: ModelConfig, xb: jax.Array, cache):
    """Reuse-phase SSM decode over the layer stack, shared by the padded and
    packed paths — the recurrence is block-exact per request, so both
    execute the identical scan (only the batch geometry differs)."""
    def body(c, scanned):
        p, st, hi = scanned
        return S.mamba_decode_block(p, c, cfg, st, hi), None
    h, _ = jax.lax.scan(body, xb, (params["stack"], cache.state, cache.conv))
    return h


def serve_reuse_packed(
    params: dict,
    cfg: ModelConfig,
    flat_tokens: jax.Array,      # [Tq] int32 packed active-block stream
    flat_positions: jax.Array,   # [Tq] int32 absolute positions
    cache,                       # leading [L]; batch = Tq // Sb, or see rows
    serve: T.ServeContext,
    rows: Optional[jax.Array] = None,     # [R] int32 slot table
    n_live: Optional[jax.Array] = None,   # [1] int32 real requests
) -> jax.Array:
    """Token-packed Reuse (whole-iteration packing): the iteration's R active
    blocks run as ONE ragged ``[R·Sb]`` query stream against their slot
    caches (``Tq = R·Sb`` rounded to the token bucket by the engine — never
    a pow2 batch bucket). ``cache`` is either gathered (row r is request r)
    or, for the attention families' kernel path, the whole slot pool read
    in place through the slot table ``rows`` (the first ``n_live`` real;
    :func:`repro.models.transformer.forward_block_packed`). Attention
    families run the varlen cross-attention; SSM blocks decode recurrently
    from their cached states (block-exact — the packed win is the exact
    request count); hybrids
    combine both with a causal shared block. Modality-frontend archs take
    this path unchanged: the active block is always text, so the Reuse
    stream is text-only by construction — the frontend prefix participates
    only through whatever rows Refresh retained into the gathered cache
    (and through the absolute ``flat_positions``, which are offset by
    ``frontend_len``). Emits the flat ``[Tq, D]`` final-normed hidden
    stream the packed logit stage consumes directly; the padded
    :func:`serve_reuse` is kept as the correctness oracle for every family,
    same policy as Refresh."""
    Sb = serve.block_size
    Tq = flat_tokens.shape[0]
    R = Tq // Sb
    xb = LM.embed_tokens(params["embed"], flat_tokens.reshape(R, Sb))
    if cfg.family in ATTN_FAMILIES:
        h = T.forward_block_packed(params["stack"], cfg, xb,
                                   flat_positions.reshape(R, Sb), cache,
                                   serve=serve, rows=rows, n_live=n_live)
    elif cfg.family == "ssm":
        assert rows is None, "SSM Reuse reads a gathered cache"
        h = _ssm_reuse(params, cfg, xb, cache)
    else:  # hybrid
        assert rows is None, "hybrid Reuse reads a gathered cache"
        h = HY.forward_block_packed(params["stack"], cfg, xb,
                                    flat_positions.reshape(R, Sb), cache,
                                    serve=serve)
    # same boundary pin as the packed Refresh stream: the flat hidden rows
    # feed the (vocab-parallel) logit stage replicated over the mesh
    return L.constrain(_final(params, cfg, h).reshape(Tq, -1), "packed_h")


def serve_reuse(
    params: dict,
    cfg: ModelConfig,
    block_tokens: jax.Array,     # [B, Sb]
    block_positions: jax.Array,  # [B, Sb] absolute positions
    cache,
    serve: T.ServeContext,
) -> jax.Array:
    xb = LM.embed_tokens(params["embed"], block_tokens)
    if cfg.family in ATTN_FAMILIES:
        h = T.forward_block(params["stack"], cfg, xb, block_positions, cache,
                            serve=serve, mask_mode=mask_mode(cfg))
    elif cfg.family == "ssm":
        h = _ssm_reuse(params, cfg, xb, cache)
    else:  # hybrid
        h = HY.forward_block(params["stack"], cfg, xb, block_positions, cache,
                             serve=serve)
    return _final(params, cfg, h)
