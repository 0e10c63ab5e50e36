"""Jit'd public wrappers around the Pallas kernels.

:func:`_interpret` is the one place that decides how a kernel runs:
compiled by Mosaic on a TPU backend, through the Pallas interpreter
everywhere else (the CPU test mode). No kernel entry point has an
``interpret`` default of its own. Each wrapper adapts the model-layer calling
convention ([B, S, H, dh] tensors) to the kernels' head-major packed layout.

Mesh dispatch: every serving hot path consults
``jax_compat.get_active_mesh()`` at trace time (the engine activates its mesh
around each stage dispatch) and, on a model axis > 1, shard_maps the kernel
per shard — varlen attention over its local query/KV heads, the segment-reset
SSD scan over its local state heads, the fused logit argmax over its local
vocab shard with a cross-shard (max, index, logsumexp) reduce. Indivisible
head/vocab counts raise at trace time instead of silently falling back; the
engine pre-validates the same law (``launch.sharding.kernel_partition_plan``)
so serving configs fail at construction, not mid-trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.jax_compat import P
from repro.kernels.flash_attention import packed_flash_attention_call
from repro.kernels.logit_argmax import fused_logit_argmax_call
from repro.kernels.select_pack import head_score_call, head_score_varlen_call


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mesh_model():
    """(mesh, model-axis size) of the enclosing ``use_mesh`` scope.

    (None, 1) when no mesh — or no ``model`` axis — is active at trace time,
    which keeps the no-mesh path byte-for-byte the single-device dispatch.
    A 1-sized model axis also dispatches locally (bit-identical 1×1 law)."""
    from repro.jax_compat import get_active_mesh
    mesh = get_active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None, 1
    m = mesh.shape["model"]
    return (mesh, m) if m > 1 else (None, 1)


def _require_divisible(kernel: str, **dims) -> None:
    """Fail-loud divisibility law for per-shard kernel dispatch (mirrors
    ``launch.sharding.kernel_partition_plan``): never silently fall back."""
    m = dims.pop("m")
    bad = [f"{k}={v}" for k, v in dims.items() if v % m]
    if bad:
        raise ValueError(
            f"{kernel} cannot partition over the {m}-way model axis: "
            f"{', '.join(bad)} must divide it exactly")


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def fused_logit_argmax(h, w, *, softcap: float = 0.0, vocab_tile: int = 512,
                       t_tile: int = 256, w_layout: str = "dv", valid=None):
    """h: [T, D]; w: [D, V] ("dv") or [V, D] ("vd", tied-embedding table).
    Returns (ids [T] i32, conf [T] f32). Paper C1, fused.

    ``valid`` ([T] bool, optional) marks real rows of a token-bucketed packed
    stream: the kernel skips the V loop of all-padding T-tiles entirely and
    invalid rows decode to (0, 0.0)."""
    T = h.shape[0]
    V = w.shape[1] if w_layout == "dv" else w.shape[0]
    t_tile = min(t_tile, max(8, T))
    hp, _ = _pad_to(h, t_tile, 0)
    vld = jnp.ones((T,), bool) if valid is None else valid
    vp, _ = _pad_to(vld, t_tile, 0)
    mesh, msize = _mesh_model()
    if mesh is not None:
        ids, m, s = _sharded_logit_argmax(
            hp, w, vp, mesh, msize, V, softcap=softcap, t_tile=t_tile,
            vocab_tile=vocab_tile, w_layout=w_layout)
    else:
        # the vocab tile must divide V: zero padding would fabricate
        # logit-0 columns, and a jnp stand-in would hide that the kernel
        # never ran — an indivisible vocab is a configuration error
        vt = min(vocab_tile, V)
        while V % vt:
            vt //= 2
            if vt < 8:
                raise ValueError(
                    f"fused logit argmax: no >=8-column vocab tile divides "
                    f"the vocab {V}")
        ids, m, s = fused_logit_argmax_call(
            hp, w, vp, softcap=softcap, t_tile=t_tile, v_tile=vt,
            interpret=_interpret(), w_layout=w_layout)
    conf = 1.0 / jnp.maximum(s, 1e-30)
    ids, conf = ids[:T], conf[:T]
    if valid is not None:
        ids = jnp.where(valid, ids, 0)
        conf = jnp.where(valid, conf, 0.0)
    return ids, conf


def _sharded_logit_argmax(hp, w, vp, mesh, msize, V, *, softcap, t_tile,
                          vocab_tile, w_layout):
    """Vocab-sharded fused argmax: each model shard runs the Pallas kernel
    over its local [T, V/m] vocab slice, then a cheap cross-shard reduce
    merges (max, argmax-index, logsumexp) — pmax for the running max, pmin
    over offset-shifted indices among max-achieving shards (preserving the
    single-device lowest-index tie-break, since a lower shard id means a
    lower global vocab offset), and a psum of the rescaled softmax sums."""
    _require_divisible("fused logit argmax", m=msize, vocab_size=V)
    v_loc = V // msize
    vt = min(vocab_tile, v_loc)
    while v_loc % vt:
        vt //= 2
        if vt < 8:
            raise ValueError(
                "fused logit argmax: no >=8-column vocab tile divides the "
                f"per-shard vocab {v_loc} (vocab {V} over {msize} shards)")
    from repro.jax_compat import shard_map as _shard_map
    w_spec = P(None, "model") if w_layout == "dv" else P("model", None)
    interp = _interpret()

    def local(hp_l, w_l, vp_l):
        ids, m, s = fused_logit_argmax_call(
            hp_l, w_l, vp_l, softcap=softcap, t_tile=t_tile, v_tile=vt,
            interpret=interp, w_layout=w_layout)
        off = jax.lax.axis_index("model").astype(jnp.int32) * v_loc
        gids = ids.astype(jnp.int32) + off
        m_max = jax.lax.pmax(m, "model")
        big = jnp.int32(jnp.iinfo(jnp.int32).max)
        gid = jax.lax.pmin(jnp.where(m == m_max, gids, big), "model")
        s_g = jax.lax.psum(s * jnp.exp(m - m_max), "model")
        return gid, m_max, s_g

    return _shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None), w_spec, P(None)),
        out_specs=(P(None), P(None), P(None)),
        check_vma=False,
    )(hp, w, vp)


def packed_flash_attention_stats(qr, k_all, v_all, ok, *, softcap: float = 0.0,
                                 t_tile: int = 512):
    """Raw flash statistics for exact split-attention merging.

    qr: [B, K, R, dh] (rows = Sb·G); returns (o_unnorm f32 [B,K,R,dh],
    m [B,K,R], s [B,K,R]).
    """
    T = k_all.shape[2]
    tt = min(t_tile, T)
    while T % tt:
        tt //= 2
    return packed_flash_attention_call(
        qr, k_all, v_all, ok, softcap=softcap, t_tile=tt,
        interpret=_interpret())


def packed_flash_attention(q, k_all, v_all, ok, *, softcap: float = 0.0,
                           t_tile: int = 512):
    """Model-layer contract (see ``transformer._attend_packed``):

    q: [B, Sb, H, dh]; k_all/v_all: [B, K, T, dh]; ok: [B, K, Sb, T] bool.
    Returns [B, Sb, H, dh].
    """
    B, Sb, H, dh = q.shape
    K, T = k_all.shape[1], k_all.shape[2]
    G = H // K
    qr = (q.reshape(B, Sb, K, G, dh).transpose(0, 2, 1, 3, 4)
          .reshape(B, K, Sb * G, dh))
    tt = min(t_tile, T)
    while T % tt:
        tt //= 2
    out, m, s = packed_flash_attention_call(
        qr, k_all, v_all, ok, softcap=softcap, t_tile=tt,
        interpret=_interpret())
    out = out / jnp.maximum(s, 1e-30)[..., None]
    out = (out.reshape(B, K, Sb, G, dh).transpose(0, 2, 1, 3, 4)
           .reshape(B, Sb, H, dh))
    return out.astype(q.dtype)


def flash_refresh_attention(q, k, v, *, q_pos, kv_pos, kv_valid, mask_mode,
                            window, is_local, softcap, q_tile: int = 256,
                            kv_tile: int = 512):
    """Refresh-phase flash attention (model-layer contract).

    q: [B, S, H, dh]; k/v: [B, S, K, dh]; returns [B, S, H, dh].
    Under an active mesh the call is shard_mapped: batch over the data axes
    and heads over 'model' when H divides it (each shard slices its KV-head
    range locally; KV stays replicated over 'model' — GQA KV heads below the
    TP degree are replicated anyway).
    """
    import numpy as np
    from repro.kernels.flash_refresh import flash_refresh_call

    B, S, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    causal = mask_mode == "causal"
    loc = jnp.asarray(is_local, bool).reshape(1)

    qh = q.transpose(0, 2, 1, 3)        # [B, H, S, dh]
    kh = k.transpose(0, 2, 1, 3)        # [B, K, S, dh]
    vh = v.transpose(0, 2, 1, 3)

    def local_call(q_l, k_l, v_l, qp, kp, kv, lc, *, h_shards: int = 1):
        H_loc, Sq = q_l.shape[1], q_l.shape[2]
        if h_shards > 1:
            idx = jax.lax.axis_index("model")
            K_eff = max(1, H_loc // G)
            kv_start = (idx * H_loc) // G
            k_l = jax.lax.dynamic_slice_in_dim(k_l, kv_start, K_eff, axis=1)
            v_l = jax.lax.dynamic_slice_in_dim(v_l, kv_start, K_eff, axis=1)
        else:
            K_eff = K
        G_eff = H_loc // K_eff
        Bl = q_l.shape[0]
        qr = (q_l.reshape(Bl, K_eff, G_eff, Sq, dh).transpose(0, 1, 3, 2, 4)
              .reshape(Bl, K_eff, Sq * G_eff, dh))
        out = flash_refresh_call(
            qr, k_l, v_l, qp, kp, kv, lc, softcap=softcap, causal=causal,
            window=window, q_tile=min(q_tile, Sq),
            kv_tile=min(kv_tile, k_l.shape[2]),
            interpret=_interpret())
        out = (out.reshape(Bl, K_eff, Sq, G_eff, dh).transpose(0, 1, 3, 2, 4)
               .reshape(Bl, H_loc, Sq, dh))
        return out.astype(q_l.dtype)

    from repro.jax_compat import get_active_mesh, shard_map as _shard_map
    mesh = get_active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        out = local_call(qh, kh, vh, q_pos, kv_pos, kv_valid, loc)
    else:
        m = mesh.shape["model"]
        dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        import functools as ft
        if H % m == 0:
            # TP over heads; each shard slices its KV-head range locally
            fn = ft.partial(local_call, h_shards=m)
            q_spec = out_spec = P(dp, "model", None, None)
            qp_spec = P(dp, None)
        elif S % m == 0:
            # heads don't divide the TP axis (e.g. H=40 on 16): shard the
            # QUERY sequence axis instead — every query row's output is
            # complete against the replicated KV, so no psum is needed.
            # §Perf iteration C2: engages idle TP compute for refresh.
            fn = local_call
            q_spec = out_spec = P(dp, None, "model", None)
            qp_spec = P(dp, "model")
        else:
            fn = local_call
            q_spec = out_spec = P(dp, None, None, None)
            qp_spec = P(dp, None)
        out = _shard_map(
            fn, mesh=mesh,
            in_specs=(q_spec, P(dp, None, None, None),
                      P(dp, None, None, None), qp_spec, P(dp, None),
                      P(dp, None), P(None)),
            out_specs=out_spec,
            check_vma=False,
        )(qh, kh, vh, q_pos, kv_pos, kv_valid, loc)
    return out.transpose(0, 2, 1, 3)    # back to [B, S, H, dh]


def flash_varlen_attention(q, k, v, *, seg_ids, positions, kv_valid,
                           window: int = 0, is_local=False,
                           softcap: float = 0.0, causal: bool = False,
                           q_tile: int = 256, kv_tile: int = 512):
    """Ragged flash attention over a token-packed stream (model contract).

    q: [T, H, dh]; k/v: [T, K, dh]; seg_ids/positions: [T] int32 (segment id
    ascending, position within the owning request); kv_valid: [T] bool.
    Returns [T, H, dh]. One flat dispatch replaces the padded [B, S] batch;
    cross-request attention is masked in-kernel via segment ids and
    non-intersecting tiles are skipped (FLOPs ~ Σ Sᵢ², not T²).
    """
    from repro.kernels.flash_varlen import flash_varlen_call

    T, H, dh = q.shape
    K = k.shape[1]
    qt = min(q_tile, T)
    while T % qt:
        qt //= 2
    kt = min(kv_tile, T)
    while T % kt:
        kt //= 2
    loc = jnp.asarray(is_local, bool).reshape(1)
    interp = _interpret()

    def local_call(q_l, k_l, v_l, pos, seg, kvv, lc):
        # per-shard geometry: contiguous H/m query-head blocks align with
        # K/m KV-head blocks (both divide), so GQA grouping is shard-local
        H_l, K_l = q_l.shape[1], k_l.shape[1]
        G_l = H_l // K_l
        qr = (q_l.reshape(T, K_l, G_l, dh).transpose(1, 0, 2, 3)
              .reshape(K_l, T * G_l, dh))
        out = flash_varlen_call(
            qr, k_l.transpose(1, 0, 2), v_l.transpose(1, 0, 2),
            pos.astype(jnp.int32), seg.astype(jnp.int32), kvv, lc,
            softcap=softcap, causal=causal, window=window,
            q_tile=qt, kv_tile=kt, interpret=interp)
        out = (out.reshape(K_l, T, G_l, dh).transpose(1, 0, 2, 3)
               .reshape(T, H_l, dh))
        return out.astype(q_l.dtype)

    mesh, msize = _mesh_model()
    if mesh is None:
        return local_call(q, k, v, positions, seg_ids, kv_valid, loc)
    _require_divisible("varlen flash attention", m=msize, n_heads=H,
                       n_kv_heads=K)
    from repro.jax_compat import shard_map as _shard_map
    h_spec = P(None, "model", None)
    return _shard_map(
        local_call, mesh=mesh,
        in_specs=(h_spec, h_spec, h_spec, P(None), P(None), P(None), P(None)),
        out_specs=h_spec,
        check_vma=False,
    )(q, k, v, positions, seg_ids, kv_valid, loc)


def flash_varlen_cross_attention(q, k, v, *, q_seg, q_pos, kv_seg, kv_pos,
                                 kv_valid, window: int = 0, is_local=False,
                                 softcap: float = 0.0, causal: bool = False,
                                 q_tile: int = 128, kv_tile: int = 512):
    """Packed-Reuse cross attention (model contract).

    q: [Tq, H, dh] flat packed block queries; k/v: [K, Tkv, dh] head-major
    flat KV stream ([retain ; live block] per request, requests contiguous);
    q_seg/q_pos: [Tq] int32; kv_seg: [Tkv] int32; kv_pos/kv_valid: [K, Tkv]
    (head-centric selection retains different tokens per KV head). Returns
    [Tq, H, dh]. One flat dispatch replaces the pow2-bucketed [B, Sb] Reuse
    batch; non-owned KV tiles are skipped in-kernel. The hybrid family's
    Reuse runs it over a gathered cache; the attention families read the
    pool in place (:func:`flash_varlen_pool_attention`).
    """
    from repro.kernels.flash_varlen import flash_varlen_cross_call

    Tq, H, dh = q.shape
    K, Tkv = k.shape[0], k.shape[1]
    qt = min(q_tile, Tq)
    while Tq % qt:
        qt //= 2
    kt = min(kv_tile, Tkv)
    while Tkv % kt:
        kt //= 2
    loc = jnp.asarray(is_local, bool).reshape(1)
    interp = _interpret()

    def local_call(q_l, k_l, v_l, qp, kvp, qs, kvs, kvv, lc):
        H_l, K_l = q_l.shape[1], k_l.shape[0]
        G_l = H_l // K_l
        qr = (q_l.reshape(Tq, K_l, G_l, dh).transpose(1, 0, 2, 3)
              .reshape(K_l, Tq * G_l, dh))
        out = flash_varlen_cross_call(
            qr, k_l, v_l, qp.astype(jnp.int32), kvp.astype(jnp.int32),
            qs.astype(jnp.int32), kvs.astype(jnp.int32), kvv, lc,
            softcap=softcap, causal=causal, window=window,
            q_tile=qt, kv_tile=kt, interpret=interp)
        out = (out.reshape(K_l, Tq, G_l, dh).transpose(1, 0, 2, 3)
               .reshape(Tq, H_l, dh))
        return out.astype(q_l.dtype)

    mesh, msize = _mesh_model()
    if mesh is None:
        return local_call(q, k, v, q_pos, kv_pos, q_seg, kv_seg,
                          kv_valid, loc)
    # the head-major KV stream is already head-sharded ([K, Tkv, dh] built
    # from the Rules.cache head-sharded pool) — each shard consumes its
    # local KV heads directly, no all-gather of KV
    _require_divisible("varlen cross attention", m=msize, n_heads=H,
                       n_kv_heads=K)
    from repro.jax_compat import shard_map as _shard_map
    return _shard_map(
        local_call, mesh=mesh,
        in_specs=(P(None, "model", None), P("model", None, None),
                  P("model", None, None), P(None), P("model", None),
                  P(None), P(None), P("model", None), P(None)),
        out_specs=P(None, "model", None),
        check_vma=False,
    )(q, k, v, q_pos, kv_pos, q_seg, kv_seg, kv_valid, loc)


def _pool_tile(Cr: int, kv_tile: int) -> int:
    """Retained-axis tile of the pool kernel: the whole axis when it fits
    ``kv_tile``, else its largest divisor that is a multiple of 16 and fits;
    the whole axis when none does."""
    if Cr <= kv_tile:
        return Cr
    return max((d for d in range(16, kv_tile + 1, 16) if Cr % d == 0),
               default=Cr)


def retained_positions(pos, valid, rows, *, kv_tile: int = 1024):
    """The pool kernel's view of the slot table's retained positions.

    pos/valid: ``[L, S, K, Cr]`` pool (or gathered) leaves; rows: ``[R]``
    int32 slot table. Returns ``[L, R, K, n_c, 1, ct]`` int32, -1 where a
    row retains nothing, laid out so that one ``(1, ct)`` block is a whole
    tile. The leaves are gathered for the table's rows (a Mosaic operand
    cannot be bool, and ``(1, Cr)`` rows of a ``[.., K, Cr]`` leaf break the
    TPU block rule); at 4 or 5 bytes a position this is ~1% of the K/V the
    kernel reads in place. Built once per program, outside the layer scan.
    """
    L, _, K, Cr = pos.shape
    ct = _pool_tile(Cr, kv_tile)
    kp = jnp.where(valid[:, rows], pos[:, rows], -1).astype(jnp.int32)
    return kp.reshape(L, rows.shape[0], K, Cr // ct, 1, ct)


def flash_varlen_pool_attention(q, k_blk, v_blk, pool_k, pool_v, kv_pos, *,
                                rows, n_live, layer, q_pos, window: int = 0,
                                is_local=False, softcap: float = 0.0):
    """Bidirectional packed-Reuse cross attention over the slot pool, in
    place (model contract).

    q: [R·Sb, H, dh] packed block queries; k_blk/v_blk: [R·Sb, K, dh] the
    live blocks' K/V; pool_k/pool_v: [L, S, K, Cr, dh] whole pool leaves;
    kv_pos: :func:`retained_positions` of the slot table ``rows`` ([R]
    int32); n_live: [1] int32, the leading real requests; layer: the layer
    index into the pool's [L] axis; q_pos: [R·Sb]. Returns [R·Sb, H, dh].
    Under a model axis each shard reads its own KV heads of the
    head-sharded pool (``Rules.cache``): nothing is gathered across shards.
    """
    from repro.kernels.flash_varlen import flash_varlen_pool_call

    T, H, dh = q.shape
    K = k_blk.shape[1]
    loc = jnp.asarray(is_local, bool).reshape(1)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    interp = _interpret()

    def local_call(q_l, kb_l, vb_l, pk, pv, kp, rw, nl, ly, lc, qp):
        H_l, K_l = q_l.shape[1], kb_l.shape[1]
        G_l = H_l // K_l
        qr = (q_l.reshape(T, K_l, G_l, dh).transpose(1, 0, 2, 3)
              .reshape(K_l, T * G_l, dh))
        out = flash_varlen_pool_call(
            qr, kb_l.transpose(1, 0, 2), vb_l.transpose(1, 0, 2), qp, pk,
            pv, kp, rw, nl, ly, lc, softcap=softcap, window=window,
            interpret=interp)
        out = (out.reshape(K_l, T, G_l, dh).transpose(1, 0, 2, 3)
               .reshape(T, H_l, dh))
        return out.astype(q_l.dtype)

    args = (q, k_blk, v_blk, pool_k, pool_v, kv_pos, rows, n_live, lyr, loc,
            q_pos)
    mesh, msize = _mesh_model()
    if mesh is None:
        return local_call(*args)
    _require_divisible("varlen pool attention", m=msize, n_heads=H,
                       n_kv_heads=K)
    from repro.jax_compat import shard_map as _shard_map
    h_spec = P(None, "model", None)
    pool_spec = P(None, None, "model", None, None)
    return _shard_map(
        local_call, mesh=mesh,
        in_specs=(h_spec, h_spec, h_spec, pool_spec, pool_spec,
                  P(None, None, "model", None, None, None), P(None),
                  P(None), P(None), P(None), P(None)),
        out_specs=h_spec,
        check_vma=False,
    )(*args)


def ssm_segment_scan(xh, dt, A, Bm, Cm, reset, cap_rows, *, chunk: int = 64):
    """Segment-reset SSD scan over a packed stream (model contract).

    xh: [T, H, P]; dt: [T, H] f32 (post-softplus); A: [H] (negative);
    Bm/Cm: [T, N]; reset: [T] bool (True on each request's first token);
    cap_rows: [R] int32 flat row AFTER which request r's state is captured
    (−1 → zero state). Returns (y [T, H, P] f32, states [R, H, P, N] f32).
    One flat dispatch replaces the padded ``[B, max_seq_len]`` scan — the
    recurrent state resets at segment boundaries in-kernel and the captured
    states are accumulated without materializing per-token states.
    """
    from repro.kernels.ssm_scan import ssm_segment_scan_call

    T, H = xh.shape[0], xh.shape[1]
    f32 = jnp.float32
    ct = min(chunk, T)
    while T % ct:
        ct //= 2
    dtf = dt.astype(f32)
    xdt = xh.astype(f32) * dtf[..., None]
    dA = dtf * A.astype(f32)[None, :]
    interp = _interpret()

    def local_call(xdt_l, dA_l, Bm_l, Cm_l, reset_l, cap_l):
        y, cap, _ = ssm_segment_scan_call(
            xdt_l, dA_l, Bm_l, Cm_l, reset_l, cap_l, chunk=ct,
            interpret=interp)
        return y, cap

    mesh, msize = _mesh_model()
    if mesh is None:
        return local_call(xdt, dA, Bm.astype(f32), Cm.astype(f32),
                          reset.astype(f32), cap_rows.astype(jnp.int32))
    # shard the state-head axis; each shard scans and captures its local
    # [R, H/m, P, N] states — matching the Rules.ssm_cache head-sharded pool
    _require_divisible("varlen SSD scan", m=msize, ssm_heads=H)
    from repro.jax_compat import shard_map as _shard_map
    return _shard_map(
        local_call, mesh=mesh,
        in_specs=(P(None, "model", None), P(None, "model"), P(None, None),
                  P(None, None), P(None), P(None)),
        out_specs=(P(None, "model", None), P(None, "model", None, None)),
        check_vma=False,
    )(xdt, dA, Bm.astype(f32), Cm.astype(f32), reset.astype(f32),
      cap_rows.astype(jnp.int32))


def head_score(q_block, k_full, *, s_tile: int = 512):
    """q_block: [B, Sb, H, dh]; k_full: [B, S, K, dh] -> [B, K, S] f32 raw
    (pre-maxpool) importance scores — kernel side of paper C3 eq.(6)."""
    B, Sb, H, dh = q_block.shape
    K, S = k_full.shape[2], k_full.shape[1]
    G = H // K
    qr = (q_block.reshape(B, Sb, K, G, dh).transpose(0, 2, 1, 3, 4)
          .reshape(B, K, Sb * G, dh))
    kr = k_full.transpose(0, 2, 1, 3)
    st = min(s_tile, S)
    while S % st:
        st //= 2
    return head_score_call(qr, kr, s_tile=st, interpret=_interpret())


def head_score_varlen(q_block, k_flat, seg_ids, *, s_tile: int = 512):
    """q_block: [R, Sb, H, dh]; k_flat: [T, K, dh] flat packed stream;
    seg_ids: [T] int32 -> [R, K, T] f32 raw scores (-inf off-segment).
    Tile-skipping varlen side of paper C3 eq.(6) — no padded K gather."""
    R, Sb, H, dh = q_block.shape
    T, K = k_flat.shape[0], k_flat.shape[1]
    st = min(s_tile, T)
    while T % st:
        st //= 2
    interp = _interpret()

    def local_call(q_l, k_l, seg):
        H_l, K_l = q_l.shape[2], k_l.shape[1]
        G_l = H_l // K_l
        qr = (q_l.reshape(R, Sb, K_l, G_l, dh).transpose(0, 2, 1, 3, 4)
              .reshape(R, K_l, Sb * G_l, dh))
        return head_score_varlen_call(qr, k_l.transpose(1, 0, 2),
                                      seg.astype(jnp.int32), s_tile=st,
                                      interpret=interp)

    mesh, msize = _mesh_model()
    if mesh is None:
        return local_call(q_block, k_flat, seg_ids)
    _require_divisible("varlen head-score", m=msize, n_heads=H,
                       n_kv_heads=K)
    from repro.jax_compat import shard_map as _shard_map
    return _shard_map(
        local_call, mesh=mesh,
        in_specs=(P(None, None, "model", None), P(None, "model", None),
                  P(None)),
        out_specs=P(None, "model", None),
        check_vma=False,
    )(q_block, k_flat, seg_ids)


def dequantize_gathered(gathered, kv_quant: str, dtypes):
    """KV-load dequantization point for the Reuse stages (docs/memory.md).

    Under ``ServeConfig.kv_quant="int8"`` the slot pool's gather returns
    the QUANTIZED view ``{"data": int8-leaf tree, "scale": per-leaf
    [L, B] f32}`` so the pool — and the HBM traffic across the gather —
    stays int8; this helper, called at the top of every Reuse stage jit
    (packed varlen kernels and the padded jnp oracle alike), scales the KV
    leaves back to ``dtypes`` inside the SAME XLA program as the attention
    kernels, so the dequantized tensors are transient activations fused
    into the kernel's KV load, never pool state.

    The unquantized path passes the gathered cache through untouched —
    billed as itself (the bit-exact oracle); there is no silent third mode
    (`KVPool` validates ``kv_quant`` at construction).
    """
    if kv_quant == "none":
        return gathered
    from repro.kernels.kv_quant import dequantize_slot_leaves
    return dequantize_slot_leaves(gathered["data"], gathered["scale"],
                                  dtypes)
