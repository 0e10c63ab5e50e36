"""LLaDA's block: bidirectional attention with RoPE over grouped KV heads,
a SwiGLU MLP, RMSNorm before each.

The reference replays the paper's serving algorithm. At a Refresh step, a
full bidirectional forward over the whole sequence (prompt, finished
blocks, the current block's state, masked future blocks); each layer then
keeps, per KV head, the ``retain`` highest-scoring positions outside the
block (score: the block's queries against each key, max over the group's
heads and the block, max-pooled over ``kernel_size`` neighbours). A Reuse
step runs the block alone against the kept keys and values of its last
Refresh plus the block's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import F32, layer_params, lin, rms, round_up
from chipbench.weights import STD

FIELDS = dict(n_heads=0, n_kv_heads=0, head_dim=0, d_ff=0,
              rope_theta=10_000.0)

REHEARSAL = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, d_ff=256,
                 vocab_size=4096, head_dim=32, dtype="bfloat16")


def layout(d: dict) -> dict:
    L, D, F = d["n_layers"], d["d_model"], d["d_ff"]
    H, K, dh = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    return {
        "attn_norm": ((L, D), 0.0), "mlp_norm": ((L, D), 0.0),
        "wq": ((L, D, H, dh), STD), "wk": ((L, D, K, dh), STD),
        "wv": ((L, D, K, dh), STD), "wo": ((L, H, dh, D), STD),
        "w_gate": ((L, D, F), STD), "w_up": ((L, D, F), STD),
        "w_down": ((L, F, D), STD),
    }


def matmul_flops_per_token(d: dict) -> float:
    """Projection and MLP operations per token through every layer."""
    D, L = d["d_model"], d["n_layers"]
    H, K, dh, F = d["n_heads"], d["n_kv_heads"], d["head_dim"], d["d_ff"]
    return 2.0 * L * (D * H * dh + 2 * D * K * dh + H * dh * D + 3 * D * F)


def attention_flops(d: dict, queries: int, keys: int) -> float:
    """``4 T Sk H dh`` over every layer."""
    return 4.0 * queries * keys * d["n_heads"] * d["head_dim"] * d["n_layers"]


def groups(n_steps: int, refresh_interval: int) -> list:
    """A Refresh at the block's first step and every ``refresh_interval``
    steps; the steps between reuse what it kept."""
    out = []
    for s in range(n_steps):
        if s == 0 or (refresh_interval and s % refresh_interval == 0):
            out.append((s, []))
        else:
            out[-1][1].append(s)
    return out


def qkv(p, h, quant):
    """The layer's query, key and value projections, before RoPE."""
    return (lin(h, p["wq"], 1, quant), lin(h, p["wk"], 1, quant),
            lin(h, p["wv"], 1, quant))


def _rope(x, pos, theta):
    """Rotate-half RoPE; x [..., S, H, dh], pos [..., S]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[..., None] * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(p, x, quant):
    g = jax.nn.silu(lin(x, p["w_gate"], 1, quant))
    u = lin(x, p["w_up"], 1, quant)
    return lin(g * u, p["w_down"], 1, quant)


def _maxpool(raw, w):
    out = raw
    for off in range(1, w // 2 + 1):
        pad = jnp.full(raw.shape[:-1] + (off,), -jnp.inf, raw.dtype)
        out = jnp.maximum(out, jnp.concatenate([raw[..., off:], pad], -1))
        out = jnp.maximum(out, jnp.concatenate([pad, raw[..., :-off]], -1))
    return out


@functools.partial(jax.jit, static_argnames=("d", "quant", "retain", "sb",
                                             "ksize", "proj"))
def _refresh_layer(stack, l, x, valid, bstart, *, d, quant, retain, sb,
                   ksize, proj):
    """One layer over the whole (padded) sequence, plus what it retains.
    x [Lp, D]; valid [Lp] (False on padding); bstart scalar."""
    H, K, dh, eps, theta = d
    p = layer_params(stack, l)
    Lp = x.shape[0]
    pos = jnp.arange(Lp, dtype=jnp.int32)
    h = rms(x, p["attn_norm"], eps)
    q, k, v = proj(p, h, quant)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    G = H // K
    qg = q.reshape(Lp, K, G, dh)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k) * dh ** -0.5
    s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
    o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, -1), v)
    x = x + lin(o.reshape(Lp, H, dh), p["wo"], 2, quant)
    x = x + _mlp(p, rms(x, p["mlp_norm"], eps), quant)
    # head-centric retention for the Reuse steps that follow
    qb = jax.lax.dynamic_slice_in_dim(qg, bstart, sb, axis=0)
    raw = jnp.einsum("qkgd,skd->kgqs", qb, k).max(axis=(1, 2))   # [K, Lp]
    raw = _maxpool(jnp.where(valid[None], raw, -jnp.inf), ksize)
    in_blk = (pos >= bstart) & (pos < bstart + sb)
    excl = in_blk | ~valid
    sc = jnp.where(excl[None], -1e30, raw)
    _, idx = jax.lax.top_k(sc, retain)
    idx = jnp.sort(idx, axis=-1)                                  # [K, R]
    kh = k.transpose(1, 0, 2)
    vh = v.transpose(1, 0, 2)
    ar = jnp.arange(K)[:, None]
    kept = (kh[ar, idx], vh[ar, idx], valid[idx] & ~excl[idx])
    return x, kept


@functools.partial(jax.jit, static_argnames=("d", "quant", "proj"))
def _reuse_layer(stack, l, x, pos, kr, vr, rvalid, *, d, quant, proj):
    """One layer over n block states [n, Sb, D] against the kept keys and
    values [K, R, dh] of the last Refresh plus the block's own."""
    H, K, dh, eps, theta = d
    p = layer_params(stack, l)
    n, sb, _ = x.shape
    h = rms(x, p["attn_norm"], eps)
    q, k, v = proj(p, h, quant)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    G = H // K
    keys = jnp.concatenate([jnp.broadcast_to(kr, (n,) + kr.shape),
                            k.transpose(0, 2, 1, 3)], axis=2)
    vals = jnp.concatenate([jnp.broadcast_to(vr, (n,) + vr.shape),
                            v.transpose(0, 2, 1, 3)], axis=2)
    ok = jnp.concatenate([rvalid, jnp.ones((K, sb), bool)], axis=1)
    qg = q.reshape(n, sb, K, G, dh)
    s = jnp.einsum("nqkgd,nktd->nkgqt", qg, keys) * dh ** -0.5
    s = jnp.where(ok[None, :, None, None, :], s, -jnp.inf)
    o = jnp.einsum("nkgqt,nktd->nqkgd", jax.nn.softmax(s, -1), vals)
    x = x + lin(o.reshape(n, sb, H, dh), p["wo"], 2, quant)
    return x + _mlp(p, rms(x, p["mlp_norm"], eps), quant)


def block_hidden(ref, ctx, steps, refresh_s, reuse_ss, bstart, total_len,
                 quant, proj=qkv):
    """Final-layer hidden rows [Sb, D] (before the final norm) of the
    block at ``refresh_s`` and at each step of ``reuse_ss``. ``proj`` is
    the layer's q, k, v projection (:func:`qkv`); a module of a variant
    block passes its own."""
    d, s = ref.d, ref.s
    dk = (d["n_heads"], d["n_kv_heads"], d["head_dim"], float(d["rms_eps"]),
          float(d["rope_theta"]))
    sb, retain, st = s["block_size"], s["retain"], ref.p["stack"]
    seq = ref.seq(ctx, steps[refresh_s][0], total_len)
    Lp = round_up(max(len(seq), retain), 128)
    ids = np.zeros(Lp, np.int32)
    ids[:len(seq)] = seq
    valid = jnp.asarray(np.arange(Lp) < len(seq))
    x = ref.embed(ids)
    kept = []
    for l in range(d["n_layers"]):
        x, kv = _refresh_layer(st, l, x, valid, bstart, d=dk, quant=quant,
                               retain=retain, sb=sb,
                               ksize=s["kernel_size"], proj=proj)
        kept.append(kv)
    out = [x[bstart:bstart + sb]]
    if reuse_ss:
        xb = ref.embed(np.stack([steps[i][0] for i in reuse_ss]))
        pos = jnp.broadcast_to(jnp.arange(bstart, bstart + sb),
                               xb.shape[:2])
        for l in range(d["n_layers"]):
            xb = _reuse_layer(st, l, xb, pos, *kept[l], d=dk, quant=quant,
                              proj=proj)
        out += list(xb)
    return out
