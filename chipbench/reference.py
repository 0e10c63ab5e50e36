"""Plain float32 reference of the served models, and its fp8 control.

It imports nothing of the program. It reads the benchmark's own weights
(``weights.py``) and sizes (``config.dims``) and replays the denoising
trajectory that the timed path served, step by step, teacher-forced: at
each step the block holds exactly the tokens the engine had committed
before that step, so the reference's logits at the positions the engine
committed are comparable with the tokens it committed there.

Semantics reproduced (the paper's serving algorithm, not a shortcut):

* attention family (LLaDA): at a Refresh step, a full bidirectional
  forward over the whole sequence (prompt, finished blocks, the current
  block's state, masked future blocks); each layer then keeps, per KV head,
  the ``retain`` highest-scoring positions outside the block (score: the
  block's queries against each key, max over the group's heads and the
  block, max-pooled over ``kernel_size`` neighbours). A Reuse step runs the
  block alone against the kept keys and values of its last Refresh plus the
  block's own.
* SSM family (Mamba2): causal, so every step is the causal forward of the
  prefix through the block's end; the recurrent state and the conv history
  are taken exactly at the block's start.

Matmuls run in float32 at ``highest`` precision. The control
(``quant="fp8"``) puts an fp8 (e4m3) copy in the program's place: every
linear layer's weights (per output channel) and inputs (per row) are
rounded to e4m3 with an absmax scale, attention stays float32.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
E4M3_MAX = 448.0


def _e4m3(x):
    """Round float32 to the nearest fp8 e4m3 value (3 mantissa bits,
    normals down to 2^-6, subnormals in steps of 2^-9, saturating at 448),
    ties to even; done in float32 so no fp8 type is needed on the chip."""
    _, e = jnp.frexp(x)                       # x = m 2^e, 0.5 <= |m| < 1
    q = jnp.exp2((jnp.maximum(e - 1, -6) - 3).astype(F32))
    return jnp.clip(jnp.round(x / q) * q, -E4M3_MAX, E4M3_MAX)


def _fq(x, axes):
    """Round to fp8 e4m3 with an absmax scale over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return _e4m3(x / s) * s


def _lin(x, w, n_in: int, quant: Optional[str]):
    """x [..., in...] times w [in..., out...] over ``n_in`` leading dims."""
    if quant == "fp8":
        x = _fq(x, tuple(range(x.ndim - n_in, x.ndim)))
        w = _fq(w, tuple(range(n_in)))
    return jnp.tensordot(x, w, axes=n_in)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    """Rotate-half RoPE; x [..., S, H, dh], pos [..., S]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[..., None] * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(stack, l):
    return {k: v[l].astype(F32) for k, v in stack.items()}


def _mlp(p, x, quant):
    g = jax.nn.silu(_lin(x, p["w_gate"], 1, quant))
    u = _lin(x, p["w_up"], 1, quant)
    return _lin(g * u, p["w_down"], 1, quant)


def _maxpool(raw, w):
    out = raw
    for off in range(1, w // 2 + 1):
        pad = jnp.full(raw.shape[:-1] + (off,), -jnp.inf, raw.dtype)
        out = jnp.maximum(out, jnp.concatenate([raw[..., off:], pad], -1))
        out = jnp.maximum(out, jnp.concatenate([pad, raw[..., :-off]], -1))
    return out


# ---------------------------------------------------------------------------
# attention family
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d", "quant", "retain", "sb",
                                             "ksize"))
def _refresh_layer(stack, l, x, valid, bstart, *, d, quant, retain, sb,
                   ksize):
    """One layer over the whole (padded) sequence, plus what it retains.
    x [Lp, D]; valid [Lp] (False on padding); bstart scalar."""
    H, K, dh, eps, theta = d
    p = _layer(stack, l)
    Lp = x.shape[0]
    pos = jnp.arange(Lp, dtype=jnp.int32)
    h = _rms(x, p["attn_norm"], eps)
    q = _rope(_lin(h, p["wq"], 1, quant), pos, theta)
    k = _rope(_lin(h, p["wk"], 1, quant), pos, theta)
    v = _lin(h, p["wv"], 1, quant)
    G = H // K
    qg = q.reshape(Lp, K, G, dh)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k) * dh ** -0.5
    s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
    o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, -1), v)
    x = x + _lin(o.reshape(Lp, H, dh), p["wo"], 2, quant)
    x = x + _mlp(p, _rms(x, p["mlp_norm"], eps), quant)
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


@functools.partial(jax.jit, static_argnames=("d", "quant"))
def _reuse_layer(stack, l, x, pos, kr, vr, rvalid, *, d, quant):
    """One layer over n block states [n, Sb, D] against the kept keys and
    values [K, R, dh] of the last Refresh plus the block's own."""
    H, K, dh, eps, theta = d
    p = _layer(stack, l)
    n, sb, _ = x.shape
    h = _rms(x, p["attn_norm"], eps)
    q = _rope(_lin(h, p["wq"], 1, quant), pos, theta)
    k = _rope(_lin(h, p["wk"], 1, quant), pos, theta)
    v = _lin(h, p["wv"], 1, quant)
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
    x = x + _lin(o.reshape(n, sb, H, dh), p["wo"], 2, quant)
    return x + _mlp(p, _rms(x, p["mlp_norm"], eps), quant)


# ---------------------------------------------------------------------------
# SSM family (Mamba2)
# ---------------------------------------------------------------------------

def _ssm_project(p, x, dm, quant):
    Din, N, G = dm["d_inner"], dm["ssm_state"], dm["ssm_groups"]
    h = _rms(x, p["norm"], dm["rms_eps"])
    z = _lin(h, p["w_z"], 1, quant)
    xbc = _lin(h, p["w_xbc"], 1, quant)
    dt = jax.nn.softplus(_lin(h, p["w_dt"], 1, quant) + p["dt_bias"])
    return z, xbc, dt


def _ssm_finish(p, x, y, xh, z, dm, quant):
    y = y + p["D_skip"][:, None] * xh
    y = y.reshape(y.shape[:-2] + (dm["d_inner"],))
    y = _rms(y * jax.nn.silu(z), p["gate_norm"], dm["rms_eps"])
    return x + _lin(y, p["out_proj"], 1, quant)


def _ssm_scan(xh, dt, A, Bm, Cm, state):
    """Sequential recurrence over the leading axis; returns (y, state)."""
    def step(h, t):
        x_t, dt_t, b_t, c_t = t
        h = h * jnp.exp(dt_t * A)[:, None, None] + \
            (dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None, :])
        return h, jnp.einsum("n,hpn->hp", c_t, h)
    state, y = jax.lax.scan(step, state, (xh, dt, Bm, Cm))
    return y, state


def _ssm_layer_core(p, x, hist, state, dm, quant):
    """One Mamba2 layer over rows x [S, D] that follow a conv history
    [ck-1, ch] (pre-conv) and a recurrent state [H, P, N]."""
    Din, N, G = dm["d_inner"], dm["ssm_state"], dm["ssm_groups"]
    Hs, P = dm["ssm_heads"], dm["ssm_head_dim"]
    z, xbc_pre, dt = _ssm_project(p, x, dm, quant)
    ck = p["conv_w"].shape[0]
    xin_all = jnp.concatenate([hist, xbc_pre], axis=0)
    S = x.shape[0]
    conv = sum(xin_all[i:i + S] * p["conv_w"][i] for i in range(ck))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xin = xbc[:, :Din]
    Bm = xbc[:, Din:Din + G * N]
    Cm = xbc[:, Din + G * N:]
    xh = xin.reshape(S, Hs, P)
    A = -jnp.exp(p["A_log"])
    y, state = _ssm_scan(xh, dt, A, Bm, Cm, state)
    out = _ssm_finish(p, x, y, xh, z, dm, quant)
    return out, xin_all[-(ck - 1):], state


@functools.partial(jax.jit, static_argnames=("dk", "quant"))
def _ssm_prefix_layer(stack, l, x, n_real, *, dk, quant):
    """A layer over a prefix padded at its end; returns its output and the
    (conv history, state) after its first ``n_real`` rows."""
    dm = dict(dk)
    p = _layer(stack, l)
    ck = p["conv_w"].shape[0]
    ch = p["conv_w"].shape[1]
    Hs, P, N = dm["ssm_heads"], dm["ssm_head_dim"], dm["ssm_state"]
    valid = jnp.arange(x.shape[0]) < n_real
    z, xbc_pre, dt = _ssm_project(p, x, dm, quant)
    hist0 = jnp.zeros((ck - 1, ch), F32)
    xin_all = jnp.concatenate([hist0, xbc_pre], axis=0)
    S = x.shape[0]
    conv = sum(xin_all[i:i + S] * p["conv_w"][i] for i in range(ck))
    xbc = jax.nn.silu(conv + p["conv_b"])
    Din, G = dm["d_inner"], dm["ssm_groups"]
    xh = xbc[:, :Din].reshape(S, Hs, P)
    A = -jnp.exp(p["A_log"])
    dt = jnp.where(valid[:, None], dt, 0.0)      # padding leaves h alone
    y, state = _ssm_scan(xh, dt, A, xbc[:, Din:Din + G * N],
                         xbc[:, Din + G * N:], jnp.zeros((Hs, P, N), F32))
    out = _ssm_finish(p, x, y, xh, z, dm, quant)
    hist = jax.lax.dynamic_slice_in_dim(xin_all, n_real, ck - 1, axis=0)
    return out, hist, state


@functools.partial(jax.jit, static_argnames=("dk", "quant"))
def _ssm_block_layer(stack, l, x, hist, state, *, dk, quant):
    """A layer over n block states [n, Sb, D] from one (history, state)."""
    dm = dict(dk)
    p = _layer(stack, l)
    f = lambda xb: _ssm_layer_core(p, xb, hist, state, dm, quant)[0]
    return jax.vmap(f)(x)


# ---------------------------------------------------------------------------
# replay
# ---------------------------------------------------------------------------

@dataclass
class Trajectory:
    """What the timed path served for one request: its prompt, and per
    block the block's tokens before each step and the positions (within
    the block) and ids committed at that step."""
    rid: int
    prompt: np.ndarray
    gen_len: int
    steps: List[List[Tuple[np.ndarray, np.ndarray, np.ndarray]]]

    @property
    def total_len(self) -> int:
        return len(self.prompt) + self.gen_len

    @property
    def n_tokens(self) -> int:
        return sum(len(p) for blk in self.steps for _, p, _ in blk)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("eps", "tied", "quants"))
def _head(embed, final_norm, rows_by_mode, served, mask_id, *, eps, tied,
          quants):
    """Gaps at the checked rows: the served token's (float32 reference)
    logit below the reference's best, and for each lower-precision mode the
    same for the token that mode puts first. A served id 0 stands for the
    mask id too (the engine commits 0 where the argmax is the mask)."""
    w = embed["table"].astype(F32).T if tied else \
        embed["lm_head"].astype(F32)
    fn = final_norm.astype(F32)
    out = []
    z_ref = None
    for mode, h in zip(quants, rows_by_mode):
        z = _lin(_rms(h, fn, eps), w, 1, mode)
        if mode is None:
            z_ref = z
            zmax = z.max(-1)
            zs = jnp.take_along_axis(z, served[:, None], -1)[:, 0]
            zs = jnp.where(served == 0, jnp.maximum(zs, z[:, mask_id]), zs)
            out.append(zmax - zs)
        else:
            c = jnp.argmax(z, -1)
            out.append(z_ref.max(-1) -
                       jnp.take_along_axis(z_ref, c[:, None], -1)[:, 0])
    return out


class Reference:
    """The reference for one configuration's sizes and one set of weights.

    ``dims``: ``config.dims``; ``serve``: block size, steps per block,
    Refresh interval, retained length, pooling window, mask id."""

    def __init__(self, dims: dict, params: dict, serve: dict):
        self.d = dims
        self.p = params
        self.s = serve
        self.attn = dims["family"] != "ssm"
        if self.attn:
            self.dk = (dims["n_heads"], dims["n_kv_heads"], dims["head_dim"],
                       float(dims["rms_eps"]), float(dims["rope_theta"]))
        else:
            din = dims["ssm_expand"] * dims["d_model"]
            dm = dict(rms_eps=float(dims["rms_eps"]), d_inner=din,
                      ssm_state=dims["ssm_state"],
                      ssm_groups=dims["ssm_groups"],
                      ssm_heads=din // dims["ssm_head_dim"],
                      ssm_head_dim=dims["ssm_head_dim"])
            self.dk = tuple(sorted(dm.items()))

    def _embed(self, ids):
        return self.p["embed"]["table"][jnp.asarray(ids)].astype(F32)

    def gaps(self, traj: Trajectory, quants=(None,)) -> List[np.ndarray]:
        """One array of gaps per mode in ``quants`` (``None`` first), over
        every committed position of the trajectory, in commit order."""
        with jax.default_matmul_precision("highest"):
            return self._gaps(traj, tuple(quants))

    def _gaps(self, traj, quants):
        sb, mask = self.s["block_size"], self.s["mask_id"]
        P = len(traj.prompt)
        done = []           # finished blocks
        res = [[] for _ in quants]
        for b, steps in enumerate(traj.steps):
            bstart = P + b * sb
            rows = {q: [] for q in quants}
            served = []
            groups = self._groups(len(steps))
            for refresh_s, reuse_ss in groups:
                ctx = [traj.prompt] + done
                for q in quants:
                    hs = self._block_hidden(ctx, steps, refresh_s, reuse_ss,
                                            bstart, traj.total_len, q)
                    for s, h in zip([refresh_s] + reuse_ss, hs):
                        pos = steps[s][1]
                        rows[q].append(h[jnp.asarray(pos)])
                for s in [refresh_s] + reuse_ss:
                    served.append(steps[s][2])
            served = jnp.asarray(np.concatenate(served).astype(np.int32))
            out = _head(self.p["embed"], self.p["final_norm"],
                        tuple(jnp.concatenate(rows[q]) for q in quants),
                        served, mask, eps=float(self.d["rms_eps"]),
                        tied=bool(self.d["tie_embeddings"]), quants=quants)
            for i, o in enumerate(out):
                res[i].append(np.asarray(o))
            final = steps[-1][0].copy()
            pos, ids = steps[-1][1], steps[-1][2]
            final[pos] = ids
            done.append(final)
        return [np.concatenate(r) if r else np.zeros(0) for r in res]

    def _groups(self, n_steps):
        """[(refresh step, [reuse steps that read its cache])]."""
        ri = self.s["refresh_interval"]
        out = []
        for s in range(n_steps):
            if s == 0 or (ri and s % ri == 0) or not self.attn:
                out.append((s, []))
            else:
                out[-1][1].append(s)
        if not self.attn:       # causal: every step from one prefix pass
            return [(0, list(range(1, n_steps)))]
        return out

    def _seq(self, ctx, block, total_len):
        mask = self.s["mask_id"]
        head = np.concatenate(ctx + [block]).astype(np.int32)
        tail = np.full(total_len - len(head), mask, np.int32)
        return np.concatenate([head, tail])

    def _block_hidden(self, ctx, steps, refresh_s, reuse_ss, bstart, total,
                      q):
        """Final-layer hidden rows [Sb, D] (before the final norm) of the
        block at ``refresh_s`` and at each step of ``reuse_ss``."""
        sb = self.s["block_size"]
        st = self.p["stack"]
        L = self.d["n_layers"]
        if not self.attn:
            return self._ssm_block_hidden(ctx, steps, [refresh_s] + reuse_ss,
                                          bstart, q)
        retain = self.s["retain"]
        seq = self._seq(ctx, steps[refresh_s][0], total)
        Lp = _round_up(max(len(seq), retain), 128)
        ids = np.zeros(Lp, np.int32)
        ids[:len(seq)] = seq
        valid = jnp.asarray(np.arange(Lp) < len(seq))
        x = self._embed(ids)
        kept = []
        for l in range(L):
            x, kv = _refresh_layer(st, l, x, valid, bstart, d=self.dk,
                                   quant=q, retain=retain, sb=sb,
                                   ksize=self.s["kernel_size"])
            kept.append(kv)
        out = [x[bstart:bstart + sb]]
        if reuse_ss:
            xb = self._embed(np.stack([steps[s][0] for s in reuse_ss]))
            pos = jnp.broadcast_to(jnp.arange(bstart, bstart + sb),
                                   xb.shape[:2])
            for l in range(L):
                xb = _reuse_layer(st, l, xb, pos, *kept[l], d=self.dk,
                                  quant=q)
            out += list(xb)
        return out

    def _ssm_block_hidden(self, ctx, steps, ss, bstart, q):
        st = self.p["stack"]
        prefix = np.concatenate(ctx).astype(np.int32)
        Lp = _round_up(len(prefix), 128)
        ids = np.zeros(Lp, np.int32)
        ids[:len(prefix)] = prefix
        x = self._embed(ids)
        xb = self._embed(np.stack([steps[s][0] for s in ss]))
        for l in range(self.d["n_layers"]):
            x, hist, state = _ssm_prefix_layer(st, l, x, len(prefix),
                                               dk=self.dk, quant=q)
            xb = _ssm_block_layer(st, l, xb, hist, state, dk=self.dk,
                                  quant=q)
        return list(xb)
