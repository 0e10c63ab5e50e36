"""Mamba2's block: the SSD recurrence behind a causal depthwise conv, a gated
RMSNorm, no attention and no MLP.

Causal, so the reference replays every step of a block from one pass over
the prefix through the block's start: the recurrent state and the conv
history are taken exactly there, and each step's block state runs from
them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import F32, layer_params, lin, rms, round_up
from chipbench.weights import STD

CONV_STD = 0.2

FIELDS = dict(ssm_state=0, ssm_head_dim=64, ssm_expand=2, ssm_groups=1,
              ssm_conv_kernel=4, ssm_chunk=64)

REHEARSAL = dict(n_layers=2, d_model=64, vocab_size=256, ssm_state=16,
                 ssm_head_dim=8, dtype="bfloat16")


def _sizes(d: dict):
    din = d["ssm_expand"] * d["d_model"]
    return din, din // d["ssm_head_dim"], \
        din + 2 * d["ssm_groups"] * d["ssm_state"]


def layout(d: dict) -> dict:
    """Decay ``A_log`` 0, skip ``D`` 1, conv taps std 0.2."""
    L, D = d["n_layers"], d["d_model"]
    Din, Hs, ch = _sizes(d)
    return {
        "norm": ((L, D), 0.0), "w_z": ((L, D, Din), STD),
        "w_xbc": ((L, D, ch), STD), "w_dt": ((L, D, Hs), STD),
        "dt_bias": ((L, Hs), 0.0),
        "conv_w": ((L, d["ssm_conv_kernel"], ch), CONV_STD),
        "conv_b": ((L, ch), 0.0), "A_log": ((L, Hs), 0.0),
        "D_skip": ((L, Hs), "ones"), "gate_norm": ((L, Din), 0.0),
        "out_proj": ((L, Din, D), STD),
    }


def matmul_flops_per_token(d: dict) -> float:
    """Input and output projection operations per token through every
    layer."""
    D, L = d["d_model"], d["n_layers"]
    din, hs, ch = _sizes(d)
    return 2.0 * L * (D * (din + ch + hs) + din * D)


def attention_flops(d: dict, queries: int, keys: int) -> float:
    """None: the model is attention-free."""
    return 0.0


def groups(n_steps: int, refresh_interval: int) -> list:
    """Every step from one pass over the prefix."""
    return [(0, list(range(1, n_steps)))]


def _project(p, x, dm, quant):
    h = rms(x, p["norm"], dm["rms_eps"])
    z = lin(h, p["w_z"], 1, quant)
    xbc = lin(h, p["w_xbc"], 1, quant)
    dt = jax.nn.softplus(lin(h, p["w_dt"], 1, quant) + p["dt_bias"])
    return z, xbc, dt


def _finish(p, x, y, xh, z, dm, quant):
    y = y + p["D_skip"][:, None] * xh
    y = y.reshape(y.shape[:-2] + (dm["d_inner"],))
    y = rms(y * jax.nn.silu(z), p["gate_norm"], dm["rms_eps"])
    return x + lin(y, p["out_proj"], 1, quant)


def _scan(xh, dt, A, Bm, Cm, state):
    """Sequential recurrence over the leading axis; returns (y, state)."""
    def step(h, t):
        x_t, dt_t, b_t, c_t = t
        h = h * jnp.exp(dt_t * A)[:, None, None] + \
            (dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None, :])
        return h, jnp.einsum("n,hpn->hp", c_t, h)
    state, y = jax.lax.scan(step, state, (xh, dt, Bm, Cm))
    return y, state


def _layer_core(p, x, hist, state, dm, quant):
    """One Mamba2 layer over rows x [S, D] that follow a conv history
    [ck-1, ch] (pre-conv) and a recurrent state [H, P, N]."""
    Din, N, G = dm["d_inner"], dm["ssm_state"], dm["ssm_groups"]
    Hs, P = dm["ssm_heads"], dm["ssm_head_dim"]
    z, xbc_pre, dt = _project(p, x, dm, quant)
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
    y, state = _scan(xh, dt, A, Bm, Cm, state)
    out = _finish(p, x, y, xh, z, dm, quant)
    return out, xin_all[-(ck - 1):], state


@functools.partial(jax.jit, static_argnames=("dk", "quant"))
def _prefix_layer(stack, l, x, n_real, *, dk, quant):
    """A layer over a prefix padded at its end; returns its output and the
    (conv history, state) after its first ``n_real`` rows."""
    dm = dict(dk)
    p = layer_params(stack, l)
    ck = p["conv_w"].shape[0]
    ch = p["conv_w"].shape[1]
    Hs, P, N = dm["ssm_heads"], dm["ssm_head_dim"], dm["ssm_state"]
    valid = jnp.arange(x.shape[0]) < n_real
    z, xbc_pre, dt = _project(p, x, dm, quant)
    hist0 = jnp.zeros((ck - 1, ch), F32)
    xin_all = jnp.concatenate([hist0, xbc_pre], axis=0)
    S = x.shape[0]
    conv = sum(xin_all[i:i + S] * p["conv_w"][i] for i in range(ck))
    xbc = jax.nn.silu(conv + p["conv_b"])
    Din, G = dm["d_inner"], dm["ssm_groups"]
    xh = xbc[:, :Din].reshape(S, Hs, P)
    A = -jnp.exp(p["A_log"])
    dt = jnp.where(valid[:, None], dt, 0.0)      # padding leaves h alone
    y, state = _scan(xh, dt, A, xbc[:, Din:Din + G * N],
                     xbc[:, Din + G * N:], jnp.zeros((Hs, P, N), F32))
    out = _finish(p, x, y, xh, z, dm, quant)
    hist = jax.lax.dynamic_slice_in_dim(xin_all, n_real, ck - 1, axis=0)
    return out, hist, state


@functools.partial(jax.jit, static_argnames=("dk", "quant"))
def _block_layer(stack, l, x, hist, state, *, dk, quant):
    """A layer over n block states [n, Sb, D] from one (history, state)."""
    dm = dict(dk)
    p = layer_params(stack, l)
    f = lambda xb: _layer_core(p, xb, hist, state, dm, quant)[0]
    return jax.vmap(f)(x)


def block_hidden(ref, ctx, steps, refresh_s, reuse_ss, bstart, total_len,
                 quant):
    """Final-layer hidden rows [Sb, D] of the block at each step of
    ``[refresh_s] + reuse_ss``, all from the prefix ``ctx``."""
    d = ref.d
    din, hs, _ = _sizes(d)
    dk = tuple(sorted(dict(
        rms_eps=float(d["rms_eps"]), d_inner=din, ssm_state=d["ssm_state"],
        ssm_groups=d["ssm_groups"], ssm_heads=hs,
        ssm_head_dim=d["ssm_head_dim"]).items()))
    st = ref.p["stack"]
    prefix = np.concatenate(ctx).astype(np.int32)
    Lp = round_up(len(prefix), 128)
    ids = np.zeros(Lp, np.int32)
    ids[:len(prefix)] = prefix
    x = ref.embed(ids)
    xb = ref.embed(np.stack([steps[s][0] for s in [refresh_s] + reuse_ss]))
    for l in range(d["n_layers"]):
        x, hist, state = _prefix_layer(st, l, x, len(prefix), dk=dk,
                                       quant=quant)
        xb = _block_layer(st, l, xb, hist, state, dk=dk, quant=quant)
    return list(xb)
