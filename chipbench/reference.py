"""Plain float32 reference of the served models, and its fp8 control.

It imports nothing of the program. It reads the benchmark's own weights
(``weights.py``) and sizes (``config.dims``) and replays the denoising
trajectory that the timed path served, step by step, teacher-forced: at
each step the block holds exactly the tokens the engine had committed
before that step, so the reference's logits at the positions the engine
committed are comparable with the tokens it committed there.

What a block's steps compute is the architecture's: the configuration's
module (``archs/<arch>.py``, ``dims["arch"]``) says which steps replay as a
Refresh and which as a Reuse, and gives the block's final hidden rows at
each. This module keeps what every architecture shares: the trajectory,
the teacher forcing, the embedding, the head and the gaps, and the linear
layer that the control rounds.

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


def lin(x, w, n_in: int, quant: Optional[str]):
    """x [..., in...] times w [in..., out...] over ``n_in`` leading dims."""
    if quant == "fp8":
        x = _fq(x, tuple(range(x.ndim - n_in, x.ndim)))
        w = _fq(w, tuple(range(n_in)))
    return jnp.tensordot(x, w, axes=n_in)


def rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def layer_params(stack, l):
    """Layer ``l`` of the weights' layer stack, in float32."""
    return {k: v[l].astype(F32) for k, v in stack.items()}


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


def round_up(n: int, m: int) -> int:
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
        z = lin(rms(h, fn, eps), w, 1, mode)
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

    ``dims``: ``config.dims``, its architecture module under ``arch``;
    ``serve``: block size, steps per block, Refresh interval, retained
    length, pooling window, mask id."""

    def __init__(self, dims: dict, params: dict, serve: dict):
        self.d = dims
        self.p = params
        self.s = serve
        self.arch = dims["arch"]

    def embed(self, ids):
        return self.p["embed"]["table"][jnp.asarray(ids)].astype(F32)

    def seq(self, ctx, block, total_len):
        """The whole sequence as a Refresh sees it: the finished context,
        the block's state, the mask up to ``total_len``."""
        mask = self.s["mask_id"]
        head = np.concatenate(ctx + [block]).astype(np.int32)
        tail = np.full(total_len - len(head), mask, np.int32)
        return np.concatenate([head, tail])

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
            groups = self.arch.groups(len(steps),
                                      self.s["refresh_interval"])
            for refresh_s, reuse_ss in groups:
                ctx = [traj.prompt] + done
                for q in quants:
                    hs = self.arch.block_hidden(self, ctx, steps, refresh_s,
                                                reuse_ss, bstart,
                                                traj.total_len, q)
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
