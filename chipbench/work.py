"""Operations and bytes of the served work, computed from the sizes.

Conventions (those of the repository's analytic model): a matmul
``[m, k] x [k, n]`` is ``2mkn`` operations; attention is ``4 T Sk H dh``
(scores and the weighted sum); bytes are bf16 (2 per element). Only real
work counts: real tokens of each stage, never the padded buckets the
device ran, and nothing recomputed after a preemption.
"""
from __future__ import annotations

BYTES = 2


def matmul_flops_per_token(d: dict) -> float:
    """Projection and MLP operations per token through every layer (the
    output head is :func:`logit_flops_per_row`)."""
    D, L = d["d_model"], d["n_layers"]
    if d["family"] == "ssm":
        din = d["ssm_expand"] * D
        hs = din // d["ssm_head_dim"]
        ch = din + 2 * d["ssm_groups"] * d["ssm_state"]
        return 2.0 * L * (D * (din + ch + hs) + din * D)
    H, K, dh, F = d["n_heads"], d["n_kv_heads"], d["head_dim"], d["d_ff"]
    return 2.0 * L * (D * H * dh + 2 * D * K * dh + H * dh * D + 3 * D * F)


def attention_flops(d: dict, queries: int, keys: int) -> float:
    """``4 T Sk H dh`` over every layer (0 for an attention-free model)."""
    if d["family"] == "ssm":
        return 0.0
    return 4.0 * queries * keys * d["n_heads"] * d["head_dim"] * d["n_layers"]


def logit_flops_per_row(d: dict) -> float:
    return 2.0 * d["d_model"] * d["vocab_size"]


def logit_call_bytes(d: dict, rows: int) -> float:
    """One fused logit tile: the ``V x D`` table read once, plus the hidden
    rows in."""
    return BYTES * (d["vocab_size"] * d["d_model"] + rows * d["d_model"])


def step_flops(d: dict, phase: str, total_len: int, block: int,
               retain: int) -> float:
    """One denoising step of one request. Refresh: the whole sequence
    through every layer, attention over the sequence. Reuse: the block
    alone, attention over the kept positions (at most ``retain``, at most
    the positions outside the block) and the block. Both: the block's
    logit rows."""
    if phase == "refresh":
        t = total_len
        f = t * matmul_flops_per_token(d) + attention_flops(d, t, t)
    else:
        keys = min(retain, total_len - block) + block
        f = block * matmul_flops_per_token(d) + \
            attention_flops(d, block, keys)
    return f + block * logit_flops_per_row(d)
