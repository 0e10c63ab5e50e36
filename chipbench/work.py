"""Operations and bytes of the served work, computed from the sizes.

Conventions (those of the repository's analytic model): a matmul
``[m, k] x [k, n]`` is ``2mkn`` operations; attention is ``4 T Sk H dh``
(scores and the weighted sum); bytes are bf16 (2 per element). Only real
work counts: real tokens of each stage, never the padded buckets the
device ran, and nothing recomputed after a preemption. A layer's
projections and attention are the architecture module's
(``archs/<arch>.py``: ``matmul_flops_per_token``, ``attention_flops``).
"""
from __future__ import annotations

BYTES = 2


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
    arch = d["arch"]
    if phase == "refresh":
        t = total_len
        f = t * arch.matmul_flops_per_token(d) + \
            arch.attention_flops(d, t, t)
    else:
        keys = min(retain, total_len - block) + block
        f = block * arch.matmul_flops_per_token(d) + \
            arch.attention_flops(d, block, keys)
    return f + block * logit_flops_per_row(d)
