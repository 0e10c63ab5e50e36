"""Random weights from the seed, made by the benchmark, in the layout the
engine takes (``Engine(params=...)``) and in the type they are served in.

The benchmark makes the weights itself so that the reference
(``reference.py``) reads nothing the program made. One jitted call builds
the whole tree on the device. The distribution is the repository's own
convention for random weights: projections normal with std 0.02, norm
scales 0 (the ``1 + scale`` parameterization), SSM decay ``A_log`` 0, skip
``D`` 1, conv taps std 0.2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02
CONV_STD = 0.2


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: the low 32 bits seed it, the rest are
    folded in."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _dense_layout(mc: dict) -> dict:
    L, D, F, V = mc["n_layers"], mc["d_model"], mc["d_ff"], mc["vocab_size"]
    H, K, dh = mc["n_heads"], mc["n_kv_heads"], mc["head_dim"]
    stack = {
        "attn_norm": ((L, D), 0.0), "mlp_norm": ((L, D), 0.0),
        "wq": ((L, D, H, dh), STD), "wk": ((L, D, K, dh), STD),
        "wv": ((L, D, K, dh), STD), "wo": ((L, H, dh, D), STD),
        "w_gate": ((L, D, F), STD), "w_up": ((L, D, F), STD),
        "w_down": ((L, F, D), STD),
    }
    return stack


def _ssm_layout(mc: dict) -> dict:
    L, D = mc["n_layers"], mc["d_model"]
    Din = mc["ssm_expand"] * D
    Hs = Din // mc["ssm_head_dim"]
    ch = Din + 2 * mc["ssm_groups"] * mc["ssm_state"]
    return {
        "norm": ((L, D), 0.0), "w_z": ((L, D, Din), STD),
        "w_xbc": ((L, D, ch), STD), "w_dt": ((L, D, Hs), STD),
        "dt_bias": ((L, Hs), 0.0),
        "conv_w": ((L, mc["ssm_conv_kernel"], ch), CONV_STD),
        "conv_b": ((L, ch), 0.0), "A_log": ((L, Hs), 0.0),
        "D_skip": ((L, Hs), 1.0), "gate_norm": ((L, Din), 0.0),
        "out_proj": ((L, Din, D), STD),
    }


def layout(mc: dict) -> dict:
    """{path: (shape, std)} for every leaf; a std of 0 or 1 with no draw is
    a constant (zeros or, for ``D_skip``, ones)."""
    V, D = mc["vocab_size"], mc["d_model"]
    out = {("embed", "table"): ((V, D), STD), ("final_norm",): ((D,), 0.0)}
    if not mc["tie_embeddings"]:
        out[("embed", "lm_head")] = ((D, V), STD)
    stack = (_ssm_layout(mc) if mc["family"] == "ssm"
             else _dense_layout(mc))
    for k, v in stack.items():
        out[("stack", k)] = v
    return out


def _build(mc: dict, key: jax.Array) -> dict:
    dtype = jnp.dtype(mc["dtype"])
    tree: dict = {}
    for i, (path, (shape, std)) in enumerate(sorted(layout(mc).items())):
        if path[-1] == "D_skip":
            leaf = jnp.ones(shape, dtype)
        elif std == 0.0:
            leaf = jnp.zeros(shape, dtype)
        else:
            leaf = (std * jax.random.normal(jax.random.fold_in(key, i),
                                            shape, jnp.float32)).astype(dtype)
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return tree


def make_params(mc: dict, seed: int) -> dict:
    """The whole tree on the default device, in one jitted call."""
    return jax.jit(functools.partial(_build, mc))(seed_key(seed))
