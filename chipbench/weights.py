"""Random weights from the seed, made by the benchmark, in the layout the
engine takes (``Engine(params=...)``) and in the type they are served in.

The benchmark makes the weights itself so that the reference
(``reference.py``) reads nothing the program made. One jitted call builds
the whole tree on the device. The distribution is the repository's own
convention for random weights: projections normal with std 0.02, norm
scales 0 (the ``1 + scale`` parameterization); the layer stack's leaves are
the architecture module's (``archs/<arch>.py`` ``layout``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: the low 32 bits seed it, the rest are
    folded in."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def layout(mc: dict) -> dict:
    """{path: (shape, std)} for every leaf; a std of 0 is zeros, and
    ``"ones"`` ones."""
    V, D = mc["vocab_size"], mc["d_model"]
    out = {("embed", "table"): ((V, D), STD), ("final_norm",): ((D,), 0.0)}
    if not mc["tie_embeddings"]:
        out[("embed", "lm_head")] = ((D, V), STD)
    for k, v in mc["arch"].layout(mc).items():
        out[("stack", k)] = v
    return out


def _build(mc: dict, key: jax.Array) -> dict:
    dtype = jnp.dtype(mc["dtype"])
    tree: dict = {}
    for i, (path, (shape, std)) in enumerate(sorted(layout(mc).items())):
        if std == "ones":
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
