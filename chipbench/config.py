"""A configuration file as the benchmark and the program read it.

The file holds the source's own keys with the values that are run, the
registry arch the program builds it from (``arch``), and ``program``: for
each field of the program's ``ModelConfig``, the source key that sets it,
or a literal where the source has no key. :func:`dims` resolves that to one
flat dict, which the reference and the work functions read; the program's
``ModelConfig`` is built from the same dict (:func:`model_config`), so both
sides run the sizes the file states.
"""
from __future__ import annotations

import dataclasses

# ModelConfig fields the file may set, with their defaults where the source
# has no key (an attention-free model has no heads, a dense model no state)
_DEFAULTS = dict(n_heads=0, n_kv_heads=0, head_dim=0, d_ff=0,
                 rope_theta=10_000.0, rms_eps=1e-6, tie_embeddings=False,
                 ssm_state=0, ssm_head_dim=64, ssm_expand=2, ssm_groups=1,
                 ssm_conv_kernel=4, ssm_chunk=64)


def dims(conf: dict) -> dict:
    """Flat sizes of the configuration as run, under the program's names."""
    out = dict(_DEFAULTS)
    for field, src in conf["program"].items():
        out[field] = conf[src] if isinstance(src, str) and src in conf \
            else src
    if out["n_heads"] and not out["head_dim"]:
        out["head_dim"] = out["d_model"] // out["n_heads"]
    out["dtype"] = conf["dtype"]
    out["name"] = conf["name"]
    return out


def model_config(conf: dict, **overrides):
    """The program's ``ModelConfig`` for this file: the registry arch with
    every size the file states. ``overrides`` replace fields afterwards (the
    CPU rehearsal shrinks widths this way)."""
    from repro.configs import get_config
    d = dims(conf)
    base = get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    cfg = dataclasses.replace(
        base, name=conf["name"],
        **{k: v for k, v in d.items() if k in fields and k != "name"})
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def dims_of(cfg) -> dict:
    """The flat sizes of a ``ModelConfig`` that is about to run (after any
    rehearsal overrides), for the reference and the work functions."""
    d = {k: getattr(cfg, k) for k in _DEFAULTS}
    d.update(name=cfg.name, family=cfg.family, n_layers=cfg.n_layers,
             d_model=cfg.d_model, vocab_size=cfg.vocab_size,
             head_dim=cfg.resolved_head_dim, dtype=cfg.dtype)
    return d
