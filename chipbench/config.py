"""A configuration file as the benchmark and the program read it.

The file holds the source's own keys with the values that are run, the
registry arch the program builds it from (``arch``), the benchmark's module
of its architecture (``arch_module``, ``archs/``), and ``program``: for
each field of the program's ``ModelConfig`` that the file sets, the source
key that sets it, or a literal where the source has no key.
:func:`dims` resolves that to one flat dict, which the reference, the
weights and the work functions read, with the module under ``arch``; the
program's ``ModelConfig`` is built from the same dict
(:func:`model_config`), so both sides run the sizes the file states.
"""
from __future__ import annotations

import dataclasses

# ModelConfig fields every architecture's reference reads, with their
# defaults where the source has no key; the module adds its own (FIELDS)
_DEFAULTS = dict(rms_eps=1e-6, tie_embeddings=False)


def dims(conf: dict, arch) -> dict:
    """Flat sizes of the configuration as run, under the program's names:
    every field the file's ``program`` sets, and the module's fields."""
    out = dict(_DEFAULTS, **arch.FIELDS)
    for field, src in conf["program"].items():
        out[field] = conf[src] if isinstance(src, str) and src in conf \
            else src
    missing = {"n_layers", "d_model", "vocab_size"} - set(out)
    if missing:
        raise ValueError(f"{conf['name']}: the program sets no "
                         f"{sorted(missing)}")
    if out.get("n_heads") and not out.get("head_dim"):
        out["head_dim"] = out["d_model"] // out["n_heads"]
    out.update(dtype=conf["dtype"], name=conf["name"], arch=arch)
    return out


def model_config(conf: dict, arch, **overrides):
    """The program's ``ModelConfig`` for this file: the registry arch with
    every size the file states. ``overrides`` replace fields afterwards (the
    CPU rehearsal shrinks widths this way)."""
    from repro.configs import get_config
    d = dims(conf, arch)
    base = get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    cfg = dataclasses.replace(
        base, name=conf["name"],
        **{k: v for k, v in d.items() if k in fields and k != "name"})
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def dims_of(cfg, conf: dict, arch) -> dict:
    """The flat sizes of a ``ModelConfig`` that is about to run (after any
    rehearsal overrides), under the keys :func:`dims` gives the file."""
    d = {k: getattr(cfg, k) for k in dims(conf, arch)
         if k not in ("name", "dtype", "arch")}
    if "head_dim" in d:
        d["head_dim"] = cfg.resolved_head_dim
    d.update(dtype=cfg.dtype, name=cfg.name, arch=arch)
    return d
