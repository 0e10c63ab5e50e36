"""The one doorway to jax's mesh, sharding and jit APIs.

Every other module reaches the mesh context (``jax.set_mesh``), the
active-mesh query (``jax.sharding.get_abstract_mesh``), ``jax.shard_map``,
``PartitionSpec`` and ``jax.jit`` through here — the invariant
``repro.analysis`` lints for. ``P`` re-exports ``PartitionSpec`` so no other
module imports ``jax.sharding`` directly, and :func:`jit` /
:func:`jit_sharded` wrap ``jax.jit`` with an optional per-entry-point
**compile counter** — the retrace sentinel (``repro.analysis.retrace``)
reads those counters to prove the steady-state serving loop never
recompiles after warmup.

Keep this module dependency-free (imported by kernels, models, and launch).
"""
from __future__ import annotations

import collections
import contextlib

import jax
from jax.sharding import PartitionSpec as P  # the sanctioned re-export

__all__ = [
    "P", "use_mesh", "get_active_mesh", "named_shardings", "jit",
    "jit_sharded", "shard_map", "compile_counts", "reset_compile_counts",
]

# XLA:CPU cannot alias every donated buffer, and jax warns "Some donated
# buffers were not usable" on each such compile. Donation is a pure lifetime
# hint — numerics are identical either way — so when a caller opts into
# donation we silence exactly that message once.
_DONATION_WARNING_FILTERED = False


def _enable_donation(jit_kwargs: dict, donate_argnums) -> dict:
    global _DONATION_WARNING_FILTERED
    if donate_argnums:
        jit_kwargs["donate_argnums"] = tuple(donate_argnums)
        if not _DONATION_WARNING_FILTERED:
            import warnings
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            _DONATION_WARNING_FILTERED = True
    return jit_kwargs

# process-global trace/compile counters, keyed by entry-point name. A jitted
# function's Python body runs exactly once per cache miss (each trace lowers
# and compiles), so counting body executions counts compilations — no
# version-fragile jax.monitoring hook needed.
_compile_counts: collections.Counter = collections.Counter()


def compile_counts() -> dict:
    """Snapshot of the process-global per-entry compile counters."""
    return dict(_compile_counts)


def reset_compile_counts() -> None:
    _compile_counts.clear()


def _counting(fn, entry: str, counter):
    """Wrap ``fn`` so each *trace* (= jit cache miss = one XLA compilation)
    increments ``counter[entry]`` and the global ledger. The wrapper body
    only runs while jax traces, so steady-state cached calls cost nothing.

    The wrapper is named ``entry``, so the compiled program is the module
    ``jit_<entry>`` in HLO dumps and device traces (a stage's inner ``fn``
    or a pool ``lambda`` would otherwise all read ``jit_fn``)."""
    import functools

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        _compile_counts[entry] += 1
        if counter is not None:
            counter[entry] += 1
        return fn(*args, **kwargs)

    traced.__name__ = traced.__qualname__ = entry
    return traced


def jit(fn=None, *, entry=None, counter=None, donate_argnums=(),
        **jit_kwargs):
    """``jax.jit`` through the compat layer (the lint-sanctioned spelling).

    ``entry`` names the jit entry point for the retrace sentinel: every
    compilation (trace) of the returned function increments the global
    ``compile_counts()`` ledger and, if given, ``counter[entry]`` (any
    Counter-like mapping — the engine passes its per-instance counter).
    It also names the compiled program: ``jit_<entry>``.
    Without ``entry`` this is a plain ``jax.jit``. Usable as a decorator
    (``@JC.jit`` / ``@functools.partial(JC.jit, static_argnames=...)``).

    ``donate_argnums`` marks per-call input buffers whose storage XLA may
    reuse for the outputs (the engine donates its per-iteration stream
    buffers so packed streams stop double-buffering — docs/engine.md).
    The caller contract: a donated argument's buffer is dead after the
    call; never re-pass or read it. Backends that can't alias a given
    donation silently keep a copy (the CPU warning is filtered here), so
    donation never changes numerics — only buffer lifetime."""
    if fn is None:
        import functools
        return functools.partial(jit, entry=entry, counter=counter,
                                 donate_argnums=donate_argnums, **jit_kwargs)
    if entry is not None:
        fn = _counting(fn, entry, counter)
    return jax.jit(fn, **_enable_donation(jit_kwargs, donate_argnums))


@contextlib.contextmanager
def use_mesh(mesh):
    """Activate ``mesh`` for the dynamic scope (``jax.set_mesh``)."""
    with jax.set_mesh(mesh):
        yield mesh


def get_active_mesh():
    """The ``AbstractMesh`` of the enclosing ``use_mesh`` scope, or None.

    Never returns an *empty* mesh object, so call sites need a single
    emptiness check; they read only ``axis_names``/``shape``."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m


def named_shardings(mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh``.

    Specs are the *leaves* (tree ops must treat a PartitionSpec
    atomically)."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def jit_sharded(fn, *, mesh, in_specs=None, out_specs=None, entry=None,
                counter=None, donate_argnums=(), **jit_kwargs):
    """``jax.jit`` with PartitionSpec-valued in/out shardings on ``mesh``.

    The serving engine's per-stage entry points thread their stage layouts
    through here: host inputs are auto-placed to the given in_specs (a spec
    leaf broadcasts over optional ``None`` args), outputs are pinned to
    out_specs so downstream consumers (the slot pool above all) see a
    stable layout instead of whatever GSPMD propagation happened to pick. ``mesh=None`` is a plain ``jax.jit`` —
    the single-device path stays byte-for-byte the old code path.

    ``entry``/``counter`` hook the retrace sentinel exactly as in
    :func:`jit`: each compilation of the entry point is counted, so the
    engine can prove zero post-warmup recompilation. ``donate_argnums``
    follows the :func:`jit` donation contract (buffer dead after the call);
    donation composes with shardings — aliasing happens per device buffer."""
    if entry is not None:
        fn = _counting(fn, entry, counter)
    jit_kwargs = _enable_donation(jit_kwargs, donate_argnums)
    if mesh is None:
        return jax.jit(fn, **jit_kwargs)
    if in_specs is not None:
        jit_kwargs["in_shardings"] = named_shardings(mesh, in_specs)
    if out_specs is not None:
        jit_kwargs["out_shardings"] = named_shardings(mesh, out_specs)
    return jax.jit(fn, **jit_kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` (the lint-sanctioned spelling)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
