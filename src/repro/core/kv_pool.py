"""Slot-granular static KV pool (paper §4.5 "Static Allocation and
Contiguous Storage").

Holds one device-resident cache pytree whose second axis is the request slot
(``[L, slots+1, ...]``; the extra slot is scratch for padded batch rows).
Refresh writes a freshly packed cache into a request's slot. Reuse reads
it back in one of two ways: the attention families' packed kernel path
reads the retained K/V in place, through a slot table of :meth:`rows`
(nothing is copied); the other paths (hybrid and SSM caches, the int8
view, a pool whose slot axis is sharded over ``data``, the padded oracle)
copy the scheduled slots out with :meth:`gather`. The cache content is
family-specific (PackedKV / SSMCache / HybridCache) — the pool is
shape-agnostic.

Mesh serving: the engine passes the pool a ``NamedSharding`` pytree built
from ``launch.sharding.Rules.cache`` (KV heads over the ``model`` axis when
divisible, retained-length fallback otherwise; the slot axis over ``data``
so each replica stream stores its slots locally — the engine pads the slot
count so the axis divides). The pool then allocates its backing pytree
sharded and pins the scatter's output layout with ``out_shardings`` so
repeated writes can never drift the pool off its planned placement —
per-device pool bytes are exactly what ``plan_memory`` billed; gathers land
in the data-replicated stream layout via ``gather_shardings``. Without
shardings (no mesh) nothing changes.

Slot lifecycle (robustness layer): :meth:`take` / :meth:`free` keep an
explicit free-set plus a per-slot **generation counter**. ``free`` bumps the
slot's generation, so a request holding a handle from before the free (a
preempted-then-recycled slot) can be detected: its recorded generation no
longer matches :meth:`generation`. Double-free and double-take raise — slot
leaks and aliasing are bugs, never silent.

Content-addressed sharing (``sharing=True``, docs/memory.md): a
:class:`~repro.core.share_ledger.ShareLedger` sits between logical slots
and physical rows. :meth:`write_shared` hashes nothing itself — the caller
supplies each request's content key — but redirects a write whose key is
already resident to the scratch row (skip) and records the logical slot as
a referrer of the owning row; :meth:`rows` resolves referrers to their
owner row; :meth:`free` releases references, promoting owned bytes to a
surviving referrer (one device row-copy, the ``pool_copy`` jit) before the
row is recycled — copy-on-write in both the divergent-Refresh and the
free-while-shared direction. The generation ledger is untouched: handles
stay logical, so preempt-and-requeue composes with sharing unchanged.

int8 slot storage (``kv_quant="int8"``): the pool's float KV leaves are
stored quantized with per-(layer, slot) scales (``kernels.kv_quant``).
Quantization runs inside the scatter jit; :meth:`gather` then returns the
**quantized view** (``{"data": ..., "scale": ...}``) so HBM traffic across
the gather stays int8 — the Reuse stages dequantize at their KV load
(``kernels.ops.dequantize_gathered``). Not yet composed with a device
mesh (the scale leaves need their own Rules-derived placement): the
constructor raises rather than guessing a layout.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import jax_compat as JC
from repro.core.share_ledger import ShareLedger

# host spans on the profiler's clock around each pool call (the engine's
# full list of span names is ``repro.core.engine.SPANS``)
SPAN_WRITE = "dllm.pool.write"
SPAN_GATHER = "dllm.pool.gather"


class KVPool:
    def __init__(self, max_slots: int, shardings=None,
                 gather_shardings=None, pad_slots: int = 0,
                 compile_counter=None, sharing: bool = False,
                 kv_quant: str = "none", donate_cache: bool = False):
        """``shardings``: optional NamedSharding pytree matching the cache
        structure (leading slot axis included) — resolved lazily against the
        first Refresh output in :meth:`ensure`.

        ``gather_shardings``: optional NamedSharding pytree pinning the
        layout of gathered sub-batches (the engine's data-replicated stream
        layout — gathers cross from the slot-sharded pool into it).

        ``pad_slots``: extra never-allocated tail slots so a data-sharded
        pool's slot axis always divides the data axis; they are invisible to
        the slot ledger and never written.

        ``compile_counter``: optional Counter the pool's scatter/gather/copy
        jits report compilations into (entries ``pool_write``/
        ``pool_gather``/``pool_copy``) — the engine threads its per-instance
        retrace-sentinel counter here.

        ``sharing``: enable the content-addressed share ledger (callers
        must then write via :meth:`write_shared` with per-slot keys).

        ``kv_quant``: ``"none"`` (bit-exact float storage) or ``"int8"``
        (per-slot-scale quantized KV leaves).

        ``donate_cache``: additionally donate the INCOMING refresh cache to
        the scatter jit (the pool buffer, argnum 0, is always donated — the
        update is in place either way). The engine opts in
        (``ServeConfig.donate_buffers``): its refresh outputs are
        single-use, dead once scattered. Callers that reuse a cache pytree
        across writes (the share-ledger property tests do) must leave this
        off — a donated tree's buffers are invalid after the call."""
        if kv_quant not in ("none", "int8"):
            raise ValueError(f"KVPool: kv_quant must be 'none' or 'int8', "
                             f"got {kv_quant!r}")
        if kv_quant != "none" and shardings is not None:
            raise NotImplementedError(
                "KVPool: int8 slot storage is not yet composed with a "
                "device mesh — the per-(layer, slot) scale leaves need "
                "their own Rules.cache-derived placement (planned; see "
                "docs/memory.md). Run quantized pools without mesh_shape.")
        self.max_slots = max_slots
        self.scratch_slot = max_slots
        self.pad_slots = pad_slots
        self.shardings = shardings
        self.gather_shardings = gather_shardings
        self._compile_counter = compile_counter
        self.kv_quant = kv_quant
        self._write_donate = (0, 1) if donate_cache else (0,)
        self.ledger: Optional[ShareLedger] = ShareLedger() if sharing \
            else None
        self.phys_peak = 0         # high-water distinct-owner occupancy
        self.cache = None          # device pytree, slot axis = 1
        self._write = None
        self._gather = None
        self._copy = None
        self._dtypes = None        # pre-quantization leaf dtypes (by index)
        # slot lifecycle ledger (content arrays above are allocation-lazy;
        # the ledger is live from construction so schedulers can use it
        # before the first Refresh materializes the pool)
        self._free = set(range(max_slots))
        self._gen = np.zeros(max_slots + 1, np.int64)

    # -- slot lifecycle ----------------------------------------------------
    @property
    def slots_in_use(self) -> list:
        return sorted(set(range(self.max_slots)) - self._free)

    @property
    def phys_slots_in_use(self) -> int:
        """Distinct content-holding rows: with sharing, the share ledger's
        owner count (the pool's REAL occupancy — referrers are free
        capacity); without, simply the logical slots in use."""
        if self.ledger is not None:
            return self.ledger.phys_slots
        return self.max_slots - len(self._free)

    def shared_refs(self, slot: int) -> int:
        """Live references backed by ``slot`` (≤ 1 when freeing it costs no
        promote copy; 0 without sharing). The scheduler's preemption victim
        preference reads this."""
        return self.ledger.refcount(slot) if self.ledger is not None else 0

    def take(self, slot: int) -> int:
        """Claim ``slot``; returns its current generation (the handle a
        holder must present at gather time). Raises if already in use."""
        if slot not in self._free:
            raise RuntimeError(f"KVPool: slot {slot} taken while in use "
                               f"(free={sorted(self._free)})")
        self._free.discard(slot)
        return int(self._gen[slot])

    def free(self, slots: Sequence[int]) -> None:
        """Return slots to the pool, bumping each generation so stale
        handles become detectable. Raises on double-free — before any
        mutation, so a bad batch never half-releases. With sharing, each
        slot's content reference is released first; bytes still referenced
        by other logical slots are promoted (device row-copy) before the
        owning row is recycled."""
        for s in slots:
            if s in self._free:
                raise RuntimeError(f"KVPool: double-free of slot {s}")
            if not 0 <= s < self.max_slots:
                raise RuntimeError(f"KVPool: free of invalid slot {s}")
        for s in slots:
            if self.ledger is not None:
                promote = self.ledger.release(s)
                if promote is not None:
                    self._copy_row(*promote)
            self._free.add(s)
            self._gen[s] += 1

    def generation(self, slot: int) -> int:
        return int(self._gen[slot])

    def ensure(self, cache_example) -> None:
        """Lazily allocate the pool from the first Refresh output's shapes."""
        if self.cache is not None:
            return
        n = self.max_slots + 1 + self.pad_slots

        def alloc(c, ns=None, dtype=None):
            shape = (c.shape[0], n) + tuple(c.shape[2:])
            dtype = dtype or c.dtype
            if ns is None:
                return jnp.zeros(shape, dtype)
            # allocate each device's shard directly — jnp.zeros(global) +
            # device_put would transiently hold the WHOLE pool on one
            # device, defeating the per-device plan at exactly the scale
            # the sharded pool enables
            shard = np.zeros(ns.shard_shape(shape), dtype)
            return jax.make_array_from_callback(shape, ns, lambda _: shard)

        cc = self._compile_counter
        if self.kv_quant == "int8":
            # int8 backing for the KV leaves + per-(layer, slot) scales;
            # quantize_slot_leaves runs INSIDE the scatter jit so the float
            # refresh output never lands in HBM as pool state
            from repro.kernels import kv_quant as KQ
            leaves, treedef = jax.tree.flatten(cache_example)
            flags = KQ.quant_leaf_flags(cache_example)
            self._dtypes = {str(i): leaf.dtype
                            for i, (leaf, q) in enumerate(zip(leaves, flags))
                            if q}
            data = jax.tree.unflatten(treedef, [
                alloc(c, dtype=jnp.int8 if q else None)
                for c, q in zip(leaves, flags)])
            scale = {str(i): jnp.zeros((leaves[int(i)].shape[0], n),
                                       jnp.float32) for i in self._dtypes}
            self.cache = {"data": data, "scale": scale}

            def wfn(pool, cache, slots):
                q, sc = KQ.quantize_slot_leaves(cache)
                return {
                    "data": jax.tree.map(
                        lambda P, c: P.at[:, slots].set(c), pool["data"], q),
                    "scale": {k: pool["scale"][k].at[:, slots].set(v)
                              for k, v in sc.items()},
                }

            self._write = JC.jit(wfn, donate_argnums=self._write_donate,
                                 entry="pool_write", counter=cc)
        elif self.shardings is None:
            self.cache = jax.tree.map(alloc, cache_example)
            self._write = JC.jit(
                lambda pool, cache, slots: jax.tree.map(
                    lambda P, c: P.at[:, slots].set(c), pool, cache),
                donate_argnums=self._write_donate, entry="pool_write",
                counter=cc)
        else:
            self.cache = jax.tree.map(alloc, cache_example, self.shardings)
            # pin the pool's planned layout across writes (donation keeps the
            # update in place; out_shardings keeps GSPMD from re-laying it out)
            self._write = JC.jit(
                lambda pool, cache, slots: jax.tree.map(
                    lambda P, c: P.at[:, slots].set(c), pool, cache),
                donate_argnums=self._write_donate,
                out_shardings=self.shardings,
                entry="pool_write", counter=cc)
        # every pool leaf — int8 data, f32 scales, float caches alike —
        # keeps the slot axis at position 1, so ONE gather/copy program
        # covers all storage modes
        if self.gather_shardings is None:
            self._gather = JC.jit(
                lambda pool, slots: jax.tree.map(lambda P: P[:, slots], pool),
                entry="pool_gather", counter=cc)
        else:
            # gathered sub-batches feed the data-replicated engine streams:
            # pin that layout so the slot-sharded pool's gather always lands
            # in the stage jits' expected placement
            self._gather = JC.jit(
                lambda pool, slots: jax.tree.map(lambda P: P[:, slots], pool),
                out_shardings=self.gather_shardings,
                entry="pool_gather", counter=cc)
        copy_kwargs = {} if self.shardings is None else \
            {"out_shardings": self.shardings}
        self._copy = JC.jit(
            lambda pool, src, dst: jax.tree.map(
                lambda P: P.at[:, dst].set(P[:, src]), pool),
            donate_argnums=0, entry="pool_copy", counter=cc, **copy_kwargs)

    @property
    def gathered_dtypes(self):
        """Pre-quantization leaf dtypes for ``dequantize_gathered`` (None
        until the pool materializes, or when storage is bit-exact)."""
        return self._dtypes

    def nbytes(self) -> int:
        if self.cache is None:
            return 0
        return sum(x.nbytes for x in jax.tree.leaves(self.cache))

    def _copy_row(self, src: int, dst: int) -> None:
        """Device row-copy ``src -> dst`` (COW promote). A no-op before the
        pool materializes — the ledger's bookkeeping alone is correct then,
        because an unmaterialized pool holds no bytes to preserve."""
        if self.cache is None:
            return
        self.cache = self._copy(self.cache, jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32))

    def warm_aux(self) -> None:
        """Warm the auxiliary ``pool_copy`` jit (scratch -> scratch, content
        irrelevant) so a sharing pool's first COW promote never compiles
        mid-serve — the retrace sentinel holds post-warmup compiles at
        zero. No-op without sharing (the copy path can't run)."""
        if self.ledger is not None and self.cache is not None:
            self._copy_row(self.scratch_slot, self.scratch_slot)

    def write(self, slots: Sequence[int], cache) -> None:
        with jax.profiler.TraceAnnotation(SPAN_WRITE):
            self.ensure(cache)
            idx = jnp.asarray(np.asarray(slots, np.int32))
            self.cache = self._write(self.cache, cache, idx)

    def write_shared(self, slots: Sequence[int], cache,
                     keys: Sequence[Optional[bytes]]) -> None:
        """Content-aware Refresh write: one batched scatter in which every
        row whose key is already resident under an owner slot is redirected
        to the scratch row (the device write is skipped; the logical slot
        becomes a referrer). Divergent rows (a slot re-keyed while owning
        shared bytes) promote their old content to a surviving referrer
        BEFORE the scatter lands. ``keys[j] is None`` (warmup/padding rows)
        bypasses the ledger entirely."""
        if self.ledger is None:
            raise RuntimeError("KVPool: write_shared on a pool constructed "
                               "without sharing=True")
        with jax.profiler.TraceAnnotation(SPAN_WRITE):
            self.ensure(cache)
            scatter = list(slots)
            for j, (s, key) in enumerate(zip(slots, keys)):
                if key is None or not 0 <= s < self.max_slots:
                    continue
                do_write, promote = self.ledger.record_write(s, key)
                if promote is not None:
                    self._copy_row(*promote)
                if not do_write:
                    scatter[j] = self.scratch_slot
            idx = jnp.asarray(np.asarray(scatter, np.int32))
            self.cache = self._write(self.cache, cache, idx)
        self.phys_peak = max(self.phys_peak, self.ledger.phys_slots)

    def rows(self, slots: Sequence[int]) -> np.ndarray:
        """Physical rows of logical ``slots`` ([n] int32): with sharing a
        referrer reads its owner's row — the one place logical slots
        translate to physical rows. The slot table of a Reuse that reads
        the pool in place, and the index of :meth:`gather`."""
        if self.ledger is not None:
            slots = [self.ledger.resolve(s) for s in slots]
        return np.asarray(slots, np.int32)

    def gather(self, slots: Sequence[int]):
        with jax.profiler.TraceAnnotation(SPAN_GATHER):
            return self._gather(self.cache, jnp.asarray(self.rows(slots)))
