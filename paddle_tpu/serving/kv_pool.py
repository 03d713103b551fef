"""Slot-pooled KV cache for continuous batching.

One fixed allocation ``[num_slots, max_seq, kv_heads, head_dim]`` per
layer per k/v holds EVERY in-flight request's context; a slot is one
request's row.  The pool never reallocates: admission writes a freshly
prefilled context into a free slot (``adopt``), eviction just returns the
slot index to the free list (the stale rows are overwritten by the next
occupant — and masked until then by the per-slot ``seq_lens`` feeding the
ragged decode-attention kernel, kernels/decode_attention.py).

The pool's per-layer view ``(k, v, pos_vector)`` is EXACTLY the models'
functional cache tuple with a per-row position (models/kv_cache.py), so
``model.decode_step`` runs over all slots unchanged — one fixed-shape
compiled program regardless of which slots are live.

A model may carry a SECOND kind of per-request state: a recurrent state
of fixed shape, whatever the request's length (a state-space layer's).
The model declares it per slot (``recurrent_state_spec``, a pytree of
``jax.ShapeDtypeStruct``; :func:`recurrent_state_spec`), and the pool
holds it as ``state``, the same pytree of ``[num_slots, ...]`` arrays,
opaque here: made with the pool, overwritten wholesale by ``adopt``,
threaded through the decode program by the engine.  Empty (``()``) for
every model that declares none.

What a cached position HOLDS is the model's to declare too
(:func:`cache_row`): ordinarily a K row and a V row of ``head_dim``
values a kv head; a model with latent attention holds ONE row kind
(``cfg.cache_row_kinds`` 1, ``cfg.cache_row_width`` values: the
compressed KV and the shared rotary key, models/deepseek_v3.py).  The
pool then makes no V slabs at all: ``vs`` is a list of ``None``, which
every program that takes ``(ks, vs)`` carries as no operand, and the
model's cache tuple is ``(rows, None, pos)``.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.kv_cache import gather_block_rows, scatter_block_rows

__all__ = ["KVPool", "BlockPool", "cache_geometry", "cache_row",
           "recurrent_state_spec", "state_bytes", "zero_state"]

# graftmem marker (tools/analysis/memory.py): every slab extent in the
# pool constructors below must flow from registered capacity fields —
# the derived blocks-per-row ratio is declared here so the capacity
# manifest can name it alongside the constructor parameters
__memory_capacity_fields__ = ("blocks_per_row", "v_slabs")


def cache_geometry(cfg) -> Tuple[int, int, int]:
    """``(planes, slabs, kv heads a slab holds)`` of a causal-LM's cache.

    A PLANE is one K and one V row per cached position: a model that
    applies each layer once has a plane per layer; a looped model has a
    plane per pass per layer and says so in ``cfg.num_cache_layers``.
    A SLAB is one array of the pool's lists.  Ordinarily a slab is a
    plane; a model whose program loops over its planes reaches them by
    a traced index, so it keeps ``cfg.cache_planes_per_slab`` planes
    side by side on one slab's head axis (models/ouro.py).  ``kv_heads``
    falls back to ``num_heads`` for MHA models like GPT."""
    planes = getattr(cfg, "num_cache_layers", None) or cfg.num_layers
    per_slab = getattr(cfg, "cache_planes_per_slab", None) or 1
    if planes % per_slab:
        raise ValueError(f"cache_planes_per_slab {per_slab} must divide "
                         f"the {planes} cache planes")
    kv_heads = getattr(cfg, "kv_heads", None) or cfg.num_heads
    return planes, planes // per_slab, per_slab * kv_heads


def cache_row(cfg) -> Tuple[int, int]:
    """``(row kinds, values a kv head)`` of one cached position of one
    plane: ``(2, head_dim)``, a K row and a V row, unless the model says
    otherwise (``cfg.cache_row_kinds`` / ``cfg.cache_row_width``)."""
    kinds = getattr(cfg, "cache_row_kinds", None) or 2
    if kinds not in (1, 2):
        raise ValueError(f"cache_row_kinds {kinds}: a position holds one "
                         f"row kind or a K and a V")
    width = getattr(cfg, "cache_row_width", None) or cfg.head_dim
    return kinds, width


def recurrent_state_spec(model):
    """The per-slot recurrent state ``model`` declares beside its KV
    rows: a pytree of ``jax.ShapeDtypeStruct`` (shapes WITHOUT the slot
    axis), ``()`` where the model declares none."""
    declare = getattr(model, "recurrent_state_spec", None)
    return () if declare is None else declare()


def state_bytes(spec) -> int:
    """Bytes one slot's recurrent state holds."""
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
               for s in jax.tree_util.tree_leaves(spec))


def zero_state(spec, rows: int):
    """``spec``'s pytree as ``[rows, ...]`` arrays of zeros: where a
    fresh sequence starts its recurrence."""
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros((rows,) + tuple(s.shape), s.dtype), spec)


@functools.partial(jax.jit, donate_argnums=(0,))
def _adopt_state(state, rows, slot):
    """Write a request's ``[1, ...]`` recurrent state into row ``slot``
    of every ``[num_slots, ...]`` leaf: ONE program for the pytree."""
    return jax.tree_util.tree_map(
        lambda buf, row: jax.lax.dynamic_update_slice(
            buf, row.astype(buf.dtype), (slot,) + (0,) * (buf.ndim - 1)),
        state, rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def _adopt_row(buf, row, slot):
    """Write a [1, max_seq, h, d] prefilled row into slab row ``slot``.
    One compiled program per (shape, dtype) — ``slot`` stays traced."""
    return jax.lax.dynamic_update_slice(buf, row, (slot, 0, 0, 0))


class KVPool:
    """Fixed-shape KV slab + free-list slot accounting.

    Device state:
      * ``ks/vs``   — per-layer [num_slots, max_seq, kv_heads, head_dim]
        (``vs`` a list of ``None`` where a position holds one row kind:
        ``row_kinds`` 1, :func:`cache_row`);
      * ``seq_pos`` — [num_slots] int32, each slot's current cache length
        (the per-row ``pos`` the models append at AND the ``seq_lens`` the
        ragged attention masks by, after the in-step +1);
      * ``state``   — the model's recurrent state, a pytree of
        ``[num_slots, ...]`` arrays (``()`` for most models).  A free
        slot's row keeps whatever its last occupant (and the ride-along
        decode steps since) left: ``adopt`` overwrites it wholesale.

    Host state: the free list.  Alloc/free/reset are host-side list ops —
    no device sync, no reallocation.
    """

    def __init__(self, num_slots: int, max_seq: int, num_layers: int,
                 kv_heads: int, head_dim: int, dtype=jnp.float32,
                 mesh=None, planes: Optional[int] = None, state_spec=(),
                 row_kinds: int = 2):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if mesh is not None and row_kinds != 2:
            raise ValueError("a cache of one row kind has no "
                             "tensor-parallel layout")
        if mesh is not None and jax.tree_util.tree_leaves(state_spec):
            raise ValueError("a recurrent state has no tensor-parallel "
                             "layout")
        if mesh is not None and kv_heads % mesh.devices.size:
            raise ValueError(
                f"kv_heads {kv_heads} must divide evenly over the "
                f"{mesh.devices.size}-device tensor-parallel mesh (the "
                f"slot slabs partition on the kv-head axis)")
        self.num_slots = num_slots
        self.max_seq = max_seq
        # num_layers counts the SLABS of the lists below; planes the K/V
        # rows a cached position spans (cache_geometry: they differ for
        # a looped model only)
        self.num_layers = num_layers
        self.planes = planes if planes is not None else num_layers
        self.mesh = mesh
        self.row_kinds = row_kinds
        # the V slabs: as many as the K slabs, or none where a position
        # holds one row kind (graftmem counts both lists by these names)
        v_slabs = num_layers * (row_kinds - 1)
        shape = (num_slots, max_seq, kv_heads, head_dim)
        # bytes ONE cached position holds over every slab
        self.row_bytes = (row_kinds * num_layers * kv_heads * head_dim
                          * jnp.dtype(dtype).itemsize)
        if mesh is None:
            self.ks: List[jax.Array] = [jnp.zeros(shape, dtype)
                                        for _ in range(num_layers)]
            self.vs: List[Optional[jax.Array]] = [
                jnp.zeros(shape, dtype) for _ in range(v_slabs)]
            self.seq_pos = jnp.zeros((num_slots,), jnp.int32)
        else:
            # tensor-parallel serving (serving/tp.py): slabs partition
            # on the kv-head axis, the position vector replicates —
            # every compiled program touching the pool then compiles
            # against the sharded layout.  Born SHARDED (jit with
            # out_shardings), never materialized whole on one device:
            # at pod scale the full slab may not fit a single chip —
            # that is the point of sharding it
            from .tp import sharded_zeros, replicated
            mk = sharded_zeros(mesh, shape, dtype)
            self.ks = [mk() for _ in range(num_layers)]
            self.vs = [mk() for _ in range(num_layers)]
            self.seq_pos = replicated(
                jnp.zeros((num_slots,), jnp.int32), mesh)
        if row_kinds == 1:
            # the programs' ``(ks, vs)`` lists pair up slab by slab: a
            # ``None`` is no operand, and the model's tuple is
            # ``(rows, None, pos)``
            self.vs = [None] * num_layers
        self.state_spec = state_spec
        self.state_bytes_per_slot = state_bytes(state_spec)
        self.state = zero_state(state_spec, num_slots)
        self._free: List[int] = list(range(num_slots - 1, -1, -1))
        # lifetime slot-churn counters (telemetry: metrics_dict reports
        # them; high churn relative to finished requests = thrashing)
        self.alloc_count = 0
        self.free_count = 0
        # chaos hook (serving/faults.py): None in production — the only
        # overhead when off is this attribute test in alloc()
        self.faults = None

    @classmethod
    def create(cls, model, num_slots: int,
               max_seq: Optional[int] = None, mesh=None) -> "KVPool":
        """Size the pool from a causal-LM's config
        (:func:`cache_geometry`).  With ``mesh`` the slabs lay out
        kv-head-sharded over the tensor-parallel mesh."""
        cfg = model.cfg
        max_seq = max_seq or cfg.max_seq_len
        planes, slabs, slab_heads = cache_geometry(cfg)
        kinds, width = cache_row(cfg)
        return cls(num_slots, max_seq, slabs, slab_heads, width,
                   dtype=jnp.dtype(cfg.dtype), mesh=mesh, planes=planes,
                   state_spec=recurrent_state_spec(model), row_kinds=kinds)

    # ------------------------------------------------------------ slots
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def used_slots(self) -> int:
        return self.num_slots - len(self._free)

    def alloc(self) -> int:
        """Claim a free slot (lowest index first, so slot churn reuses a
        warm row).  Raises if the pool is full — the scheduler gates
        admission on ``free_slots``."""
        if self.faults is not None:
            self.faults.fire("kv_alloc")
        if not self._free:
            raise RuntimeError("KVPool exhausted: no free slot")
        self.alloc_count += 1
        return self._free.pop()

    def free(self, slot: int) -> None:
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range")
        if slot in self._free:
            raise ValueError(f"slot {slot} already free (double free)")
        self.free_count += 1
        self._free.append(slot)
        self._free.sort(reverse=True)
        self.park(slot)

    def park(self, slot: int) -> None:
        """Hold ``slot``'s row at position 0: the decode program rides it
        along as a no-op (its writes stay at the row head, bounded, until
        the next adopt overwrites it; a routing model sends it to no
        expert).  ``free`` parks what it frees; the engine parks a slot
        AHEAD of its release where the host already knows that the
        token still on the device is the request's last (engine.py, "one
        program ahead")."""
        self.seq_pos = self.seq_pos.at[slot].set(0)

    def reset(self) -> None:
        """Return every slot to the free list; buffers stay allocated
        (stale rows are masked by seq_pos=0 until overwritten)."""
        self._free = list(range(self.num_slots - 1, -1, -1))
        self.seq_pos = jnp.zeros((self.num_slots,), jnp.int32)
        if self.mesh is not None:
            from .tp import replicated
            self.seq_pos = replicated(self.seq_pos, self.mesh)

    def adopt(self, slot: int, layer_caches, length: int,
              set_pos: bool = True, state=None) -> None:
        """Move a freshly prefilled single-request cache (per-layer
        ``(k [1, max_seq, h, d], v, _)`` tuples, ``v`` None where the
        pool holds one row kind) into ``slot`` and record
        its ``length`` valid positions.  The copy is a jitted
        dynamic_update_slice with a traced slot index — admitting to a
        different slot never recompiles.  ``state`` is the request's
        recurrent state after its last prompt token (``[1, ...]``
        leaves), written over the slot's row wholesale.

        ``set_pos=False`` skips the position write: the fleet KV handoff
        (serving/handoff.py) stages transferred rows through a transient
        slot purely as the scatter program's source — no decode ever
        reads the slot, so updating (and then re-zeroing) ``seq_pos``
        would be two wasted device ops per transfer."""
        s = jnp.asarray(slot, jnp.int32)
        for i, layer in enumerate(layer_caches):
            self.ks[i] = _adopt_row(self.ks[i], layer[0], s)
            if self.vs[i] is not None:
                self.vs[i] = _adopt_row(self.vs[i], layer[1], s)
        if self.state_bytes_per_slot:
            if state is None:
                raise ValueError("adopt: the pool holds a recurrent "
                                 "state and was given none")
            self.state = _adopt_state(self.state, state, s)
        if set_pos:
            self.seq_pos = self.seq_pos.at[slot].set(length)

    # ------------------------------------------------------- cache views
    def caches(self) -> List[Tuple[jax.Array, jax.Array, jax.Array]]:
        """The models' cache pytree over all slots: per-layer
        ``(k, v, seq_pos)`` with the SHARED per-slot position vector."""
        return [(k, v, self.seq_pos) for k, v in zip(self.ks, self.vs)]

    def update(self, new_caches) -> None:
        """Absorb the cache pytree a decode step returned (every layer
        advanced the shared position vector identically — keep layer 0's)."""
        self.ks = [c[0] for c in new_caches]
        self.vs = [c[1] for c in new_caches]
        self.seq_pos = new_caches[0][2]


class BlockPool:
    """The SECOND fixed-shape KV slab: per-layer
    ``[num_blocks, block_len, kv_heads, head_dim]`` block rows holding
    cached PREFIX context, shared across requests.  The radix tree
    (serving/prefix_cache.py) owns which block holds which token span —
    this class owns only the device memory and the two compiled copy
    programs:

      * ``load_row(idx)``   — gather ``max_seq // block_len`` block rows
        into a fresh ``[1, max_seq]`` cache row (the staging cache a
        matched request prefills its suffix into).  ``idx`` is traced row
        data padded arbitrarily past the true match count (stale gathers
        are masked downstream by ``seq_lens``), so ONE program serves
        every match length;
      * ``store_row(ks, vs, slot, dest)`` — split pool slot ``slot``'s
        row into blocks and scatter block j to ``dest[j]``; ``dest``
        entries set to ``num_blocks`` are out-of-bounds and DROPPED, so
        the same single program writes any subset of a prompt's blocks.

    Like ``KVPool``, buffers never reallocate; block lifecycle (alloc /
    free / refcount / LRU) is host-side list accounting.
    """

    def __init__(self, num_blocks: int, block_len: int, max_seq: int,
                 num_layers: int, kv_heads: int, head_dim: int,
                 dtype=jnp.float32, mesh=None):
        if num_blocks < 1:
            raise ValueError("num_blocks must be >= 1")
        if block_len < 1:
            raise ValueError("block_len must be >= 1")
        if max_seq % block_len:
            raise ValueError(
                f"block_len {block_len} must divide max_seq {max_seq} "
                f"(block boundaries must tile the slot row)")
        self.num_blocks = num_blocks
        self.block_len = block_len
        self.max_seq = max_seq
        self.num_layers = num_layers
        self.mesh = mesh
        self.blocks_per_row = max_seq // block_len
        shape = (num_blocks, block_len, kv_heads, head_dim)
        if mesh is None:
            self.bks: List[jax.Array] = [jnp.zeros(shape, dtype)
                                         for _ in range(num_layers)]
            self.bvs: List[jax.Array] = [jnp.zeros(shape, dtype)
                                         for _ in range(num_layers)]
        else:
            # radix block slab partitions on the SAME kv-head axis as
            # the slot slabs (so the gather/scatter copy programs move
            # blocks without cross-device traffic), and is likewise
            # born sharded — never whole on one device
            from .tp import sharded_zeros
            mk = sharded_zeros(mesh, shape, dtype)
            self.bks = [mk() for _ in range(num_layers)]
            self.bvs = [mk() for _ in range(num_layers)]
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self.trace_counts = {"gather": 0, "scatter": 0}
        self._load_fn = None
        self._store_fn = None
        # chaos hook (serving/faults.py): None in production
        self.faults = None

    @classmethod
    def create(cls, model, num_blocks: int, block_len: int,
               max_seq: int, mesh=None) -> "BlockPool":
        cfg = model.cfg
        _, slabs, slab_heads = cache_geometry(cfg)
        kinds, width = cache_row(cfg)
        if kinds != 2:
            raise ValueError("the block pool holds K and V blocks: a "
                             "cache of one row kind has no blocks here")
        return cls(num_blocks, block_len, max_seq, slabs, slab_heads,
                   width, dtype=jnp.dtype(cfg.dtype), mesh=mesh)

    # ------------------------------------------------------------ blocks
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - len(self._free)

    def alloc(self) -> int:
        if self.faults is not None:
            self.faults.fire("block_alloc")
        if not self._free:
            raise RuntimeError("BlockPool exhausted: no free block")
        return self._free.pop()

    def free(self, block: int) -> None:
        if not 0 <= block < self.num_blocks:
            raise ValueError(f"block {block} out of range")
        if block in self._free:
            raise ValueError(f"block {block} already free (double free)")
        self._free.append(block)

    # ---------------------------------------------------- copy programs
    def _build_load_fn(self):
        """The gather program factory — shared by the lazy trace in
        :meth:`load_row` and the AOT builder (serving/aot.py), so the
        exported artifact and the traced program are one body."""
        def load(bks, bvs, idx):
            self.trace_counts["gather"] += 1   # trace-time tick
            ks = [gather_block_rows(b, idx)[None] for b in bks]
            vs = [gather_block_rows(b, idx)[None] for b in bvs]
            return ks, vs

        return jax.jit(load)

    def _build_store_fn(self):
        """The scatter program factory (same sharing contract as
        :meth:`_build_load_fn`)."""
        n = (1, self.max_seq) + self.bks[0].shape[2:]

        def store(bks, bvs, ks, vs, slot, dest):
            self.trace_counts["scatter"] += 1  # trace-time tick
            start = (slot, 0, 0, 0)
            new_bks = [
                scatter_block_rows(
                    b, jax.lax.dynamic_slice(k, start, n)[0], dest)
                for b, k in zip(bks, ks)]
            new_bvs = [
                scatter_block_rows(
                    b, jax.lax.dynamic_slice(v, start, n)[0], dest)
                for b, v in zip(bvs, vs)]
            return new_bks, new_bvs

        return jax.jit(store, donate_argnums=(0, 1))

    def load_row(self, idx) -> Tuple[List[jax.Array], List[jax.Array]]:
        """Gather blocks ``idx`` ([blocks_per_row] int32, padded past the
        match with any in-bounds value) into per-layer ``[1, max_seq, h,
        d]`` staging rows."""
        if self.faults is not None:
            self.faults.fire("gather")
        if self._load_fn is None:
            self._load_fn = self._build_load_fn()
        return self._load_fn(self.bks, self.bvs,
                             jnp.asarray(idx, jnp.int32))

    def store_row(self, pool: KVPool, slot: int, dest) -> None:
        """Scatter pool slot ``slot``'s row into block rows ``dest``
        ([blocks_per_row] int32; entries == num_blocks are dropped).
        Donates the block slabs — cache memory stays one allocation."""
        if self.faults is not None:
            self.faults.fire("scatter")
        if self._store_fn is None:
            self._store_fn = self._build_store_fn()
        self.bks, self.bvs = self._store_fn(
            self.bks, self.bvs, pool.ks, pool.vs,
            jnp.asarray(slot, jnp.int32), jnp.asarray(dest, jnp.int32))
