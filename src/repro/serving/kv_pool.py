"""Slot-indexed multi-request KV pool state over the CHIME tiered stores.

The pool is the model's ordinary decode cache (`Model.init_cache`) with the
batch axis reinterpreted as *serving slots*: slot s holds the tiered
DRAM-hot / RRAM-cold KV state of whichever request currently occupies it.
Slot admission overwrites the slot with a freshly prefilled per-request
cache — including its per-slot endurance counters, which is what preserves
the writes<=1-per-cold-slot RRAM discipline across slot recycling.

This module is deliberately model-free: the cache layout lives in
`KVPoolState`, an explicit typed pytree (cache tree + static slot-axis
index per leaf), and `TieredKVPool` is pure host-side slot bookkeeping
over that state. The jitted cache arithmetic (insert / decode-step) is
owned by the executing `serving.backend.InferenceBackend`, which is what
lets the same pool run on the single-device vmapped path and on a
pjit-sharded mesh unmodified.

Cache pytree layout (from Model.init_cache): per scan-unit subtrees whose
leaves carry the slot axis at position 0, or 1 for scanned units (leading
layer-repeat axis). `batch_axes` materializes that axis index per leaf so
insert/reset/vmap all address the slot dimension uniformly.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kv_tiers as KT
from repro.models.counting import kv_elems_per_token, kv_scale_elems_per_token


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVPoolState:
    """Explicit pytree of a multi-slot KV pool.

    ``cache``: the slot-batched cache tree (arrays, or ShapeDtypeStructs
    for abstract use). ``axes``: a matching tree of ints giving each
    leaf's slot-axis index — static metadata, so a KVPoolState flows
    through jit/pjit with only the cache (and spill buffers) as traced
    children.

    ``spill``: the RRAM-backed spill store (preemption victims + idle
    cold-KV offloads) — a tree mirroring ``cache`` with the slot axis
    reinterpreted as *spill lanes*, or None until the first eviction
    materializes it (lazy: a pool that never spills never pays for the
    extra copy) or when the backend was built without lanes. A
    compressed-lane backend (`spill_compress`) stores the hot ring in
    int8 codec form, so the spill tree's STRUCTURE differs from the
    cache's — ``spill_axes`` then carries its own slot-axis tree (None
    means the lanes mirror ``cache`` and ``axes`` addresses both).
    ``spill_writes``: (n_lanes, n_endurance_blocks) int32
    cumulative RRAM write counters per lane (see
    `core.kv_tiers.bump_spill_writes`) — unlike the per-slot cache
    counters these never reset, because RRAM wear survives lane
    recycling.

    ``prefix``: the paged prefix-sharing block store (PR 7) — a tree
    shaped like the model's extend state with the batch axis
    reinterpreted as *block ids* and the sequence axis shrunk to
    ``block_tokens``: full-precision workspace K/V rows per block, plus
    per-block recurrent-state snapshots for SSM architectures. Lazy like
    ``spill``: None until the first prefix registration materializes it
    (an engine with paging off never pays the copy). ``prefix_axes``
    carries its block-axis index tree (static aux). Which block holds
    what is host-side state in `serving.block_pool.BlockPool`.
    """

    cache: dict
    axes: dict
    spill: dict | None = None
    spill_writes: jax.Array | None = None
    spill_axes: dict | None = None
    prefix: dict | None = None
    prefix_axes: dict | None = None

    @property
    def num_slots(self) -> int:
        leaf = jax.tree.leaves(self.cache)[0]
        return leaf.shape[jax.tree.leaves(self.axes)[0]]

    @property
    def num_spill_lanes(self) -> int:
        if self.spill is None:
            return 0
        axes = self.axes if self.spill_axes is None else self.spill_axes
        leaf = jax.tree.leaves(self.spill)[0]
        return leaf.shape[jax.tree.leaves(axes)[0]]

    def tree_flatten(self):
        axes_leaves, axes_def = jax.tree_util.tree_flatten(self.axes)
        sp_leaves, sp_def = jax.tree_util.tree_flatten(self.spill_axes)
        px_leaves, px_def = jax.tree_util.tree_flatten(self.prefix_axes)
        return ((self.cache, self.spill, self.spill_writes, self.prefix),
                (tuple(axes_leaves), axes_def, tuple(sp_leaves), sp_def,
                 tuple(px_leaves), px_def))

    @classmethod
    def tree_unflatten(cls, aux, children):
        axes = jax.tree_util.tree_unflatten(aux[1], list(aux[0]))
        spill_axes = jax.tree_util.tree_unflatten(aux[3], list(aux[2]))
        prefix_axes = jax.tree_util.tree_unflatten(aux[5], list(aux[4]))
        cache, spill, spill_writes, prefix = children
        return cls(cache=cache, axes=axes, spill=spill,
                   spill_writes=spill_writes, spill_axes=spill_axes,
                   prefix=prefix, prefix_axes=prefix_axes)


def batch_axes(model, cache: dict) -> dict:
    """Tree matching ``cache`` whose leaves give the slot-axis index."""
    axes = {}
    for ui, unit in enumerate(model.plan):
        a = 1 if unit.repeats > 1 else 0
        axes[f"u{ui}"] = jax.tree.map(lambda _: a, cache[f"u{ui}"])
    return axes


def tree_expand(tree: dict, axes: dict) -> dict:
    """Re-insert a size-1 slot axis (inside a vmap body)."""
    return jax.tree.map(lambda l, a: jnp.expand_dims(l, a), tree, axes)


def tree_squeeze(tree: dict, axes: dict) -> dict:
    return jax.tree.map(lambda l, a: jnp.squeeze(l, axis=a), tree, axes)


def map_spill_stores(tree, fn):
    """Rebuild a cache/spill tree with every TIERED store dict (one
    carrying a hot ring — 'hot', or its compressed 'hot_q' form) passed
    through ``fn``; flat stores and recurrent-state subtrees are left in
    place. This is the structural transform between a slot image and its
    compressed spill-lane form (and between their metadata trees — axis
    indices and shardings transform with `kv_tiers.spill_store_meta`)."""
    if isinstance(tree, dict):
        if "hot" in tree or "hot_q" in tree:
            return fn(tree)
        return {k: map_spill_stores(v, fn) for k, v in tree.items()}
    return tree


# keys of the sequence-store leaves inside a block cache; anything else
# (SSM states, rwkv token-mix state, cm_x_prev) is per-slot fixed-size
# DRAM state
_STORE_KEYS = frozenset({"hot", "cold_q", "cold_scale", "writes", "flat"})


def _charged_len(max_len: int, length: int | None,
                 block_tokens: int | None) -> int:
    """Sequence positions one occupant is CHARGED for.

    ``length=None`` keeps the historical worst case — every resident
    pays ``max_len`` regardless of its actual prompt+generation span.
    With a length, the charge is the request's own span rounded up to
    whole ``block_tokens`` pages (a paged allocator hands out whole
    blocks) and clamped to ``max_len``. This is the ACCOUNTING model the
    admission gate and the capacity bench price — the physical XLA slot
    buffers stay statically ``max_len``-shaped (simulated hardware, like
    every energy number in this repo); the paged engine's point is that
    a real block allocator would only materialize these bytes."""
    if length is None:
        return max_len
    bt = block_tokens or KT.ENDURANCE_BLOCK
    length = max(1, min(int(length), max_len))
    return min(-(-length // bt) * bt, max_len)


@dataclasses.dataclass(frozen=True)
class KVByteRates:
    """One slot's KV bytes as per-token terms: ``hot`` bytes per position
    up to ``hot_cap`` positions (the hot ring; every position of a flat
    cache), ``cold`` bytes per position (the int8 cold tier and its
    scales) and ``state`` bytes whatever the length (recurrent states).
    `slot_kv_bytes` prices a charged length with them; `live` counts the
    positions the slots hold."""
    hot: int
    hot_cap: int
    cold: int
    state: int

    @classmethod
    def of(cls, model, max_len: int) -> KVByteRates:
        cfg = model.cfg
        cd = jnp.dtype(cfg.compute_dtype).itemsize
        seq_elems = kv_elems_per_token(cfg)
        shapes, _ = model.cache_spec(1, max_len)
        state = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            key = path[-1].key if hasattr(path[-1], "key") \
                else str(path[-1])
            if key in _STORE_KEYS:
                continue
            nbytes = jnp.dtype(leaf.dtype).itemsize
            for d in leaf.shape:
                nbytes *= d
            state += nbytes
        if cfg.kv_policy == "tiered":
            cold = (seq_elems * jnp.dtype(jnp.int8).itemsize
                    + kv_scale_elems_per_token(cfg)
                    * jnp.dtype(jnp.float32).itemsize)
            return cls(seq_elems * cd, cfg.kv_hot_window, int(cold), state)
        return cls(seq_elems * cd, max_len, 0, state)

    def split(self, length: int) -> tuple[int, int]:
        """(hot, cold) bytes of one slot holding ``length`` positions."""
        return (self.hot * min(self.hot_cap, length) + self.state,
                self.cold * length)

    def live(self, pos: np.ndarray, active: np.ndarray) -> int:
        """Bytes the ``active`` slots hold at positions ``pos``."""
        p = pos[active].astype(np.int64)
        return int(self.hot * np.minimum(p, self.hot_cap).sum()
                   + self.cold * p.sum() + self.state * p.size)


def slot_kv_bytes(model, max_len: int, *, length: int | None = None,
                  block_tokens: int | None = None) -> tuple[int, int]:
    """(dram_hot_bytes, rram_cold_bytes) of ONE slot's cache.

    Hot ring, flat stores and SSM states live in the DRAM domain; the int8
    cold tier (+ its scales) is the RRAM budget. Endurance counters are
    bookkeeping, not capacity. The sequence-store sizes derive from
    `models/counting.kv_elems_per_token` — the same per-token element
    count behind the simulator's `kv_bytes_per_token` cost terms — so
    capacity admission and simulated efficiency share one KV byte math.

    ``length`` (with ``block_tokens``) switches from the worst-case
    ``max_len`` residency charge to a live block-granular charge for a
    request of that total span (see `_charged_len`) — what the paged
    admission gate and the capacity bench use so their math agrees with
    what paging actually allocates.
    """
    rates = KVByteRates.of(model, max_len)
    hot, cold = rates.split(_charged_len(max_len, length, block_tokens))
    return int(hot), int(cold)


def spill_lane_bytes(model, max_len: int, compressed: bool = False, *,
                     length: int | None = None,
                     block_tokens: int | None = None) -> int:
    """RRAM bytes ONE occupied spill lane pins while a request is parked.

    A verbatim lane holds the full slot image (hot + cold halves of
    `slot_kv_bytes`). A compressed lane stores the hot ring in the int8
    codec form — int8 payload + per-(token, head) f32 scales — while the
    cold tier, scales and recurrent states ride verbatim; with a flat
    (untiered) cache there is no hot ring and compression changes
    nothing. This is the byte the scheduler charges against the RRAM
    budget per parked request, and what `n_lanes = budget // lane_bytes`
    sizing should use — the capacity lever compressed lanes exist for.
    ``length``/``block_tokens`` apply the same live block-granular
    charge as `slot_kv_bytes` (a parked short request's image only
    covers its own blocks)."""
    hot, cold = slot_kv_bytes(model, max_len, length=length,
                              block_tokens=block_tokens)
    cfg = model.cfg
    if not compressed or cfg.kv_policy != "tiered":
        return hot + cold
    cd = jnp.dtype(cfg.compute_dtype).itemsize
    W = min(cfg.kv_hot_window,
            _charged_len(max_len, length, block_tokens))
    ring = kv_elems_per_token(cfg) * W * cd
    ring_q = kv_elems_per_token(cfg) * W          # int8 payload
    ring_scale = kv_scale_elems_per_token(cfg) * W \
        * jnp.dtype(jnp.float32).itemsize
    return hot - ring + ring_q + ring_scale + cold


class TieredKVPool:
    """Host-side slot bookkeeping over an explicit `KVPoolState`.

    Model-free by construction: the state layout and the jitted insert
    arithmetic come from the backend (`backend.make_pool()` wires them
    up), so the pool neither knows nor cares whether its arrays live on
    one device or a pjit mesh.
    """

    def __init__(self, state: KVPoolState, insert_fn, fresh_slot_fn,
                 num_spill_lanes: int | None = None):
        self.state = state
        self._insert_fn = insert_fn        # (state, req_cache, slot) -> state
        self._fresh_slot = fresh_slot_fn   # () -> batch-1 zero cache
        self.num_slots = state.num_slots
        self._free = list(range(self.num_slots))
        # spill lanes are reserved here but their arrays materialize
        # lazily (backend.evict_slot, on the first preemption)
        if num_spill_lanes is None:
            num_spill_lanes = state.num_spill_lanes
        self.num_spill_lanes = num_spill_lanes
        self._free_lanes = list(range(self.num_spill_lanes))

    # ---- views -------------------------------------------------------
    @property
    def cache(self) -> dict:
        return self.state.cache

    @property
    def axes(self) -> dict:
        return self.state.axes

    # ---- slot bookkeeping (host side) --------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> int:
        return self.num_slots - len(self._free)

    def alloc(self) -> int:
        return self._free.pop(0)

    def free(self, slot: int):
        assert 0 <= slot < self.num_slots and slot not in self._free
        self._free.append(slot)
        self._free.sort()

    # ---- spill-lane bookkeeping (host side) --------------------------
    @property
    def free_lanes(self) -> int:
        return len(self._free_lanes)

    def alloc_lane(self) -> int:
        return self._free_lanes.pop(0)

    def release_lane(self, lane: int):
        assert 0 <= lane < self.num_spill_lanes \
            and lane not in self._free_lanes
        self._free_lanes.append(lane)
        self._free_lanes.sort()

    # ---- cache ops ---------------------------------------------------
    def insert(self, req_cache: dict, slot):
        """Overwrite slot ``slot`` with a batch-1 per-request cache (this
        is also the endurance-counter reset on recycling)."""
        self.state = self._insert_fn(self.state, req_cache, slot)

    def reset(self, slot):
        """Zero a slot (explicit scrub; admission overwrites anyway)."""
        self.insert(self._fresh_slot(), slot)

    # ---- endurance audit ---------------------------------------------
    def worst_case_writes(self) -> jax.Array | None:
        """Elementwise max of every tiered store's per-slot endurance
        counters -> (num_slots, n_blocks), or None if nothing is tiered."""
        worst = None
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.state.cache)[0]:
            key = path[-1].key if hasattr(path[-1], "key") else ""
            if key != "writes":
                continue
            w = leaf
            if w.ndim == 3:              # (repeats, slots, blocks)
                w = jnp.max(w, axis=0)
            worst = w if worst is None else jnp.maximum(worst, w)
        return worst

    def endurance_report(self, prefill_lens, total_lens,
                         hot_window: int) -> dict:
        """Audit writes<=1-per-cold-slot for the CURRENT occupants.

        ``prefill_lens``/``total_lens``: per-slot token counts of the
        request that last occupied each slot (0 for never-used slots). A
        slot whose counters exceed the analytic expectation for its own
        occupancy was recycled without reset — the RRAM endurance
        violation this report exists to catch.

        Spill lanes are reported alongside: their counters are cumulative
        RRAM wear (one write per touched block per spill event, never
        reset on lane recycling). The spill keys are ALWAYS present —
        zero before the lazily-materialized lane arrays exist — so a
        report taken early in a run aggregates identically to one taken
        after the first spill, and ``total_rram_writes`` folds the lane
        writes into the cold-tier total unconditionally.
        """
        worst = self.worst_case_writes()
        if worst is None:
            rep = {"tiered": False, "write_once_ok": True,
                   "max_writes_per_cold_slot": 0.0}
        else:
            nb = worst.shape[1]
            expected = jnp.stack([
                KT.expected_block_writes(nb, hot_window, int(p), int(t))
                for p, t in zip(prefill_lens, total_lens)])
            excess = worst - expected
            ratio = worst / jnp.maximum(expected, 1)
            ratio = jnp.where((expected == 0) & (worst > 0), jnp.inf,
                              ratio)
            rep = {
                "tiered": True,
                "write_once_ok": bool(jnp.all(excess <= 0)),
                "max_writes_per_cold_slot": float(jnp.max(ratio)),
                "total_cold_writes": int(jnp.sum(worst)),
            }
        sw = self.state.spill_writes
        rep["spill_lanes"] = self.num_spill_lanes
        rep["total_spill_writes"] = 0 if sw is None else int(jnp.sum(sw))
        rep["max_spill_writes_per_block"] = \
            0 if sw is None else int(jnp.max(sw))
        rep["total_rram_writes"] = rep.get("total_cold_writes", 0) \
            + rep["total_spill_writes"]
        return rep
