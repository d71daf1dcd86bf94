"""Backend-executor seam: `InferenceBackend` is the protocol the serving
engine drives, decoupling scheduling from execution strategy.

A backend owns three things the engine must never reach into directly:

  * the parameters (placed however the backend likes — host, one device,
    or pjit-sharded over a mesh),
  * the KV pool layout, handed out as an explicit typed pytree
    (`kv_pool.KVPoolState`) rather than a model-aware object, and
  * the jitted step programs: `extend_step(batch, state, ext, slot, pos,
    length, commit)` — the unified multi-token cache extension (chunked
    prefill directly into an already-allocated pool slot) — and
    `decode_step(toks, state, pos, active)` (one token on every active
    slot).

The old two-phase admission surface — `prefill(batch, length)` building a
detached batch-1 cache, then `insert(state, req_cache, slot)` scattering
it into the pool — is subsumed by `extend_step`: the final (``commit``)
chunk folds the in-flight workspace into the flat/tiered stores and
scatters them into the slot inside one jitted program. (The `prefill` /
`insert` deprecation shims rode for their one release and are gone.)

Backends also carry the PAGED PREFIX BLOCK STORE (PR 7): a lazy tree of
``prefix_blocks`` x ``block_tokens`` full-precision workspace K/V rows
(plus per-block recurrent-state snapshots), with four tiny jitted block
copies — `prefix_save_ws`/`prefix_load_ws` move one block's rows between
the store and an in-flight extend workspace, `prefix_save_state`/
`prefix_load_state` snapshot/seed the SSM states. Which block holds
which prefix is host-side state in `serving.block_pool.BlockPool`; the
engine seeds hit blocks into a fresh workspace at admission ("gather on
admit") and registers new blocks at commit, so decode and the committed
slot layout are completely untouched — which is why a paged engine holds
exact token parity with the slot-pool oracle.

Two implementations ship:

  * `LocalBackend` — the single-host vmapped path (the seed engine's
    jitted closures, extracted verbatim): one jit-compiled step advances
    every slot, each slot attending its own hot ring + cold tier at its
    own position.
  * `ShardedBackend` — the same step jaxpr executed under pjit on a
    `launch/mesh.py` mesh: params are placed by the model's
    `param_shardings` rules and the KV pool by `Model.cache_shardings`
    (slots -> 'data', cold kv_seq / kv heads -> 'model', divisibility
    permitting). The decode jaxpr is built from a rules-free model twin
    and the layout is pinned with sharding constraints at the jit
    boundaries only, so a 1-device mesh is token-for-token identical to
    `LocalBackend` (tests/test_serving_sharded.py holds both meshes to
    exact parity).

The engine, scheduler, metrics and endurance audit run unmodified on
either backend; this seam is where later scale-out work (multi-host,
async prefill, disaggregated tiers) plugs in.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import kv_tiers as KT
from repro.models import Model
from repro.models.counting import streamed_unit_indices, weight_stream_split
from repro.serving.kv_pool import (KVByteRates, KVPoolState, TieredKVPool,
                                   batch_axes, map_spill_stores,
                                   slot_kv_bytes, spill_lane_bytes,
                                   tree_expand, tree_squeeze)
from repro.serving.telemetry import span, tracing
from repro.sharding import ShardingRules


def _resolve_spill_compress(flag: bool | None) -> bool:
    """Resolve the compressed-spill-lane knob: an explicit bool wins;
    None consults ``REPRO_SERVE_SPILL_COMPRESS`` (unset/empty/"0" = off,
    anything else = on — an env var must never wedge startup)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_SERVE_SPILL_COMPRESS",
                          "").strip() not in ("", "0")


def _resolve_fused_decode(flag: bool | None) -> bool:
    """Resolve the fused paged-decode knob: an explicit bool wins; None
    consults ``REPRO_SERVE_FUSED_DECODE`` (unset/empty/"0" = off)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_SERVE_FUSED_DECODE",
                          "").strip() not in ("", "0")


def _resolve_sparse_read(tau: float | None) -> float:
    """Resolve the SLIM-style sparse-read threshold: an explicit float
    wins; None consults ``REPRO_SERVE_SPARSE_READ``. Unparsable or
    negative values resolve to 0.0 (off) — an env var must never wedge
    startup."""
    if tau is not None:
        return max(float(tau), 0.0)
    raw = os.environ.get("REPRO_SERVE_SPARSE_READ", "").strip()
    if not raw:
        return 0.0
    try:
        return max(float(raw), 0.0)
    except ValueError:
        return 0.0


def _resolve_weight_stream(layers: int | None) -> int:
    """Resolve the RRAM weight-streaming window (W = DRAM sliding-window
    repeats per streamed scan unit; 0 = off): an explicit value wins;
    None consults ``REPRO_SERVE_WEIGHT_STREAM``. Unparsable or negative
    values resolve to 0 — an env var must never wedge startup."""
    if layers is not None:
        return max(int(layers), 0)
    raw = os.environ.get("REPRO_SERVE_WEIGHT_STREAM", "").strip()
    if not raw:
        return 0
    try:
        return max(int(raw), 0)
    except ValueError:
        return 0


@runtime_checkable
class InferenceBackend(Protocol):
    """What the engine needs from an executor. Any object with this
    surface can serve; the engine never touches model internals."""

    num_slots: int            # decode slots the pool is laid out for
    max_len: int              # per-slot KV length
    hot_window: int           # effective hot-ring length (endurance audit)
    requires_exact_prefill: bool   # recurrent states forbid padded chunks
    chunk_unit: int           # non-final chunk lengths must be multiples
    #   of this (cfg.ssm.chunk_size for recurrent archs, else 1) so the
    #   model's canonical SSM chunk grid stays split-invariant
    n_spill: int              # RRAM spill lanes for preempted slots (0 =
    #   preemption disabled); lane ARRAYS materialize lazily on the
    #   first eviction, so unpreempted pools never pay the extra copy
    spill_compress: bool      # opt-in int8 hot-ring spill codec: lanes
    #   store the hot window requantized (kv_tiers.spill_store_compress)
    #   so a parked image costs ~the cold tier's bytes; restore is then
    #   requantization-aware and bounded-error instead of bit-exact (the
    #   cold tier, scales, recurrent states and flat stores still ride
    #   verbatim). Default off: REPRO_SERVE_SPILL_COMPRESS / CLI
    #   --spill-compress.
    block_tokens: int          # prefix-page granularity (tokens/block);
    #   defaults to core.kv_tiers.ENDURANCE_BLOCK clamped to max_len and
    #   rounded to the chunk grid for recurrent architectures
    prefix_blocks: int         # physical blocks in the prefix store
    fused_decode: bool        # opt-in fused paged-decode attention
    #   (kernels/paged_decode.py): decode streams K/V pages straight
    #   from the tiered layout with in-kernel int8 dequant. GQA-only —
    #   resolves to off for architectures with no GQA attention layer.
    #   Default off: REPRO_SERVE_FUSED_DECODE / CLI --fused-decode.
    sparse_read_tau: float    # SLIM-style adaptive-threshold sparse
    #   read inside the fused kernel (0.0 = exact). Only meaningful
    #   with fused_decode; REPRO_SERVE_SPARSE_READ / CLI --sparse-read.
    weight_stream: int        # RRAM weight-streaming window W (repeats
    #   of each streamed scan unit kept DRAM-resident; 0 = off, the
    #   whole param set DRAM-resident). Resolves to 0 when nothing would
    #   actually stream (no scanned unit deeper than the window), so the
    #   knob stays truthful for the scheduler's weight charge and sim
    #   pricing. REPRO_SERVE_WEIGHT_STREAM / CLI --weight-stream.

    def weight_bytes(self) -> tuple[int, int]:
        """(dram_resident, rram_streamed) param bytes under the resolved
        weight-streaming window — what the engine hands the scheduler's
        DRAM weight charge (whole set DRAM-resident at W=0)."""
        ...

    def slot_kv_bytes(self, *, length: int | None = None
                      ) -> tuple[int, int]:
        """(dram_hot, rram_cold) bytes one resident request pins —
        worst-case ``max_len`` residency by default, or the live
        block-granular charge for a request of total span ``length``
        (what the paged admission gate prices)."""
        ...

    def spill_lane_bytes(self) -> int:
        """RRAM bytes one OCCUPIED spill lane pins (the scheduler's
        per-parked-image charge; smaller under spill_compress)."""
        ...

    def sim_context(self) -> tuple:
        """(model config, spill_compress) — what the telemetry ledger
        needs to price traffic through the analytical simulator. The
        engine degrades to a ledger-less telemetry hub when a custom
        backend lacks this (it probes via getattr)."""
        ...

    def make_pool(self) -> TieredKVPool:
        """Fresh slot pool wired to this backend's insert arithmetic."""
        ...

    def fresh_extend(self) -> dict:
        """Zero chunk-resumable prefill state (one in-flight lane); built
        once and reused — every extend is functional."""
        ...

    def extend_step(self, batch: dict, state: KVPoolState, ext: dict,
                    slot, pos, length, commit: bool
                    ) -> tuple[jax.Array | None, dict | None, KVPoolState]:
        """Advance one in-flight prefill by a chunk of ``length`` valid
        tokens at absolute position ``pos``. Non-commit chunks return
        (None, new_ext, state-unchanged); the ``commit`` chunk folds the
        workspace into the stores, scatters them into pool slot ``slot``
        and returns (first greedy token, None, new state)."""
        ...

    def decode_step(self, toks, state: KVPoolState, pos, active
                    ) -> tuple[jax.Array, KVPoolState]:
        """One greedy token on every active slot; inactive slots' cache
        is kept verbatim (no phantom appends, no endurance drift)."""
        ...

    def evict_slot(self, state: KVPoolState, slot, lane, length
                   ) -> KVPoolState:
        """Pack slot ``slot``'s cache into RRAM spill lane ``lane``
        (verbatim by default; hot ring int8-requantized under
        spill_compress) and bump that lane's per-block endurance
        counters for a ``length``-token context (one write per touched
        block — the one-shot `store_from_full`-style image write,
        whatever the representation)."""
        ...

    def restore_slot(self, state: KVPoolState, lane, slot
                     ) -> KVPoolState:
        """Scatter spill lane ``lane`` back into pool slot ``slot``.
        Bit-exact when the image was packed verbatim — resumed decode is
        token-for-token identical to never-evicted decode; under
        spill_compress the hot ring dequantizes within the documented
        codec bound instead. Restore writes land in DRAM, so no RRAM
        counters move."""
        ...


def _launch(program, args: tuple, *scalars):
    """``program(*args, *scalars)``, the scalars copied to the device as
    int32 first: the copies inside the span
    ``repro.backend.<program's name>.put``, the call inside ``.launch``."""
    name = "repro.backend." + program.__name__
    with span(name + ".put"):
        scalars = [jnp.asarray(s, jnp.int32) for s in scalars]
    with span(name + ".launch"):
        return program(*args, *scalars)


class _JittedBackend:
    """Shared scaffolding: validates the config, derives the slot-axis
    tree, and builds the three jitted programs (step / prefill / insert).
    Subclasses steer placement via `_place` and `_constrain`."""

    def __init__(self, model: Model, params, num_slots: int, max_len: int,
                 n_spill: int | None = None,
                 spill_compress: bool | None = None,
                 prefix_blocks: int | None = None,
                 block_tokens: int | None = None,
                 fused_decode: bool | None = None,
                 sparse_read: float | None = None,
                 weight_stream: int | None = None):
        cfg = model.cfg
        if cfg.is_encoder:
            raise ValueError("encoder-only model cannot be served")
        if num_slots is None or max_len is None:
            raise TypeError("backend needs num_slots and max_len")
        if num_slots < 1:
            raise ValueError("backend needs at least one decode slot")
        if n_spill is None:
            n_spill = num_slots      # preemption available out of the box
        if n_spill < 0:
            raise ValueError("backend needs n_spill >= 0")
        # fused paged-decode attention is GQA-only (apply_mla_decode
        # keeps the unfused oracle), so the flag resolves to off for
        # architectures with no GQA attention layer — keeping the knob
        # truthful for sim pricing and the CLI report, exactly like
        # spill_compress on a flat cache. The sparse-read threshold only
        # exists inside the fused kernel, so it follows the same gate.
        has_gqa = any(u.block.mixer in ("attn", "attn_shared")
                      for u in model.plan)
        if fused_decode is None and getattr(cfg, "fused_decode", False):
            fused_decode = True       # cfg flag wins over an unset env var
        if sparse_read is None and getattr(cfg, "sparse_read_tau", 0.0):
            sparse_read = cfg.sparse_read_tau
        self.fused_decode = _resolve_fused_decode(fused_decode) and has_gqa
        self.sparse_read_tau = (_resolve_sparse_read(sparse_read)
                                if self.fused_decode else 0.0)
        if (self.fused_decode != bool(getattr(cfg, "fused_decode", False))
                or self.sparse_read_tau
                != float(getattr(cfg, "sparse_read_tau", 0.0))):
            cfg = cfg.replace(fused_decode=self.fused_decode,
                              sparse_read_tau=self.sparse_read_tau)
            model = Model(cfg, model.rules)
        # RRAM weight streaming: same precedence discipline (explicit
        # arg > cfg flag > env), and the same truthfulness gate — the
        # window resolves to 0 when no scan unit would actually stream
        # (python-loop layers, or nothing deeper than W repeats), so the
        # scheduler's weight charge and the sim pricing never claim a
        # transfer the model does not perform.
        if weight_stream is None and getattr(cfg, "weight_stream_layers",
                                             0):
            weight_stream = cfg.weight_stream_layers
        W = _resolve_weight_stream(weight_stream)
        if W and not streamed_unit_indices(
                cfg.replace(weight_stream_layers=W)):
            W = 0
        self.weight_stream = W
        if W != int(getattr(cfg, "weight_stream_layers", 0) or 0):
            cfg = cfg.replace(weight_stream_layers=W)
            model = Model(cfg, model.rules)
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.n_spill = n_spill
        self.hot_window = min(cfg.kv_hot_window, max_len)
        # recurrent (SSM) prefill states are cumulative over the whole
        # padded sequence, so those architectures need exact-length prefill
        self.requires_exact_prefill = any(
            u.block.mixer in ("rwkv6", "mamba2") for u in model.plan)
        # non-final chunks must land on the canonical SSM chunk grid for
        # chunked prefill to stay bit-identical to whole-prompt prefill
        self.chunk_unit = (cfg.ssm.chunk_size
                           if self.requires_exact_prefill and cfg.ssm
                           else 1)
        shapes, _ = model.cache_spec(num_slots, max_len)
        self._axes = batch_axes(model, shapes)
        # compressed spill lanes restructure the tiered stores (hot ->
        # hot_q/hot_scale), so the lane tree carries its own axis tree.
        # A flat cache has no hot ring — nothing to compress — so the
        # flag resolves to off there, keeping spill_compress truthful
        # for lane-byte accounting, sim pricing and the CLI report.
        self.spill_compress = _resolve_spill_compress(spill_compress) \
            and cfg.kv_policy == "tiered"
        self._spill_axes = (map_spill_stores(self._axes,
                                             KT.spill_store_meta)
                            if self.spill_compress else self._axes)
        # paged prefix-store geometry (PR 7): the page size defaults to
        # the RRAM endurance-block granularity, rounded to the canonical
        # chunk grid for recurrent architectures (a state snapshot off
        # the grid could never seed a bit-identical resume) and clamped
        # to the slot length; the store defaults to enough blocks to
        # re-page every slot. Arrays and traces are lazy — an engine
        # that never pages pays nothing here.
        bt = block_tokens if block_tokens is not None \
            else min(KT.ENDURANCE_BLOCK, max_len)
        if bt < 1:
            raise ValueError(f"block_tokens must be >= 1, got {bt}")
        if self.chunk_unit > 1:
            bt = max((bt // self.chunk_unit) * self.chunk_unit,
                     self.chunk_unit)
        self.block_tokens = min(bt, max_len)
        if prefix_blocks is None:
            prefix_blocks = num_slots * (-(-max_len // self.block_tokens))
        if prefix_blocks < 1:
            raise ValueError(f"prefix_blocks must be >= 1, got "
                             f"{prefix_blocks}")
        self.prefix_blocks = prefix_blocks
        ext_shapes, _ = model.extend_spec(1, max_len)
        self._ext_axes = batch_axes(model, ext_shapes)
        self.has_prefix_ws = any(
            str(getattr(p[-1], "key", p[-1])).endswith("_ws")
            for p, _ in jax.tree_util.tree_flatten_with_path(ext_shapes)[0])
        self._zero_slot = None
        self._zero_ext = None
        # the KV pool's counters on the decode launch's span: every slot
        # at max_len, and the active slots at their positions
        self._kv_rates = KVByteRates.of(model, max_len)
        self.kv_pool_bytes = num_slots * sum(self._kv_rates.split(max_len))
        # each jitted program's function name is its XLA module's name
        # (`jit_step`, `jit_ext_part`, ...), which the benchmark's trace
        # readers select by, and names its spans (`_launch`)
        self._step = jax.jit(self._build_step())
        self._insert = jax.jit(self._build_insert())
        self._ext_part = jax.jit(self._build_extend(commit=False))
        self._ext_commit = jax.jit(self._build_extend(commit=True))
        self._evict = jax.jit(self._build_evict())
        self._restore = jax.jit(self._build_restore())
        self._pfx_save_ws = jax.jit(self._build_prefix_ws(save=True))
        self._pfx_load_ws = jax.jit(self._build_prefix_ws(save=False))
        self._pfx_save_state = jax.jit(
            self._build_prefix_state(save=True))
        self._pfx_load_state = jax.jit(
            self._build_prefix_state(save=False))

    # ---- placement hooks (ShardedBackend overrides) ------------------
    def _place(self, cache: dict) -> dict:
        return cache

    def _constrain(self, cache: dict) -> dict:
        return cache

    def _place_ext(self, ext: dict) -> dict:
        return ext

    def _constrain_ext(self, ext: dict) -> dict:
        return ext

    def _place_spill(self, spill: dict) -> dict:
        return spill

    def _constrain_spill(self, spill: dict) -> dict:
        return spill

    def _place_prefix(self, store: dict) -> dict:
        return store

    def _constrain_prefix(self, store: dict) -> dict:
        return store

    # ---- jitted program builders -------------------------------------
    def _build_step(self):
        model, axes = self.model, self._axes

        def slot_step(p, tok, cache, pos):
            c1 = tree_expand(cache, axes)
            logits, nc = model.decode_step(p, tok[None], c1, pos)
            ntok = jnp.argmax(logits[0, -1], -1).astype(jnp.int32)
            return ntok, tree_squeeze(nc, axes)

        vm = jax.vmap(slot_step, in_axes=(None, 0, axes, 0),
                      out_axes=(0, axes))

        def step(p, toks, cache, pos, active):
            ntoks, nc = vm(p, toks, cache, pos)

            def sel(n, o, a):
                shp = [1] * n.ndim
                shp[a] = n.shape[a]
                return jnp.where(active.reshape(shp), n, o)

            # inactive slots keep their old cache verbatim: no phantom
            # appends, no endurance-counter drift while a slot is parked
            return ntoks, self._constrain(
                jax.tree.map(sel, nc, cache, axes))

        return step

    def _build_insert(self):
        axes = self._axes

        def insert(pool, req_cache, slot):
            out = jax.tree.map(
                lambda p, r, a: jax.lax.dynamic_update_slice_in_dim(
                    p, r.astype(p.dtype), slot, axis=a),
                pool, req_cache, axes)
            return self._constrain(out)

        return insert

    def _build_extend(self, commit: bool):
        model, axes = self.model, self._axes

        if not commit:
            def ext_part(p, batch, ext, pos, length):
                _, new_ext = model.extend(p, batch, ext, pos, length)
                return self._constrain_ext(new_ext)
            return ext_part

        def ext_commit(p, batch, pool, ext, slot, pos, length):
            # final chunk: the committed store-form cache scatters into
            # the already-allocated pool slot in the same program
            logits, committed = model.extend(p, batch, ext, pos, length,
                                             commit=True)
            tok = jnp.argmax(logits[0, -1], -1).astype(jnp.int32)
            pool = jax.tree.map(
                lambda pl, r, a: jax.lax.dynamic_update_slice_in_dim(
                    pl, r.astype(pl.dtype), slot, axis=a),
                pool, committed, axes)
            return tok, self._constrain(pool)

        return ext_commit

    def _build_evict(self):
        axes, spill_axes = self._axes, self._spill_axes
        compress = self.spill_compress

        def evict(cache, spill, spill_writes, slot, lane, length):
            # pack the slot's cache into the spill lane. Verbatim by
            # default: the cold tier is already RRAM-resident int8, and
            # the hot ring / scales / recurrent states / endurance
            # counters ride along untouched so the restore is bit-exact.
            # Under spill_compress the hot ring alone is requantized to
            # the int8 codec form (everything else still verbatim).
            img = jax.tree.map(
                lambda c, a: jax.lax.dynamic_slice_in_dim(c, slot, 1,
                                                          axis=a),
                cache, axes)
            if compress:
                img = map_spill_stores(img, KT.spill_store_compress)
            spill = jax.tree.map(
                lambda s, r, a: jax.lax.dynamic_update_slice_in_dim(
                    s, r.astype(s.dtype), lane, axis=a),
                spill, img, spill_axes)
            spill_writes = KT.bump_spill_writes(spill_writes, lane,
                                                length)
            return self._constrain_spill(spill), spill_writes

        return evict

    def _build_restore(self):
        axes, spill_axes = self._axes, self._spill_axes
        compress = self.spill_compress
        cd = jnp.dtype(self.model.cfg.compute_dtype)

        def restore(cache, spill, lane, slot):
            img = jax.tree.map(
                lambda s, a: jax.lax.dynamic_slice_in_dim(s, lane, 1,
                                                          axis=a),
                spill, spill_axes)
            if compress:
                img = map_spill_stores(
                    img, lambda st: KT.spill_store_decompress(st, cd))
            cache = jax.tree.map(
                lambda c, r, a: jax.lax.dynamic_update_slice_in_dim(
                    c, r.astype(c.dtype), slot, axis=a),
                cache, img, axes)
            return self._constrain(cache)

        return restore

    @staticmethod
    def _is_ws(path) -> bool:
        """Workspace leaves (`*_ws`) hold per-position K/V rows; every
        other extend leaf is a recurrent-state snapshot."""
        return str(getattr(path[-1], "key", path[-1])).endswith("_ws")

    def _build_prefix_ws(self, save: bool):
        """One-block workspace copy between the prefix store (block axis
        ``a``, ``block_tokens`` rows) and an in-flight extend workspace
        (batch-1, ``max_len`` rows at axis ``a+1``). State leaves ride
        through untouched — they move with `_build_prefix_state` only at
        a chain's terminal block."""
        axes, bt, is_ws = self._ext_axes, self.block_tokens, self._is_ws

        if save:
            def save_ws(store, ext, bid, pos):
                def leaf(path, s, e, a):
                    if not is_ws(path):
                        return s
                    row = jax.lax.dynamic_slice_in_dim(e, pos, bt,
                                                       axis=a + 1)
                    return jax.lax.dynamic_update_slice_in_dim(
                        s, row.astype(s.dtype), bid, axis=a)
                return self._constrain_prefix(
                    jax.tree_util.tree_map_with_path(leaf, store, ext,
                                                     axes))
            return save_ws

        def load_ws(ext, store, bid, pos):
            def leaf(path, e, s, a):
                if not is_ws(path):
                    return e
                row = jax.lax.dynamic_slice_in_dim(s, bid, 1, axis=a)
                return jax.lax.dynamic_update_slice_in_dim(
                    e, row.astype(e.dtype), pos, axis=a + 1)
            return self._constrain_ext(
                jax.tree_util.tree_map_with_path(leaf, ext, store, axes))
        return load_ws

    def _build_prefix_state(self, save: bool):
        """Recurrent-state snapshot copy: the non-workspace extend
        leaves (SSM/rwkv states after the whole prefix) move wholesale
        between block ``bid``'s state rows and the batch-1 extend tree.
        A pure-attention model has no such leaves and these programs are
        identity copies that never run (`has_prefix_ws` gating)."""
        axes, is_ws = self._ext_axes, self._is_ws

        if save:
            def save_state(store, ext, bid):
                def leaf(path, s, e, a):
                    if is_ws(path):
                        return s
                    return jax.lax.dynamic_update_slice_in_dim(
                        s, e.astype(s.dtype), bid, axis=a)
                return self._constrain_prefix(
                    jax.tree_util.tree_map_with_path(leaf, store, ext,
                                                     axes))
            return save_state

        def load_state(ext, store, bid):
            def leaf(path, e, s, a):
                if is_ws(path):
                    return e
                return jax.lax.dynamic_slice_in_dim(
                    s, bid, 1, axis=a).astype(e.dtype)
            return self._constrain_ext(
                jax.tree_util.tree_map_with_path(leaf, ext, store, axes))
        return load_state

    # ---- InferenceBackend surface ------------------------------------
    def slot_kv_bytes(self, *, length: int | None = None
                      ) -> tuple[int, int]:
        return slot_kv_bytes(self.model, self.max_len, length=length,
                             block_tokens=self.block_tokens)

    def spill_lane_bytes(self) -> int:
        return spill_lane_bytes(self.model, self.max_len,
                                self.spill_compress)

    def weight_bytes(self) -> tuple[int, int]:
        return weight_stream_split(self.model.cfg)

    def sim_context(self) -> tuple:
        return self.model.cfg, self.spill_compress

    def init_pool(self) -> KVPoolState:
        # spill buffers are LAZY: n_spill lanes are reserved (host-side
        # bookkeeping) but the RRAM-image arrays — a full extra copy of
        # the pool — only materialize on the first eviction, so engines
        # that never preempt pay nothing
        cache = self._place(
            self.model.init_cache(self.num_slots, self.max_len))
        return KVPoolState(cache=cache, axes=self._axes)

    def fresh_slot(self) -> dict:
        """Batch-1 zero cache (explicit slot scrub); built once, reused —
        insert is functional, so sharing the tree is safe."""
        if self._zero_slot is None:
            self._zero_slot = self.model.init_cache(1, self.max_len)
        return self._zero_slot

    def fresh_extend(self) -> dict:
        """Zero extend state (one in-flight prefill lane); built once and
        reused — extend is functional, and stale workspace tails beyond a
        committed length are never attendable, so sharing is safe. Only
        the recurrent-state leaves genuinely need the zeros."""
        if self._zero_ext is None:
            self._zero_ext = self._place_ext(
                self.model.init_extend_cache(1, self.max_len))
        return self._zero_ext

    def make_pool(self) -> TieredKVPool:
        return TieredKVPool(self.init_pool(), self._insert_state,
                            self.fresh_slot, num_spill_lanes=self.n_spill)

    def extend_step(self, batch: dict, state: KVPoolState, ext: dict,
                    slot, pos, length, commit: bool
                    ) -> tuple[jax.Array | None, dict | None, KVPoolState]:
        if not commit:
            new_ext = _launch(self._ext_part, (self.params, batch, ext),
                              pos, length)
            return None, new_ext, state
        tok, cache = _launch(self._ext_commit,
                             (self.params, batch, state.cache, ext),
                             slot, pos, length)
        return tok, None, dataclasses.replace(state, cache=cache)

    def decode_step(self, toks, state: KVPoolState, pos, active
                    ) -> tuple[jax.Array, KVPoolState]:
        name = "repro.backend." + self._step.__name__
        with span(name + ".put"):
            toks_d = jnp.asarray(toks)
            pos_d = jnp.asarray(pos)
            active_d = jnp.asarray(active)
        with span(name + ".launch", **self._kv_args(pos, active)):
            ntoks, cache = self._step(self.params, toks_d, state.cache,
                                      pos_d, active_d)
        return ntoks, dataclasses.replace(state, cache=cache)

    def _kv_args(self, pos, active) -> dict:
        """``kv_pool_bytes`` and ``kv_live_bytes`` for the decode launch's
        span, only while the profiler records, from the engine's host
        arrays ``pos`` and ``active``."""
        if not tracing():
            return {}
        return {"kv_pool_bytes": self.kv_pool_bytes,
                "kv_live_bytes": self._kv_rates.live(pos, active)}

    def evict_slot(self, state: KVPoolState, slot, lane, length
                   ) -> KVPoolState:
        if self.n_spill == 0:
            raise ValueError("backend was built with n_spill=0; nothing "
                             "can be evicted")
        if state.spill is None:           # first eviction: materialize
            lanes = self.model.init_cache(self.n_spill, self.max_len)
            if self.spill_compress:
                lanes = map_spill_stores(lanes, KT.spill_store_template)
            state = dataclasses.replace(
                state,
                spill=self._place_spill(lanes),
                spill_writes=KT.init_spill_writes(self.n_spill,
                                                  self.max_len),
                spill_axes=(self._spill_axes if self.spill_compress
                            else None))
        spill, writes = _launch(
            self._evict, (state.cache, state.spill, state.spill_writes),
            slot, lane, length)
        return dataclasses.replace(state, spill=spill, spill_writes=writes)

    def restore_slot(self, state: KVPoolState, lane, slot
                     ) -> KVPoolState:
        if state.spill is None:
            raise ValueError("nothing has been spilled; there is no "
                             "lane to restore from")
        cache = _launch(self._restore, (state.cache, state.spill),
                        lane, slot)
        return dataclasses.replace(state, cache=cache)

    def _insert_state(self, state: KVPoolState, req_cache: dict, slot
                     ) -> KVPoolState:
        """Scatter a batch-1 cache into slot ``slot`` (pool internals:
        recycling scrubs; not part of the serving step surface)."""
        cache = _launch(self._insert, (state.cache, req_cache), slot)
        return dataclasses.replace(state, cache=cache)

    # ---- paged prefix block store (PR 7) -----------------------------
    def ensure_prefix(self, state: KVPoolState) -> KVPoolState:
        """Materialize the prefix block store on first use (lazy, like
        the spill lanes): a zero extend tree with the batch axis sized
        to ``prefix_blocks`` and the sequence axis to ``block_tokens``."""
        if state.prefix is not None:
            return state
        store = self._place_prefix(self.model.init_extend_cache(
            self.prefix_blocks, self.block_tokens))
        return dataclasses.replace(state, prefix=store,
                                   prefix_axes=self._ext_axes)

    def prefix_block_bytes(self) -> int:
        """Bytes ONE prefix block pins (workspace rows + state-snapshot
        rows) — what the scheduler charges the shared store against the
        RRAM budget per live block."""
        shapes, _ = self.model.extend_spec(1, self.block_tokens)
        total = 0
        for leaf in jax.tree.leaves(shapes):
            n = jnp.dtype(leaf.dtype).itemsize
            for d in leaf.shape:
                n *= d
            total += n
        return int(total)

    def prefix_save_ws(self, state: KVPoolState, ext: dict, bid, pos
                       ) -> KVPoolState:
        """Write workspace rows [pos, pos+block_tokens) of ``ext`` into
        block ``bid`` — the ONE physical write a shared block ever
        takes."""
        store = _launch(self._pfx_save_ws, (state.prefix, ext), bid, pos)
        return dataclasses.replace(state, prefix=store)

    def prefix_load_ws(self, state: KVPoolState, ext: dict, bid, pos
                       ) -> dict:
        """Seed block ``bid``'s rows into ``ext`` at position ``pos``
        (admission gather of one hit block)."""
        return _launch(self._pfx_load_ws, (ext, state.prefix), bid, pos)

    def prefix_save_state(self, state: KVPoolState, ext: dict, bid
                          ) -> KVPoolState:
        """Snapshot ``ext``'s recurrent states into block ``bid`` (the
        chain-terminal resume point for SSM architectures)."""
        store = _launch(self._pfx_save_state, (state.prefix, ext), bid)
        return dataclasses.replace(state, prefix=store)

    def prefix_load_state(self, state: KVPoolState, ext: dict, bid
                          ) -> dict:
        return _launch(self._pfx_load_state, (ext, state.prefix), bid)


class LocalBackend(_JittedBackend):
    """Single-host vmapped executor: the seed engine's decode path,
    extracted. Params and pool live wherever jax's default device is."""


class ShardedBackend(_JittedBackend):
    """pjit executor over a device mesh.

    Params are committed to the model's `param_shardings` resolution and
    the KV pool to `Model.cache_shardings` (slots -> 'data' axis, cold
    kv_seq / kv heads -> 'model' axis, with the resolver's divisibility
    fallback). The decode/prefill jaxpr is built from a rules-free model
    twin — identical to `LocalBackend`'s — and the pool layout is pinned
    by `with_sharding_constraint` at the step/insert outputs, so XLA's
    SPMD partitioner steers the interior while a 1-device mesh stays
    numerically identical to the local path.
    """

    def __init__(self, model: Model, params, num_slots: int, max_len: int,
                 mesh: jax.sharding.Mesh | None = None,
                 rules: ShardingRules | None = None,
                 n_spill: int | None = None,
                 spill_compress: bool | None = None,
                 prefix_blocks: int | None = None,
                 block_tokens: int | None = None,
                 fused_decode: bool | None = None,
                 sparse_read: float | None = None,
                 weight_stream: int | None = None):
        if mesh is None:
            from repro.launch.mesh import make_local_mesh
            mesh = make_local_mesh()
        # rules-free twin: the step jaxpr matches LocalBackend exactly;
        # sharding enters only at the jit boundaries below
        if model.rules is not None:
            model = Model(model.cfg)
        self.mesh = mesh
        self.rules = rules or ShardingRules(mesh)
        self._pool_sh = model.cache_shardings(self.rules, num_slots,
                                              max_len)
        self._ext_sh = model.extend_shardings(self.rules, 1, max_len)
        if n_spill is None:
            n_spill = num_slots
        # spill lanes shard exactly like pool slots (lanes -> 'data',
        # cold kv_seq / kv heads -> 'model'), so evict/restore stay
        # device-local tree copies wherever divisibility allows. A
        # compressed lane's hot_q inherits the hot ring's sharding and
        # hot_scale its leading axes (the size-1 scale axis was already
        # unsharded in the hot spec).
        spill_compress = _resolve_spill_compress(spill_compress)
        self._spill_sh = (model.cache_shardings(self.rules, n_spill,
                                                max_len)
                          if n_spill else None)
        if self._spill_sh is not None and spill_compress:
            self._spill_sh = map_spill_stores(self._spill_sh,
                                              KT.spill_store_meta)
        params = jax.device_put(params,
                                model.param_shardings(self.rules))
        # prefix-store shardings depend on the block geometry the base
        # ctor resolves, so they build lazily on first prefix use
        self._pfx_sh = None
        super().__init__(model, params, num_slots, max_len,
                         n_spill=n_spill, spill_compress=spill_compress,
                         prefix_blocks=prefix_blocks,
                         block_tokens=block_tokens,
                         fused_decode=fused_decode,
                         sparse_read=sparse_read,
                         weight_stream=weight_stream)

    def _place(self, cache: dict) -> dict:
        return jax.device_put(cache, self._pool_sh)

    def _constrain(self, cache: dict) -> dict:
        return jax.lax.with_sharding_constraint(cache, self._pool_sh)

    def _place_ext(self, ext: dict) -> dict:
        return jax.device_put(ext, self._ext_sh)

    def _constrain_ext(self, ext: dict) -> dict:
        return jax.lax.with_sharding_constraint(ext, self._ext_sh)

    def _place_spill(self, spill: dict) -> dict:
        return jax.device_put(spill, self._spill_sh)

    def _constrain_spill(self, spill: dict) -> dict:
        return jax.lax.with_sharding_constraint(spill, self._spill_sh)

    def _prefix_shardings(self):
        """Blocks shard like slots ('data'); the per-block sequence axis
        (block_tokens wide) keeps the workspace logical axes, with the
        resolver's divisibility fallback to replicated."""
        if self._pfx_sh is None:
            self._pfx_sh = self.model.extend_shardings(
                self.rules, self.prefix_blocks, self.block_tokens)
        return self._pfx_sh

    def _place_prefix(self, store: dict) -> dict:
        return jax.device_put(store, self._prefix_shardings())

    def _constrain_prefix(self, store: dict) -> dict:
        return jax.lax.with_sharding_constraint(store,
                                                self._prefix_shardings())


def make_backend(kind: str, model: Model, params, *, num_slots: int,
                 max_len: int, mesh=None,
                 n_spill: int | None = None,
                 spill_compress: bool | None = None,
                 prefix_blocks: int | None = None,
                 block_tokens: int | None = None,
                 fused_decode: bool | None = None,
                 sparse_read: float | None = None,
                 weight_stream: int | None = None) -> InferenceBackend:
    """CLI-facing factory: ``kind`` in {'local', 'sharded'}."""
    if kind == "local":
        return LocalBackend(model, params, num_slots, max_len,
                            n_spill=n_spill,
                            spill_compress=spill_compress,
                            prefix_blocks=prefix_blocks,
                            block_tokens=block_tokens,
                            fused_decode=fused_decode,
                            sparse_read=sparse_read,
                            weight_stream=weight_stream)
    if kind == "sharded":
        return ShardedBackend(model, params, num_slots, max_len, mesh=mesh,
                              n_spill=n_spill,
                              spill_compress=spill_compress,
                              prefix_blocks=prefix_blocks,
                              block_tokens=block_tokens,
                              fused_decode=fused_decode,
                              sparse_read=sparse_read,
                              weight_stream=weight_stream)
    raise ValueError(f"unknown backend kind {kind!r}")
