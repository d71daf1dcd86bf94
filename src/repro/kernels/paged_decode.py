"""Fused paged-decode attention over the tiered/paged KV layout.

`attn_stream` fuses prefill; this is its decode-side sibling. The serving
decode step previously materialized the whole attendable store via
`store_read` (a dequantized f32/bf16 copy of the int8 cold tier) before
running unfused XLA attention. Here the online softmax streams K/V pages
straight out of the store-native layouts instead:

  * grid (slot, 1 + block-table entry): step 0 consumes the hot ring
    (full precision, the just-appended token anchors the running max);
    steps 1..num_pages each consume one cold page through block-table
    indirection — a scalar-prefetch table maps logical page j to its
    physical page (-1 = dead page, skipped via `pl.when`). A block holds
    every kv head of its tokens (the store's (Hkv, D) minor dims are
    viewed as one Hkv*D lane axis, a free reshape), and the kernel loops
    the heads: a one-head block (1, bk, 1, D) of a (B, L, Hkv, D) array
    breaks the TPU's (8, 128) block tiling;
  * per-slot lengths ride in scalar prefetch, so ragged contexts share
    one compiled kernel and the batched serving `decode_step` vmaps it;
  * **in-kernel int8 dequant**: cold pages stay in the per-(token, head)
    symmetric codec of `core.quant` (the PR 5 `hot_q`/`hot_scale` spill
    codec, `spill_codec_bound` contract). A page's (bk, D) int8 tile is
    scaled by its (bk, 1) column of scales in VMEM just before the dots,
    so no f32 restore of the cold tier ever exists in HBM; the result
    matches the unfused `partial_attention` oracle (which factors the
    scales out of the dots) to f32 rounding.

SLIM-style adaptive-threshold sparse read (opt-in, ``tau`` > 0): with the
hot segment processed first, the running max m_g is anchored and the
denominator is >= 1, so a cold page whose score upper bound

    ub_g = scale * 127 * max(page k-scales) * ||q_g||_1   (>= any score,
                                  since |q . k_q| <= ||q||_1 * 127)

satisfies ub_g < m_g + log(tau) for EVERY group g contributes less than
block_k * tau probability mass per head and is skipped whole. The
documented drift contract (tests/test_paged_decode.py holds it
empirically, like the spill_compress logit-drift gate): total skipped
softmax mass per head < n_cold_tokens * tau. tau = 0 disables the check
and the kernel is an exact (modulo f32 associativity) twin of the
two-segment merge oracle.

Layouts (store-native, no transposed copies): q (B, Hkv, G, D) — head
h = hkv * G + g, matching the GQA group broadcast; hot k/v
(B, W, Hkv, D); cold q/v int8 (B, max_len, Hkv, D) with f32 scales
(B, max_len, Hkv, 1); lengths (B,) int32; table (B, num_pages) int32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 20
INT8_QMAX = 127.0  # symmetric int8 codec levels (core.quant)


def _attend_block(q, k, v, valid, h, acc_ref, m_ref, d_ref, scale):
    """One online-softmax update of head ``h``'s running (m, d, acc) with a
    (bk, D) K/V block; ``valid`` masks the block's tokens."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (G, bk)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    d_ref[h] = d_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def _init_state(acc_ref, m_ref, d_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    d_ref[...] = jnp.zeros_like(d_ref)


def _finalize(o_ref, acc_ref, d_ref):
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(d_ref[...], 1e-30)).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# tiered stores: hot ring (full precision) + int8 cold pages
# ---------------------------------------------------------------------------
def _paged_tiered_kernel(len_ref, tab_ref, q_ref, hk_ref, hv_ref,
                         ckq_ref, cks_ref, cvq_ref, cvs_ref, o_ref,
                         acc_ref, m_ref, d_ref, *, scale: float,
                         block_k: int, num_pages: int, hot_w: int,
                         tau: float):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    pos = len_ref[b]
    _, Hkv, G, D = q_ref.shape

    @pl.when(ki == 0)
    def _hot():
        # hot ring: slot i holds absolute position pos - ((pos - i) % W);
        # slot pos % W holds the just-appended token, so the running max
        # is anchored here — no reliance on exp underflow downstream.
        _init_state(acc_ref, m_ref, d_ref)
        slot = jax.lax.broadcasted_iota(jnp.int32, (G, hot_w), 1)
        valid = pos - ((pos - slot) % hot_w) >= 0
        for h in range(Hkv):
            lanes = pl.ds(h * D, D)
            _attend_block(q_ref[0, h].astype(jnp.float32),
                          hk_ref[0, :, lanes].astype(jnp.float32),
                          hv_ref[0, :, lanes].astype(jnp.float32),
                          valid, h, acc_ref, m_ref, d_ref, scale)

    # cold page j = ki - 1 covers tokens [j*block_k, (j+1)*block_k);
    # attendable cold positions are <= pos - W. The table entry is the
    # PHYSICAL page (used by the BlockSpecs); masking runs on logical j.
    j = jnp.maximum(ki - 1, 0)
    page_live = (ki > 0) & (tab_ref[b, j] >= 0) \
        & (j * block_k <= pos - hot_w)
    tok = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (G, block_k), 1)
    valid = tok <= pos - hot_w
    for h in range(Hkv):
        live = page_live
        if tau > 0.0:
            # SLIM sparse read: skip the page when no group's score
            # upper bound can reach within log(tau) of the running max.
            col = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            ks = cks_ref[0, :, pl.ds(h, 1)]                 # (bk, 1)
            max_ks = jnp.max(jnp.where(col <= pos - hot_w, ks, 0.0))
            q_l1 = jnp.sum(jnp.abs(q_ref[0, h].astype(jnp.float32)),
                           axis=1, keepdims=True)           # (G, 1)
            ub = scale * INT8_QMAX * max_ks * q_l1
            live &= jnp.any(ub >= m_ref[h] + math.log(tau))

        @pl.when(live)
        def _cold(h=h):
            # int8 tiles dequantize in VMEM, one (bk, D) tile at a time:
            # the int8 arrays are the only K/V bytes read from HBM
            lanes = pl.ds(h * D, D)
            k = ckq_ref[0, :, lanes].astype(jnp.float32) \
                * cks_ref[0, :, pl.ds(h, 1)]
            v = cvq_ref[0, :, lanes].astype(jnp.float32) \
                * cvs_ref[0, :, pl.ds(h, 1)]
            _attend_block(q_ref[0, h].astype(jnp.float32), k, v, valid,
                          h, acc_ref, m_ref, d_ref, scale)

    @pl.when(ki == num_pages)
    def _out():
        _finalize(o_ref, acc_ref, d_ref)


def _heads_minor(t: jax.Array) -> jax.Array:
    """(B, L, Hkv, X) -> (B, L, Hkv*X), a free row-major reshape. A
    block then spans every kv head of its tokens, so its two trailing
    dims are (block_k, Hkv*X): legal for the TPU's (8, 128) tiling
    whenever block_k is a multiple of 8 and X = head_dim a multiple of
    128 (or the block spans the whole array)."""
    B, L, H, X = t.shape
    return t.reshape(B, L, H * X)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "tau", "interpret"))
def paged_decode_tiered(q: jax.Array, hot_k: jax.Array, hot_v: jax.Array,
                        cold_kq: jax.Array, cold_ks: jax.Array,
                        cold_vq: jax.Array, cold_vs: jax.Array,
                        lengths: jax.Array, table: jax.Array, *,
                        scale: float | None = None, block_k: int = 128,
                        tau: float = 0.0,
                        interpret: bool | None = None) -> jax.Array:
    """q (B,Hkv,G,D); hot (B,W,Hkv,D); cold int8 (B,max_len,Hkv,D) +
    f32 scales (B,max_len,Hkv,1); lengths (B,) int32 current positions;
    table (B,num_pages) int32 logical->physical page map (-1 = dead).
    Returns (B,Hkv,G,D) in q.dtype."""
    B, Hkv, G, D = q.shape
    W = hot_k.shape[1]
    max_len = cold_kq.shape[1]
    scale = scale if scale is not None else D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_k = min(block_k, max_len)
    pad = (-max_len) % block_k
    hot_k, hot_v, cold_kq, cold_ks, cold_vq, cold_vs = (
        _heads_minor(t) for t in (hot_k, hot_v, cold_kq, cold_ks,
                                  cold_vq, cold_vs))
    if pad:  # ragged tail page: padded tokens sit past pos and stay masked
        cold_kq, cold_ks, cold_vq, cold_vs = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
            for t in (cold_kq, cold_ks, cold_vq, cold_vs))
    num_pages = (max_len + pad) // block_k
    assert table.shape == (B, num_pages), (table.shape, B, num_pages)

    def _slot_idx(b, ki, lens, tab):
        return (b, 0, 0, 0)

    def _hot_idx(b, ki, lens, tab):
        return (b, 0, 0)

    def _cold_idx(b, ki, lens, tab):
        return (b, jnp.maximum(tab[b, jnp.maximum(ki - 1, 0)], 0), 0)

    kernel = functools.partial(
        _paged_tiered_kernel, scale=scale, block_k=block_k,
        num_pages=num_pages, hot_w=W, tau=tau)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, num_pages + 1),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), _slot_idx),       # q (resident)
            pl.BlockSpec((1, W, Hkv * D), _hot_idx),       # hot k
            pl.BlockSpec((1, W, Hkv * D), _hot_idx),       # hot v
            pl.BlockSpec((1, block_k, Hkv * D), _cold_idx),  # cold k int8
            pl.BlockSpec((1, block_k, Hkv), _cold_idx),      # cold k scale
            pl.BlockSpec((1, block_k, Hkv * D), _cold_idx),  # cold v int8
            pl.BlockSpec((1, block_k, Hkv), _cold_idx),      # cold v scale
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), _slot_idx),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_tiered",
    )(lengths, table, q, hot_k, hot_v, cold_kq, cold_ks, cold_vq, cold_vs)


# ---------------------------------------------------------------------------
# flat stores: full-precision pages, same block-table indirection
# ---------------------------------------------------------------------------
def _paged_flat_kernel(len_ref, tab_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, d_ref, *, scale: float,
                       block_k: int, num_pages: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    pos = len_ref[b]
    _, Hkv, G, D = q_ref.shape

    @pl.when(ki == 0)
    def _init():
        _init_state(acc_ref, m_ref, d_ref)

    # page ki covers tokens [ki*block_k, (ki+1)*block_k); page 0 always
    # holds token 0 <= pos, so the running max is anchored on step 0
    page_live = (tab_ref[b, ki] >= 0) & (ki * block_k <= pos)

    @pl.when(page_live)
    def _update():
        tok = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_k), 1)
        for h in range(Hkv):
            lanes = pl.ds(h * D, D)
            _attend_block(q_ref[0, h].astype(jnp.float32),
                          k_ref[0, :, lanes].astype(jnp.float32),
                          v_ref[0, :, lanes].astype(jnp.float32),
                          tok <= pos, h, acc_ref, m_ref, d_ref, scale)

    @pl.when(ki == num_pages - 1)
    def _out():
        _finalize(o_ref, acc_ref, d_ref)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret"))
def paged_decode_flat(q: jax.Array, k: jax.Array, v: jax.Array,
                      lengths: jax.Array, table: jax.Array, *,
                      scale: float | None = None, block_k: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    """q (B,Hkv,G,D); k,v (B,max_len,Hkv,D); lengths (B,) int32; table
    (B,num_pages) int32 (-1 = dead). Returns (B,Hkv,G,D). The sparse read
    is tiered-only: the flat store carries no per-page scales to bound
    scores with."""
    B, Hkv, G, D = q.shape
    max_len = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_k = min(block_k, max_len)
    pad = (-max_len) % block_k
    k, v = _heads_minor(k), _heads_minor(v)
    if pad:
        k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (k, v))
    num_pages = (max_len + pad) // block_k
    assert table.shape == (B, num_pages), (table.shape, B, num_pages)

    def _slot_idx(b, ki, lens, tab):
        return (b, 0, 0, 0)

    def _page_idx(b, ki, lens, tab):
        return (b, jnp.maximum(tab[b, ki], 0), 0)

    kernel = functools.partial(
        _paged_flat_kernel, scale=scale, block_k=block_k,
        num_pages=num_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, num_pages),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), _slot_idx),
            pl.BlockSpec((1, block_k, Hkv * D), _page_idx),
            pl.BlockSpec((1, block_k, Hkv * D), _page_idx),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), _slot_idx),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_flat",
    )(lengths, table, q, k, v)


def paged_decode_vmem_bytes(block_k: int, G: int, D: int, hot_w: int,
                            dtype_bytes: int = 2, hkv: int = 1) -> int:
    """Static VMEM working set of the tiered kernel for ``hkv`` kv heads
    per block: store-dtype tiles, int8 cold tiles and scales, plus one
    head's in-kernel f32 casts at a time, scratch and the output block."""
    q_tile = hkv * G * D * dtype_bytes + G * D * 4
    hot_tiles = 2 * hot_w * D * (hkv * dtype_bytes + 4)
    cold_tiles = 2 * block_k * D * (hkv * 1 + 4)   # int8 + f32 cast
    scales = 2 * block_k * (hkv * 4 + 4)
    scratch = hkv * (G * D + 2 * G) * 4            # acc + m + d
    out = hkv * G * D * dtype_bytes
    return q_tile + hot_tiles + cold_tiles + scales + scratch + out
