"""Differential-oracle suite for the fused paged-decode attention kernel
(`kernels/paged_decode.py`) against the unfused two-segment merge
(`models/attention.attend_tiered` / `attend_flat`) it replaces.

All kernels run in interpret mode on CPU (the wrappers auto-detect), so
this file exercises the EXACT grid/BlockSpec/scalar-prefetch program CI
ships. Covered contracts:

  * fused == unfused across GQA group sizes, flat and tiered stores,
    ragged per-slot lengths, and permuted physical page tables;
  * in-kernel int8 dequant honors the `spill_codec_bound` codec contract
    (the kernel reads the same `hot_q`/`hot_scale`-style arrays PR 5's
    spill codec writes);
  * SLIM-style sparse read: tau = 0 and no-skip tau are bit-identical to
    exact; a forced-skip workload drifts less than the documented
    `n_cold * tau * (max|v| + max|out|)` gate.
"""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_tiers as KT
from repro.core.quant import spill_codec_bound
from repro.kernels import ops
from repro.kernels import paged_decode as PD
from repro.models import attention as A

jax.config.update("jax_platform_name", "cpu")

CFG = types.SimpleNamespace(attn_scores_dtype="float32")


def _stores(rng, B, max_len, Hkv, D, W, lengths):
    """Per-slot tiered K/V stores from fully-materialized ragged
    sequences — the exact layout `kv_tiers.tiered_from_full` writes in
    the serving prefill path. Returns (k_full, v_full, k_store, v_store)
    with the store stacked over the (possibly ragged) batch."""
    k_full = rng.standard_normal((B, max_len, Hkv, D)).astype(np.float32)
    v_full = rng.standard_normal((B, max_len, Hkv, D)).astype(np.float32)
    ks, vs = [], []
    for b in range(B):
        ks.append(KT.tiered_from_full(jnp.asarray(k_full[b:b + 1]), W,
                                      lengths[b] + 1, max_len))
        vs.append(KT.tiered_from_full(jnp.asarray(v_full[b:b + 1]), W,
                                      lengths[b] + 1, max_len))
    cat = lambda ts: jax.tree.map(  # noqa: E731
        lambda *xs: jnp.concatenate(xs, axis=0), *ts)
    return k_full, v_full, cat(ks), cat(vs)


def _oracle_tiered(q, k_store, v_store, lengths):
    """Per-slot unfused reference (attend_tiered is scalar-pos)."""
    outs = [A.attend_tiered(CFG, q[b:b + 1],
                            jax.tree.map(lambda x: x[b:b + 1], k_store),
                            jax.tree.map(lambda x: x[b:b + 1], v_store),
                            jnp.int32(lengths[b]))
            for b in range(q.shape[0])]
    return jnp.concatenate(outs, axis=0)


def _kernel_tiered(q, k_store, v_store, lengths, *, block_k, tau=0.0,
                   table=None):
    """Direct kernel call in store-native layout; identity table unless
    a permuted one is supplied."""
    B, _, H, D = q.shape
    Hkv = k_store["hot"].shape[2]
    G = H // Hkv
    W = KT.hot_window_of(k_store)
    max_len = k_store["cold_q"].shape[1]
    if table is None:
        table = jnp.stack([KT.cold_page_table(jnp.int32(lengths[b]), W,
                                              max_len, block_k)
                           for b in range(B)])
    qr = q[:, 0].reshape(B, Hkv, G, D)
    o = PD.paged_decode_tiered(
        qr, k_store["hot"], v_store["hot"],
        k_store["cold_q"], k_store["cold_scale"],
        v_store["cold_q"], v_store["cold_scale"],
        jnp.asarray(lengths, jnp.int32), table,
        scale=D ** -0.5, block_k=block_k, tau=tau)
    return o.reshape(B, H, D)[:, None]


# ---------------------------------------------------------------------------
# fused == unfused: GQA group sizes x tiered/flat x ragged lengths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("G", [1, 2, 4])
def test_tiered_matches_oracle_gqa(G):
    rng = np.random.default_rng(0)
    B, Hkv, D, W, max_len = 2, 2, 64, 8, 48
    lengths = [47, 47]
    _, _, k_store, v_store = _stores(rng, B, max_len, Hkv, D, W, lengths)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    got = _kernel_tiered(q, k_store, v_store, lengths, block_k=16)
    want = _oracle_tiered(q, k_store, v_store, lengths)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("G", [1, 4])
def test_tiered_matches_oracle_via_ops_adapter(G):
    """The `kernels.ops` adapter the model's decode dispatch calls:
    scalar pos, table derived internally."""
    rng = np.random.default_rng(1)
    B, Hkv, D, W, max_len, pos = 2, 2, 64, 8, 40, 33
    _, _, k_store, v_store = _stores(rng, B, max_len, Hkv, D, W,
                                     [pos] * B)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    got = ops.paged_decode_tiered(CFG, q, k_store, v_store,
                                  jnp.int32(pos))
    want = _oracle_tiered(q, k_store, v_store, [pos] * B)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("G", [1, 2])
def test_flat_matches_oracle_gqa(G):
    rng = np.random.default_rng(2)
    B, Hkv, D, max_len, pos = 2, 2, 64, 40, 29
    k = jnp.asarray(rng.standard_normal((B, max_len, Hkv, D)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, max_len, Hkv, D)),
                    jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    got = ops.paged_decode_flat(CFG, q, {"flat": k}, {"flat": v},
                                jnp.int32(pos))
    want = A.attend_flat(CFG, q, {"k": k, "v": v}, jnp.int32(pos))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_tiered_ragged_per_slot_lengths():
    """One compiled kernel, per-slot lengths in scalar prefetch: slots
    deep in the cold tier, inside the hot window, and at position 0."""
    rng = np.random.default_rng(3)
    B, Hkv, G, D, W, max_len = 3, 2, 2, 64, 8, 48
    lengths = [47, 5, 0]
    _, _, k_store, v_store = _stores(rng, B, max_len, Hkv, D, W, lengths)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    got = _kernel_tiered(q, k_store, v_store, lengths, block_k=16)
    want = _oracle_tiered(q, k_store, v_store, lengths)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_ragged_tail_page_is_masked():
    """max_len not divisible by block_k: the padded tail page must never
    contribute (its tokens sit past every valid position)."""
    rng = np.random.default_rng(4)
    B, Hkv, G, D, W, max_len = 1, 2, 2, 64, 4, 37
    lengths = [36]
    _, _, k_store, v_store = _stores(rng, B, max_len, Hkv, D, W, lengths)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    got = _kernel_tiered(q, k_store, v_store, lengths, block_k=16)
    want = _oracle_tiered(q, k_store, v_store, lengths)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# block-table indirection
# ---------------------------------------------------------------------------
def test_permuted_block_table_matches_identity():
    """Logical pages scattered over permuted physical pages read back
    EXACTLY what the identity layout reads (same arithmetic order)."""
    rng = np.random.default_rng(5)
    B, Hkv, G, D, W, max_len, bk = 2, 2, 2, 64, 8, 64, 16
    lengths = [63, 40]
    _, _, k_store, v_store = _stores(rng, B, max_len, Hkv, D, W, lengths)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    base = _kernel_tiered(q, k_store, v_store, lengths, block_k=bk)

    n_pages = max_len // bk
    perm = rng.permutation(n_pages)

    def scatter(x):
        y = np.array(x)
        for j in range(n_pages):
            y[:, perm[j] * bk:(perm[j] + 1) * bk] = \
                np.array(x)[:, j * bk:(j + 1) * bk]
        return jnp.asarray(y)

    k_p = {**k_store, "cold_q": scatter(k_store["cold_q"]),
           "cold_scale": scatter(k_store["cold_scale"])}
    v_p = {**v_store, "cold_q": scatter(v_store["cold_q"]),
           "cold_scale": scatter(v_store["cold_scale"])}
    ident = jnp.stack([KT.cold_page_table(jnp.int32(lengths[b]), W,
                                          max_len, bk)
                       for b in range(B)])
    table = jnp.where(ident >= 0, jnp.asarray(perm, jnp.int32)[None], -1)
    got = _kernel_tiered(q, k_p, v_p, lengths, block_k=bk, table=table)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_dead_table_entries_are_skipped():
    """A table that marks live-range pages dead must drop exactly those
    pages' contributions (the scheduler's page-free path)."""
    rng = np.random.default_rng(6)
    B, Hkv, G, D, W, max_len, bk = 1, 1, 1, 64, 4, 32, 8
    lengths = [31]
    _, _, k_store, v_store = _stores(rng, B, max_len, Hkv, D, W, lengths)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    table = jnp.asarray([[0, -1, 2, -1]], jnp.int32)   # kill pages 1, 3
    got = _kernel_tiered(q, k_store, v_store, lengths, block_k=bk,
                         table=table)
    # reference: two-segment attention over only the LIVE cold tokens
    # (dequantized), merged with the hot ring
    live = np.zeros(max_len, bool)
    live[0 * bk:1 * bk] = True
    live[2 * bk:3 * bk] = True
    live &= np.arange(max_len) <= lengths[0] - W
    kd = np.array(k_store["cold_q"], np.float32) \
        * np.array(k_store["cold_scale"])
    vd = np.array(v_store["cold_q"], np.float32) \
        * np.array(v_store["cold_scale"])
    scale = D ** -0.5
    p_cold = A.partial_attention(q, jnp.asarray(kd), jnp.asarray(vd),
                                 jnp.asarray(live), scale)
    hot_pos = KT.hot_ring_positions(jnp.int32(lengths[0]), W)
    hot_valid = (hot_pos >= 0) & (hot_pos <= lengths[0])
    p_hot = A.partial_attention(q, k_store["hot"], v_store["hot"],
                                hot_valid, scale)
    want = A.merge_partials([p_cold, p_hot], q.dtype)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# int8 codec contract
# ---------------------------------------------------------------------------
def test_cold_pages_respect_spill_codec_bound():
    """The arrays the kernel dequants in-VMEM are the PR 5 spill-codec
    representation: elementwise |full - scale*q| <= spill_codec_bound,
    and the fused output tracks full-precision attention within the
    bound-propagated tolerance."""
    rng = np.random.default_rng(7)
    B, Hkv, G, D, W, max_len = 1, 2, 2, 64, 8, 48
    lengths = [47]
    k_full, v_full, k_store, v_store = _stores(rng, B, max_len, Hkv, D,
                                               W, lengths)
    for full, store in ((k_full, k_store), (v_full, v_store)):
        deq = np.array(store["cold_q"], np.float32) \
            * np.array(store["cold_scale"])
        bound = np.array(spill_codec_bound(jnp.asarray(full)))
        assert (np.abs(full - deq) <= bound + 1e-7).all()
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    got = _kernel_tiered(q, k_store, v_store, lengths, block_k=16)
    # full-precision reference (no codec anywhere)
    scale = D ** -0.5
    cold_valid = jnp.arange(max_len) <= lengths[0] - W
    p_cold = A.partial_attention(q, jnp.asarray(k_full),
                                 jnp.asarray(v_full), cold_valid, scale)
    hot_pos = KT.hot_ring_positions(jnp.int32(lengths[0]), W)
    p_hot = A.partial_attention(q, k_store["hot"], v_store["hot"],
                                (hot_pos >= 0) & (hot_pos <= lengths[0]),
                                scale)
    want = A.merge_partials([p_cold, p_hot], q.dtype)
    # int8 codec error, not kernel error: ~scale/2 per element
    assert float(jnp.max(jnp.abs(got - want))) < 0.05


# ---------------------------------------------------------------------------
# SLIM sparse read
# ---------------------------------------------------------------------------
def test_sparse_tau_no_skip_is_bit_exact():
    """On unstructured data the l1 bound never crosses the threshold at
    small tau — the sparse kernel must then be BIT-identical to exact
    (the threshold test adds no arithmetic to surviving pages)."""
    rng = np.random.default_rng(8)
    B, Hkv, G, D, W, max_len = 2, 2, 2, 64, 8, 48
    lengths = [47, 30]
    _, _, k_store, v_store = _stores(rng, B, max_len, Hkv, D, W, lengths)
    q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
    exact = _kernel_tiered(q, k_store, v_store, lengths, block_k=16)
    sparse = _kernel_tiered(q, k_store, v_store, lengths, block_k=16,
                            tau=1e-6)
    np.testing.assert_array_equal(np.asarray(sparse), np.asarray(exact))


def test_sparse_drift_within_documented_gate():
    """Structured workload that actually trips the skip: an anchored hot
    max (aligned large-norm key) + near-zero cold pages whose upper
    bound falls below m + log(tau). Drift obeys the documented contract
    (skipped mass/token < tau) and is nonzero — proof pages were really
    skipped, not vacuously equal."""
    rng = np.random.default_rng(9)
    B, Hkv, G, D, W, max_len, bk = 1, 1, 1, 64, 8, 40, 8
    pos = 39
    tau = 1e-2
    q = rng.standard_normal((B, 1, Hkv * G, D)).astype(np.float32)
    k_full = 1e-3 * rng.standard_normal((B, max_len, Hkv, D)) \
        .astype(np.float32)
    v_full = rng.standard_normal((B, max_len, Hkv, D)).astype(np.float32)
    # hot-window token aligned with q anchors m ~= scale * a * |q|^2
    a = 10.0 / (D ** -0.5 * float((q[0, 0, 0] ** 2).sum()))
    k_full[0, pos, 0] = a * q[0, 0, 0]
    k_store = KT.tiered_from_full(jnp.asarray(k_full), W, pos + 1,
                                  max_len)
    v_store = KT.tiered_from_full(jnp.asarray(v_full), W, pos + 1,
                                  max_len)
    qj = jnp.asarray(q)
    exact = _kernel_tiered(qj, k_store, v_store, [pos], block_k=bk)
    sparse = _kernel_tiered(qj, k_store, v_store, [pos], block_k=bk,
                            tau=tau)
    diff = float(jnp.max(jnp.abs(sparse - exact)))
    assert diff > 0.0, "no page was skipped — workload fails to trip SLIM"
    n_cold = pos + 1 - W
    gate = n_cold * tau * (float(np.abs(v_full).max())
                           + float(jnp.max(jnp.abs(exact))))
    assert diff <= gate, (diff, gate)
    # and the oracle agrees with the exact kernel on this workload too
    want = _oracle_tiered(qj, k_store, v_store, [pos])
    np.testing.assert_allclose(exact, want, atol=5e-5, rtol=5e-5)


# ---------------------------------------------------------------------------
# VMEM accounting
# ---------------------------------------------------------------------------
def test_paged_decode_vmem_budget():
    """Serving shapes fit v5e VMEM with headroom; the accounting counts
    the int8 tiles AND their f32 casts plus both scale streams."""
    V5E_VMEM = 128 * 2 ** 20
    n = PD.paged_decode_vmem_bytes(block_k=128, G=8, D=128, hot_w=64)
    assert n < V5E_VMEM // 4
    cold = 2 * 128 * 128 * (1 + 4)
    scales = 2 * 128 * (4 + 4)
    assert n >= cold + scales
    # a block holds every kv head of its tokens (fastvlm-1.7b: 2 heads,
    # G = 6): the int8 tiles grow with the heads, the f32 casts do not
    n2 = PD.paged_decode_vmem_bytes(block_k=128, G=6, D=128, hot_w=128,
                                    hkv=2)
    assert n2 < V5E_VMEM // 4
    assert n2 >= 2 * 128 * 128 * (2 + 4) + 2 * 128 * (2 * 4 + 4)
