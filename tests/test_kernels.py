"""Per-kernel validation: shape/dtype sweeps, interpret=True vs the pure-jnp
oracle in ref.py, plus VMEM working-set assertions for the BlockSpecs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.attn_stream import attn_stream, attn_stream_vmem_bytes
from repro.kernels.ffn_act import ffn_act, ffn_vmem_bytes
from repro.kernels.fused_norm import fused_norm
from repro.kernels.qkv_proj import qkv_proj

jax.config.update("jax_platform_name", "cpu")

V5E_VMEM = 128 * 2 ** 20


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# FUSED_ATTN_STREAM
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,Hkv,S,L,D", [
    (1, 4, 4, 128, 128, 64),      # MHA square
    (2, 8, 2, 128, 128, 64),      # GQA 4:1
    (1, 4, 1, 256, 256, 128),     # MQA (paligemma-style)
    (1, 2, 2, 128, 256, 64),      # cached prefix (L > S)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_attn_stream_matches_ref(B, H, Hkv, S, L, D, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, L, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, L, D), dtype)
    out = attn_stream(q, k, v, causal=causal, block_q=64, block_k=64,
                      interpret=True)
    want = ref.attn_stream_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_attn_stream_blocks_sweep():
    B, H, S, D = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    want = ref.attn_stream_ref(q, k, v, causal=True)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        out = attn_stream(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,L,causal", [
    (200, 200, True),     # ragged square (not a 128-multiple)
    (200, 200, False),
    (72, 200, True),      # ragged cached-prefix (both non-multiples)
    (130, 384, True),     # ragged S over an aligned L
])
def test_attn_stream_ragged_shapes(S, L, causal):
    """Regression: lengths that aren't block multiples used to hard-assert;
    they now pad to the grid, mask the phantom keys, and slice the output."""
    B, H, Hkv, D = 1, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, L, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, L, D), jnp.float32)
    out = attn_stream(q, k, v, causal=causal, interpret=True)
    want = ref.attn_stream_ref(q, k, v, causal=causal)
    assert out.shape == (B, H, S, D)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_attn_stream_causal_s_gt_l_raises():
    """Regression: S > L with causal=True made q_offset negative, leaving
    early queries with zero attendable keys; now an explicit error."""
    B, H, S, L, D = 1, 2, 160, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, L, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, L, D), jnp.float32)
    with pytest.raises(ValueError, match="S <= L"):
        attn_stream(q, k, v, causal=True, interpret=True)
    # non-causal S > L stays legal: every key is visible to every query
    out = attn_stream(q, k, v, causal=False, interpret=True)
    want = ref.attn_stream_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_attn_stream_fully_masked_blocks_skipped():
    """Blocks entirely above the causal diagonal are pl.when-skipped; with
    small k-blocks most of the grid is dead and the result must stay exact
    (no reliance on exp underflow zeroing whole-NEG_INF blocks)."""
    B, H, S, D = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    out = attn_stream(q, k, v, causal=True, block_q=32, block_k=32,
                      interpret=True)
    want = ref.attn_stream_ref(q, k, v, causal=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_attn_vmem_budget():
    # production tile choice fits v5e VMEM with generous headroom
    assert attn_stream_vmem_bytes(128, 128, 256) < V5E_VMEM // 8
    # the estimate must charge the in-kernel f32 copies of the q/k/v
    # tiles (cast before the dots), not just the HBM-dtype tiles
    bq = bk = 128
    D = 256
    tiles_bf16 = (bq * D + 2 * bk * D) * 2
    casts_f32 = (bq * D + 2 * bk * D) * 4
    assert attn_stream_vmem_bytes(bq, bk, D) >= tiles_bf16 + casts_f32


# ---------------------------------------------------------------------------
# FUSED_FFN_ACT
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind,gated", [
    ("silu_gated", True), ("gelu", False), ("relu2", False),
    ("gelu_gated", True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ffn_act_matches_ref(kind, gated, dtype):
    M, D, F = 128, 64, 256
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (M, D), dtype)
    w1 = jax.random.normal(ks[1], (D, F), dtype) * 0.1
    wg = jax.random.normal(ks[2], (D, F), dtype) * 0.1 if gated else None
    w2 = jax.random.normal(ks[3], (F, D), dtype) * 0.1
    out = ffn_act(x, w1, wg, w2, kind, block_m=64, block_f=64,
                  interpret=True)
    want = ref.ffn_act_ref(x, w1, wg, w2, kind)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_ffn_shapes_sweep():
    # the last case asks for blocks that divide neither dim: the kernel
    # takes bm 24 and bf 128 (as d_ff 8960 takes 256 of a requested 512)
    for M, D, F, bm, bf in [(64, 32, 128, 32, 32), (256, 128, 512, 128, 256),
                            (128, 96, 192, 64, 96), (48, 32, 384, 32, 256)]:
        ks = jax.random.split(jax.random.PRNGKey(M + F), 3)
        x = jax.random.normal(ks[0], (M, D), jnp.float32)
        w1 = jax.random.normal(ks[1], (D, F), jnp.float32) * 0.1
        w2 = jax.random.normal(ks[2], (F, D), jnp.float32) * 0.1
        out = ffn_act(x, w1, None, w2, "gelu", block_m=bm, block_f=bf,
                      interpret=True)
        want = ref.ffn_act_ref(x, w1, None, w2, "gelu")
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)


def test_ffn_vmem_budget():
    # granite-scale tiles: D=2048, block_f=512
    assert ffn_vmem_bytes(128, 512, 2048) < V5E_VMEM // 4


# ---------------------------------------------------------------------------
# FUSED_QKV_PROJ
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("use_bias", [True, False])
def test_qkv_proj_matches_ref(dtype, use_bias):
    M, D, N = 128, 64, 384
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (M, D), dtype)
    w = jax.random.normal(ks[1], (D, N), dtype) * 0.1
    b = jax.random.normal(ks[2], (N,), dtype) if use_bias else None
    out = qkv_proj(x, w, b, block_m=64, block_n=128, interpret=True)
    want = ref.qkv_proj_ref(x, w, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_qkv_model_layout_roundtrip():
    """ops.qkv_proj splits concat output back into per-head Q/K/V."""
    B, S, D, H, Hkv, Dh = 2, 16, 64, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (B, S, D), jnp.float32)
    wq = jax.random.normal(ks[1], (D, H, Dh), jnp.float32) * 0.1
    wk = jax.random.normal(ks[2], (D, Hkv, Dh), jnp.float32) * 0.1
    wv = jax.random.normal(ks[3], (D, Hkv, Dh), jnp.float32) * 0.1
    q, k, v = ops.qkv_proj(x, wq, wk, wv)
    np.testing.assert_allclose(
        np.asarray(q), np.asarray(jnp.einsum("bsd,dhk->bshk", x, wq)),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(v), np.asarray(jnp.einsum("bsd,dhk->bshk", x, wv)),
        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# FUSED_NORM
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["rms", "layer"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_norm_matches_ref(kind, dtype):
    M, D = 256, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (M, D), dtype)
    s = jax.random.normal(ks[1], (D,), dtype)
    b = jax.random.normal(ks[2], (D,), dtype) if kind == "layer" else None
    out = fused_norm(x, s, b, kind, block_m=64, interpret=True)
    want = ref.fused_norm_ref(x, s, b, kind)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# ---------------------------------------------------------------------------
# FUSED_FFN_ACT with int8 "RRAM-stored" weights
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["gelu", "relu2"])
def test_ffn_act_int8_matches_dequant_ref(kind):
    from repro.kernels.ffn_act import ffn_act_int8
    M, D, F = 128, 64, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    w1q = jax.random.randint(ks[0], (D, F), -127, 128, jnp.int32) \
        .astype(jnp.int8)
    w1s = jax.random.uniform(ks[1], (F,), minval=1e-3, maxval=2e-3)
    w2q = jax.random.randint(ks[2], (F, D), -127, 128, jnp.int32) \
        .astype(jnp.int8)
    w2s = jax.random.uniform(ks[3], (D,), minval=1e-3, maxval=2e-3)
    x = jax.random.normal(jax.random.PRNGKey(8), (M, D), jnp.float32)
    out = ffn_act_int8(x, w1q, w1s, w2q, w2s, kind, block_m=64,
                       block_f=64, interpret=True)
    w1 = w1q.astype(jnp.float32) * w1s
    w2 = w2q.astype(jnp.float32) * w2s
    want = ref.ffn_act_ref(x, w1, None, w2, kind)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
