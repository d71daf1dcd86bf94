"""FUSED_ATTN_STREAM (paper Table I): flash-style streaming attention.

CHIME's DRAM-NMP streams K/V tiles from DRAM row buffers through the
SFPE-PE pipeline, updating an online softmax so the (S, L) score matrix is
never materialized. The TPU port: the Q block is VMEM-resident, K/V tiles
stream HBM->VMEM via BlockSpecs, scores/probabilities live only in
VMEM/VREGs, the running (max, denominator, accumulator) state sits in VMEM
scratch. MXU-aligned tiles (multiples of 128 on the matmul dims).

Layout: q (B, H, S, D); k, v (B, Hkv, L, D); GQA mapped by pointing each Q
head's K/V BlockSpec at head h // (H // Hkv).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 20


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, d_ref, *,
                 scale: float, causal: bool, block_q: int, block_k: int,
                 num_k: int, q_offset: int, kv_len: int):
    """Grid: (BH, num_q, num_k); the k axis is the streaming ('arbitrary')
    dimension carrying the online-softmax state in scratch. `kv_len` is the
    unpadded key count: blocks at or past it hold grid padding only."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)

    # A k-block contributes nothing when it is pure grid padding, or —
    # under the causal mask — when it sits entirely above the diagonal
    # (min k_pos > max q_pos for this q block). Skipping keeps the
    # accumulator exact instead of leaning on f32 exp underflow, which is
    # what broke once every score in a block was NEG_INF.
    dead = ki * block_k >= kv_len
    if causal:
        dead |= ki * block_k > qi * block_q + block_q - 1 + q_offset

    @pl.when(jnp.logical_not(dead))
    def _update():
        q = q_ref[0].astype(jnp.float32)                   # (bq, D)
        k = k_ref[0].astype(jnp.float32)                   # (bk, D)
        v = v_ref[0].astype(jnp.float32)                   # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (bq, bk)

        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + q_offset
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        if kv_len % block_k:  # padded tail block: mask phantom keys
            s = jnp.where(k_pos < kv_len, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(d_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"))
def attn_stream(q: jax.Array, k: jax.Array, v: jax.Array, *,
                causal: bool = True, scale: float | None = None,
                block_q: int = 128, block_k: int = 128,
                interpret: bool | None = None) -> jax.Array:
    """q: (B,H,S,D); k,v: (B,Hkv,L,D) -> (B,H,S,D)."""
    B, H, S, D = q.shape
    Hkv, L = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    if causal and S > L:
        raise ValueError(
            f"causal attn_stream requires S <= L (got S={S}, L={L}): with "
            f"q_offset = L - S negative the first S - L queries precede "
            f"every key and their attention is undefined")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, S)
    block_k = min(block_k, L)
    # Ragged shapes: pad q/k/v up to the block grid. Phantom keys are
    # masked to NEG_INF in-kernel (kv_len) and phantom query rows are
    # sliced off the output below.
    Sp = -(-S // block_q) * block_q
    Lp = -(-L // block_k) * block_k
    num_q, num_k = Sp // block_q, Lp // block_k
    q_offset = L - S  # causal alignment when L != S (cached prefix)

    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * Hkv, L, D)
    vf = v.reshape(B * Hkv, L, D)
    if Sp != S:
        qf = jnp.pad(qf, ((0, 0), (0, Sp - S), (0, 0)))
    if Lp != L:
        kf = jnp.pad(kf, ((0, 0), (0, Lp - L), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, Lp - L), (0, 0)))

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k=num_k, q_offset=q_offset, kv_len=L)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(B, H, Sp, D)
    return out[:, :, :S] if Sp != S else out


def attn_stream_vmem_bytes(block_q: int, block_k: int, D: int,
                           dtype_bytes: int = 2) -> int:
    """Static VMEM working set claimed by the BlockSpecs + scratch —
    used by tests to assert the tiles fit v5e VMEM (~128 MB)."""
    tiles = (block_q * D + 2 * block_k * D) * dtype_bytes   # q + k + v
    casts = (block_q * D + 2 * block_k * D) * 4             # f32 copies
    scratch = (block_q * D + 2 * block_q) * 4               # acc + m + d
    out = block_q * D * dtype_bytes
    return tiles + casts + scratch + out
