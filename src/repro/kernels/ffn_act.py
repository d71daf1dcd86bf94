"""FUSED_FFN_ACT (paper Table I): GEMM(X·W1) -> act -> GEMM(·W2) chained in
one kernel — the RRAM-NMP's fused FFN, retargeted to MXU.

CHIME's RRAM chiplet keeps FFN weights resident and chains the two GEMMs so
the (tokens, d_ff) intermediate never leaves the logic die. TPU port: the X
row-block and the output accumulator are VMEM-resident; W1/W2 column/row
tiles stream HBM->VMEM; the hidden activation exists only as a
(block_m, block_f) VMEM tile. Supports gated variants (W_gate streamed
alongside W1) and squared-ReLU (nemotron).

Int8 "RRAM-stored" weights are dequantized in VMEM before the MXU dot — the
HBM traffic is the int8 bytes (see core/quant.py for the domain argument).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block(n: int, want: int, unit: int) -> int:
    """``want`` when it divides ``n``, else the largest multiple of
    ``unit`` below it that does; ``n`` itself when ``n <= want`` or no
    such multiple exists. Each is a legal TPU block: a multiple of the
    (8, 128) tile, or the whole dimension (d_ff = 8960 = 128 * 70 takes
    256, not 512)."""
    if n <= want or n % want == 0:
        return min(n, want)
    for b in range(want - want % unit, 0, -unit):
        if n % b == 0:
            return b
    return n


def _act(h, kind: str):
    if kind == "silu_gated":
        return jax.nn.silu(h)
    if kind in ("gelu", "gelu_gated"):
        return jax.nn.gelu(h)
    if kind == "relu2":
        return jnp.square(jax.nn.relu(h))
    raise ValueError(kind)


def _ffn_kernel(x_ref, w1_ref, wg_ref, w2_ref, o_ref, acc_ref, *,
                kind: str, num_f: int, gated: bool):
    """Grid: (num_m, num_f). f is the streaming axis: each step computes a
    (block_m, block_f) hidden tile and accumulates its contribution to the
    (block_m, D) output in VMEM scratch."""
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                  # (bm, D)
    w1 = w1_ref[...].astype(jnp.float32)                # (D, bf)
    h = _act(jax.lax.dot_general(
        x, w1, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32), kind)
    if gated:
        wg = wg_ref[...].astype(jnp.float32)
        h = h * jax.lax.dot_general(
            x, wg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    w2 = w2_ref[...].astype(jnp.float32)                # (bf, D)
    acc_ref[...] += jax.lax.dot_general(
        h, w2, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(fi == num_f - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "block_m", "block_f", "interpret"))
def ffn_act(x: jax.Array, w_up: jax.Array, w_gate: jax.Array | None,
            w_down: jax.Array, kind: str = "silu_gated", *,
            block_m: int = 128, block_f: int = 512,
            interpret: bool | None = None) -> jax.Array:
    """x: (M, D); w_up/w_gate: (D, F); w_down: (F, D) -> (M, D)."""
    M, D = x.shape
    F = w_up.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_m = _block(M, block_m, 8)
    block_f = _block(F, block_f, 128)
    num_m, num_f = M // block_m, F // block_f
    gated = w_gate is not None
    wg = w_gate if gated else w_up  # dummy ref when ungated (never read)

    kernel = functools.partial(_ffn_kernel, kind=kind, num_f=num_f,
                               gated=gated)
    return pl.pallas_call(
        kernel,
        grid=(num_m, num_f),
        in_specs=[
            pl.BlockSpec((block_m, D), lambda mi, fi: (mi, 0)),
            pl.BlockSpec((D, block_f), lambda mi, fi: (0, fi)),
            pl.BlockSpec((D, block_f), lambda mi, fi: (0, fi)),
            pl.BlockSpec((block_f, D), lambda mi, fi: (fi, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, D), lambda mi, fi: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_up, wg, w_down)


def ffn_vmem_bytes(block_m: int, block_f: int, D: int,
                   dtype_bytes: int = 2, gated: bool = True) -> int:
    tiles = (block_m * D + (2 if gated else 1) * D * block_f
             + block_f * D) * dtype_bytes
    scratch = block_m * D * 4
    out = block_m * D * dtype_bytes
    return tiles + scratch + out


# ---------------------------------------------------------------------------
# int8 "RRAM-stored" weights: dequant in VMEM before the MXU dot — the
# HBM->VMEM stream is the int8 array (half the bf16 bytes), which is the
# paper's RRAM density/read-energy argument made concrete.
# ---------------------------------------------------------------------------
def _ffn_q_kernel(x_ref, w1q_ref, w1s_ref, w2q_ref, w2s_ref, o_ref,
                  acc_ref, *, kind: str, num_f: int):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w1 = w1q_ref[...].astype(jnp.float32) \
        * w1s_ref[...].astype(jnp.float32)          # (D,bf) x (1,bf)
    h = _act(jax.lax.dot_general(
        x, w1, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32), kind)
    w2 = w2q_ref[...].astype(jnp.float32) \
        * w2s_ref[...].astype(jnp.float32)          # (bf,D) x (1,D)
    acc_ref[...] += jax.lax.dot_general(
        h, w2, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(fi == num_f - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("kind", "block_m", "block_f", "interpret"))
def ffn_act_int8(x: jax.Array, w_up_q: jax.Array, w_up_scale: jax.Array,
                 w_down_q: jax.Array, w_down_scale: jax.Array,
                 kind: str = "gelu", *, block_m: int = 128,
                 block_f: int = 512, interpret: bool | None = None
                 ) -> jax.Array:
    """x: (M,D); w_up_q int8 (D,F), w_up_scale (F,); w_down_q int8 (F,D),
    w_down_scale (D,). Ungated kinds (gelu/relu2)."""
    M, D = x.shape
    F = w_up_q.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_m = _block(M, block_m, 8)
    block_f = _block(F, block_f, 128)
    num_m, num_f = M // block_m, F // block_f
    kernel = functools.partial(_ffn_q_kernel, kind=kind, num_f=num_f)
    return pl.pallas_call(
        kernel,
        grid=(num_m, num_f),
        in_specs=[
            pl.BlockSpec((block_m, D), lambda mi, fi: (mi, 0)),
            pl.BlockSpec((D, block_f), lambda mi, fi: (0, fi)),
            pl.BlockSpec((1, block_f), lambda mi, fi: (0, fi)),
            pl.BlockSpec((block_f, D), lambda mi, fi: (fi, 0)),
            pl.BlockSpec((1, D), lambda mi, fi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, D), lambda mi, fi: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_up_q, w_up_scale.reshape(1, F), w_down_q,
      w_down_scale.reshape(1, D))
