"""FUSED_QKV_PROJ (paper Table I): GEMM(X·Wq)+bq; GEMM(X·Wk)+bk;
GEMM(X·Wv)+bv in one pass over X.

The fusion's point in CHIME is that X is read from DRAM once and reused by
all three projections in the PU. TPU port: Wq|Wk|Wv are concatenated along
the output dim; the X row-block stays VMEM-resident while weight column
tiles stream; bias add fused (the SFPE step). The wrapper in ops.py splits
the concatenated output back into Q/K/V.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qkv_kernel(x_ref, w_ref, b_ref, o_ref, *, use_bias: bool):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    out = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if use_bias:
        out = out + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def qkv_proj(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
             block_m: int = 128, block_n: int = 256,
             interpret: bool | None = None) -> jax.Array:
    """x: (M, D); w: (D, N) = concat(Wq|Wk|Wv); b: (N,) or None."""
    M, D = x.shape
    N = w.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    assert M % block_m == 0 and N % block_n == 0, (M, N, block_m, block_n)
    use_bias = b is not None
    bb = (b if use_bias else jnp.zeros((N,), x.dtype)).reshape(1, N)

    kernel = functools.partial(_qkv_kernel, use_bias=use_bias)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n),
        in_specs=[
            pl.BlockSpec((block_m, D), lambda mi, ni: (mi, 0)),
            pl.BlockSpec((D, block_n), lambda mi, ni: (0, ni)),
            pl.BlockSpec((1, block_n), lambda mi, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, w, bb)
