"""FUSED_NORM (paper Table I): Reduce -> Normalize -> Scale -> Shift on the
SFPE, i.e. the VPU on TPU. Row-block tiling; full feature dim per tile so
the reduction is kernel-local."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _norm_kernel(x_ref, s_ref, b_ref, o_ref, *, kind: str, eps: float,
                 use_bias: bool):
    x = x_ref[...].astype(jnp.float32)                    # (bm, D)
    s = s_ref[...].astype(jnp.float32)                    # (1, D)
    if kind == "rms":
        out = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=1, keepdims=True) + eps) * s
    else:
        mean = jnp.mean(x, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=1, keepdims=True)
        out = (x - mean) * jax.lax.rsqrt(var + eps) * s
    if use_bias:
        out = out + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("kind", "eps", "block_m", "interpret"))
def fused_norm(x: jax.Array, scale: jax.Array, bias: jax.Array | None,
               kind: str = "rms", eps: float = 1e-6, *,
               block_m: int = 256, interpret: bool | None = None
               ) -> jax.Array:
    """x: (M, D) -> (M, D)."""
    M, D = x.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_m = min(block_m, M)
    assert M % block_m == 0, (M, block_m)
    use_bias = bias is not None
    bb = (bias if use_bias else jnp.zeros((D,), x.dtype)).reshape(1, D)

    kernel = functools.partial(_norm_kernel, kind=kind, eps=eps,
                               use_bias=use_bias)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, D), lambda mi: (mi, 0)),
            pl.BlockSpec((1, D), lambda mi: (0, 0)),
            pl.BlockSpec((1, D), lambda mi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, D), lambda mi: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, scale.reshape(1, D), bb)
