"""Compile rehearsals for a TPU v5e chip that is described, not attached.

The TPU compiler is installed with jaxlib, so the main path's Pallas
kernels and one whole fused decode step of fastvlm-1.7b are compiled here
at published widths for a `v5e:2x2` topology. Interpret mode, which the
rest of the suite uses, cannot see what this catches: block shapes that
break the (8, 128) tiling, VMEM overuse, or a program that does not fit
the chip's 16 GB. Nothing runs, so these tests say nothing about results
or times.

The topology is described only inside the fixture below: the TPU library
may be loaded by one process at a time, and every pytest-xdist worker
imports this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import attn_stream as AS
from repro.kernels import ffn_act as FA
from repro.kernels import fused_norm as FN
from repro.kernels import ops
from repro.kernels import paged_decode as PD
from repro.kernels import qkv_proj as QP

V5E_HBM = 16 * 2 ** 30
ARCH = "fastvlm-1.7b"
SLOTS, MAX_LEN, HOT_W, PROMPT = 8, 2048, 128, 512
PD_BLOCK = ops.PAGED_DECODE_BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_tiered(cfg, sh):
    """Per-slot call vmapped over the slots, as the serving decode step
    makes it (the kernel sees B = 1; vmap adds the slot grid axis)."""
    Hkv, D = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // Hkv
    pages = MAX_LEN // PD_BLOCK
    fn = jax.vmap(functools.partial(PD.paged_decode_tiered,
                                    block_k=PD_BLOCK, interpret=False))
    args = (_sds(sh, (SLOTS, 1, Hkv, G, D), jnp.bfloat16),
            _sds(sh, (SLOTS, 1, HOT_W, Hkv, D), jnp.bfloat16),
            _sds(sh, (SLOTS, 1, HOT_W, Hkv, D), jnp.bfloat16),
            _sds(sh, (SLOTS, 1, MAX_LEN, Hkv, D), jnp.int8),
            _sds(sh, (SLOTS, 1, MAX_LEN, Hkv, 1), jnp.float32),
            _sds(sh, (SLOTS, 1, MAX_LEN, Hkv, D), jnp.int8),
            _sds(sh, (SLOTS, 1, MAX_LEN, Hkv, 1), jnp.float32),
            _sds(sh, (SLOTS, 1), jnp.int32),
            _sds(sh, (SLOTS, 1, pages), jnp.int32))
    return fn, args


def _paged_flat(cfg, sh):
    Hkv, D = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // Hkv
    pages = MAX_LEN // PD_BLOCK
    fn = jax.vmap(functools.partial(PD.paged_decode_flat,
                                    block_k=PD_BLOCK, interpret=False))
    args = (_sds(sh, (SLOTS, 1, Hkv, G, D), jnp.bfloat16),
            _sds(sh, (SLOTS, 1, MAX_LEN, Hkv, D), jnp.bfloat16),
            _sds(sh, (SLOTS, 1, MAX_LEN, Hkv, D), jnp.bfloat16),
            _sds(sh, (SLOTS, 1), jnp.int32),
            _sds(sh, (SLOTS, 1, pages), jnp.int32))
    return fn, args


def _attn_stream(cfg, sh):
    D = cfg.head_dim
    fn = functools.partial(AS.attn_stream, causal=True, interpret=False)
    args = (_sds(sh, (1, cfg.num_heads, PROMPT, D), jnp.bfloat16),
            _sds(sh, (1, cfg.num_kv_heads, PROMPT, D), jnp.bfloat16),
            _sds(sh, (1, cfg.num_kv_heads, PROMPT, D), jnp.bfloat16))
    return fn, args


def _qkv_proj(cfg, sh):
    n = cfg.q_dim + 2 * cfg.kv_dim
    fn = functools.partial(QP.qkv_proj, interpret=False)
    args = (_sds(sh, (PROMPT, cfg.d_model), jnp.bfloat16),
            _sds(sh, (cfg.d_model, n), jnp.bfloat16),
            _sds(sh, (n,), jnp.bfloat16))
    return fn, args


def _fused_norm(cfg, sh):
    fn = functools.partial(FN.fused_norm, bias=None, kind="rms",
                           interpret=False)
    args = (_sds(sh, (PROMPT, cfg.d_model), jnp.bfloat16),
            _sds(sh, (cfg.d_model,), jnp.bfloat16))
    return fn, args


def _ffn_act(cfg, sh):
    fn = functools.partial(FA.ffn_act, kind="silu_gated", interpret=False)
    args = (_sds(sh, (PROMPT, cfg.d_model), jnp.bfloat16),
            _sds(sh, (cfg.d_model, cfg.d_ff), jnp.bfloat16),
            _sds(sh, (cfg.d_model, cfg.d_ff), jnp.bfloat16),
            _sds(sh, (cfg.d_ff, cfg.d_model), jnp.bfloat16))
    return fn, args


KERNELS = {
    "paged_decode_tiered": _paged_tiered,
    "paged_decode_flat": _paged_flat,
    "attn_stream": _attn_stream,
    "qkv_proj": _qkv_proj,
    "fused_norm": _fused_norm,
    "ffn_act": _ffn_act,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    cfg = get_config(ARCH)
    fn, args = KERNELS[name](cfg, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """One whole fused decode step of fastvlm-1.7b (28 layers, bf16) over
    the serving backend's slot pool, from `jax.eval_shape` shapes."""
    from repro.models import Model
    from repro.serving import LocalBackend

    cfg = get_config(ARCH).replace(remat="none", kv_policy="tiered",
                                   kv_hot_window=HOT_W)
    model = Model(cfg)
    backend = LocalBackend(model, None, SLOTS, MAX_LEN, fused_decode=True)
    assert backend.fused_decode

    def place(tree):
        return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                            tree)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache, _ = backend.model.cache_spec(SLOTS, MAX_LEN)
    toks = _sds(one_chip, (SLOTS, 1), jnp.int32)
    pos = _sds(one_chip, (SLOTS,), jnp.int32)
    # the kernels choose interpret mode from the default backend, which is
    # the CPU in this process; the step is lowered for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = backend._step.lower(
        params, toks, place(cache), pos,
        _sds(one_chip, (SLOTS,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM, mem
