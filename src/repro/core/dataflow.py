"""Two-cut-point dataflow verification against the lowered program.

CHIME's rule: per transformer layer, exactly two activation tensors cross
the memory-domain boundary (AttnOut, FFNOut). In the TPU port the domain
boundary maps to the tensor-parallel collective boundary: the attention
block ends with one partial-sum reduction (after the out-projection) and
the FFN block with one (after the down-projection) — collectives must not
fire *inside* a fused region.

``audit_layer_collectives`` lowers a single layer the way the model runs it
and counts collective ops in the resulting HLO, asserting the invariant.
Used by tests/test_dataflow.py; the full-model dry-run JSONs record the same
per-layer collective counts at scale.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.model import Model

_COLLECTIVE_RE = re.compile(
    r"=\s+\S+\s+(all-gather|all-reduce|reduce-scatter|all-to-all"
    r"|collective-permute)(-start)?\(")


def count_collectives(hlo_text: str) -> dict[str, int]:
    """Count collective op *definitions* (not name references) in HLO."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def lower_single_layer_hlo(cfg: ModelConfig, mesh, batch: int = 4,
                           seq: int = 32) -> str:
    """Lower one full forward of a single-layer variant of ``cfg`` on
    ``mesh`` and return optimized HLO text."""
    from repro.sharding import ShardingRules
    one = cfg.replace(num_layers=len(cfg.segments[0].pattern),
                      segments=(cfg.segments[0].__class__(
                          cfg.segments[0].pattern, 1),),
                      remat="none")
    rules = ShardingRules(mesh)
    model = Model(one, rules)
    specs = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if one.frontend is not None and one.family != "audio":
        tv = one.frontend.num_tokens
        specs = {
            "tokens": jax.ShapeDtypeStruct((batch, seq - tv), jnp.int32),
            "patches": jax.ShapeDtypeStruct(
                (batch, tv, one.frontend.frontend_dim), jnp.float32)}
    elif one.family == "audio":
        specs = {"frames": jax.ShapeDtypeStruct(
            (batch, seq, one.frontend.frontend_dim), jnp.float32)}
    p_sds, _ = model.abstract_params()
    p_sh = model.param_shardings(rules)
    lowered = jax.jit(model.forward, in_shardings=(p_sh, None)) \
        .lower(p_sds, specs)
    return lowered.compile().as_text()
