"""Operations and bytes the served work needs, from shapes alone.

``spec`` is a configuration file's dict (published key names). Counts
are of useful work: attended positions and valid prompt rows, never the
padding or masked slots a program may also compute. A multiply-add is
two operations.
"""

from __future__ import annotations


def dims(spec: dict) -> dict:
    d = spec["hidden_size"]
    H, Hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    Dh = d // H
    return dict(d=d, H=H, Hkv=Hkv, Dh=Dh, L=spec["num_hidden_layers"],
                ff=spec["intermediate_size"], V=spec["vocab_size"],
                img_dim=spec.get("mm_hidden_size", 0))


def linear_flops_per_row(spec: dict) -> int:
    """Projections and MLP of every layer for one sequence row."""
    k = dims(spec)
    d, H, Hkv, Dh, ff = k["d"], k["H"], k["Hkv"], k["Dh"], k["ff"]
    per_layer = d * H * Dh + 2 * d * Hkv * Dh + H * Dh * d + 3 * d * ff
    return 2 * k["L"] * per_layer


def attention_flops(spec: dict, context: int) -> int:
    """Scores and value sums of one query row over ``context`` keys, all
    layers."""
    k = dims(spec)
    return 4 * k["L"] * k["H"] * k["Dh"] * context


def head_flops(spec: dict) -> int:
    k = dims(spec)
    return 2 * k["d"] * k["V"]


def connector_flops(spec: dict) -> int:
    """Two-layer connector for one image row."""
    k = dims(spec)
    return 2 * (k["img_dim"] * k["d"] + k["d"] * k["d"])


def prefill_flops(spec: dict, prompt_len: int, image_rows: int) -> int:
    """Whole-prompt prefill of ``prompt_len`` positions, the first
    ``image_rows`` of them image rows, ending in one row of logits."""
    p = prompt_len
    causal_pairs = p * (p + 1) // 2
    return (linear_flops_per_row(spec) * p
            + attention_flops(spec, 1) * causal_pairs
            + connector_flops(spec) * image_rows + head_flops(spec))


def decode_flops(spec: dict, pos: int) -> int:
    """One decode token written at position ``pos`` (attends pos + 1)."""
    return (linear_flops_per_row(spec) + attention_flops(spec, pos + 1)
            + head_flops(spec))


def paged_decode_tiered_cost(spec: dict, positions, hot_window: int,
                             kv_bytes: int = 2) -> tuple[int, int]:
    """(operations, HBM bytes) of ONE call of the fused tiered decode
    kernel (one layer) over slots at decode positions ``positions``.

    A slot at position p attends p + 1 keys: the newest min(p + 1, W) in
    the hot ring at ``kv_bytes`` per element, the rest in the int8 cold
    tier with one float32 scale per token and head, for K and for V.
    Each slot also reads its query and writes its output."""
    k = dims(spec)
    H, Hkv, Dh = k["H"], k["Hkv"], k["Dh"]
    ops = nbytes = 0
    for p in positions:
        n = int(p) + 1
        hot = min(n, hot_window)
        cold = n - hot
        ops += 4 * H * Dh * n
        nbytes += 2 * hot * Hkv * Dh * kv_bytes
        nbytes += 2 * cold * Hkv * (Dh + 4)
        nbytes += 2 * H * Dh * kv_bytes
    return ops, nbytes
