"""Plain float32 reference of a Qwen2-backbone VLM (FastVLM's LLaVA-Qwen2).

Written from the published architecture, not from the program: a stub
vision tower's patch features go through the two-layer GELU connector
(``mm_projector_type`` "mlp2x_gelu", exact erf GELU), then the Qwen2
decoder: pre-RMSNorm blocks with grouped-query attention (q, k and v
carry biases, the output projection none), rotate-half RoPE with the
configuration's ``rope_theta``, a SiLU-gated MLP, a final RMSNorm and the
tied embedding as the output head. It runs one request at a time over the
whole sequence, causally, in float32 at "highest" matmul precision, and
returns the logits of the rows that predict the served tokens.

The deployment stores keys and values in two tiers, as its configuration
states: the newest ``hot_window`` positions as computed, older ones in
int8 with one symmetric scale per position and kv head (max |x| / 127,
rounded to nearest). A served token after the first comes from a decode
step at its own position, which reads the older positions from the int8
tier; the first comes from the prompt's prefill, which reads them all as
computed. So rows past the prompt attend to key j <= i - hot_window
through that codec, and prompt rows attend to every key as computed.

Weights come in the serving layout (``weights.make_params``), read as:
``embed/table`` (vocab rows, padded past ``vocab_size``),
``frontend/{w1,b1,w2,b2}``, ``units/u0/{ln1,ln2}/scale`` and
``units/u0/mixer/{wq,wk,wv,wo,bq,bk,bv,bo}`` stacked over the layers,
``units/u0/mlp/{w_up,w_gate,w_down}`` where ``w_up`` is the SiLU-gated
projection (HF ``gate_proj``) and ``w_gate`` the linear one (HF
``up_proj``), and ``final_norm/scale``.

``control=True`` computes the same forward with every linear layer in
int8 (W8A8: symmetric per-row activation and per-output-column weight
scales, int32 accumulation), the step below the configuration's bf16.
Attention's score and value products stay in float32 there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _linear(x, w, control: bool):
    """x (..., K) @ w (K, N) in float32, or in int8 for the control."""
    if not control:
        return jnp.matmul(x, w, precision=HI)
    qx, sx = _q8(x, -1)
    qw, sw = _q8(w, 0)
    acc = jax.lax.dot_general(qx, qw, (((qx.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    """x (S, heads, D), rotate-half pairing (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv           # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _f32(t):
    return jax.tree.map(lambda a: a.astype(jnp.float32), t)


def _int8_tier(x):
    """The cold tier's codec over the last axis: int8, one scale per row."""
    q, s = _q8(x, -1)
    return q.astype(jnp.float32) * s


def _forward_rows(params, patches, ids, start, *, spec, rows: int,
                  control: bool):
    d = spec["hidden_size"]
    H, Hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    Dh = d // H
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    table = params["embed"]["table"].astype(jnp.float32)
    x = table[ids]
    if patches is not None:
        fe = _f32(params["frontend"])
        h = _linear(patches, fe["w1"], control) + fe["b1"]
        h = jax.nn.gelu(h, approximate=False)
        vis = _linear(h, fe["w2"], control) + fe["b2"]
        x = jnp.concatenate([vis, x], 0)
    S = x.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    # decode rows (past the prompt, whose last row is `start`) read keys
    # older than the hot window from the int8 tier
    hot = spec["serving"]["hot_window"]
    cold = (pos[:, None] > start) & (pos[None, :] <= pos[:, None] - hot)

    def layer(x, p):
        p = _f32(p)
        m = p["mixer"]
        h = _rms(x, p["ln1"]["scale"], eps)
        q = _linear(h, m["wq"].reshape(d, H * Dh), control).reshape(
            S, H, Dh) + m["bq"]
        k = _linear(h, m["wk"].reshape(d, Hkv * Dh), control).reshape(
            S, Hkv, Dh) + m["bk"]
        v = _linear(h, m["wv"].reshape(d, Hkv * Dh), control).reshape(
            S, Hkv, Dh) + m["bv"]
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        kc, vc = _int8_tier(k), _int8_tier(v)
        k, v, kc, vc = (jnp.repeat(t, H // Hkv, axis=1)
                        for t in (k, v, kc, vc))
        scale = 1.0 / jnp.sqrt(jnp.float32(Dh))
        s = jnp.where(
            cold[None],
            jnp.einsum("qhd,khd->hqk", q, kc, precision=HI),
            jnp.einsum("qhd,khd->hqk", q, k, precision=HI)) * scale
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", jnp.where(cold[None], 0.0, pr), v,
                       precision=HI) + jnp.einsum(
            "hqk,khd->qhd", jnp.where(cold[None], pr, 0.0), vc,
            precision=HI)
        x = x + _linear(o.reshape(S, H * Dh),
                        m["wo"].reshape(H * Dh, d), control) + m["bo"]
        f = p["mlp"]
        h = _rms(x, p["ln2"]["scale"], eps)
        g = jax.nn.silu(_linear(h, f["w_up"], control)) \
            * _linear(h, f["w_gate"], control)
        return x + _linear(g, f["w_down"], control), None

    if set(params["units"]) != {"u0"}:
        raise ValueError("the reference scans one stacked unit of layers")
    x, _ = jax.lax.scan(layer, x, params["units"]["u0"])
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = _rms(x, params["final_norm"]["scale"].astype(jnp.float32), eps)
    vocab = spec["vocab_size"]
    return _linear(x, table[:vocab].T, control)             # (rows, vocab)


class Reference:
    """Logit rows of whole requests, one request per call, over a fixed
    padded length so that each cell compiles one program per variant."""

    def __init__(self, spec: dict, seq_len: int, rows: int):
        self.spec, self.seq_len, self.rows = spec, seq_len, rows
        self._fn = {}

    def _jit(self, image: bool, control: bool):
        key = (image, control)
        if key not in self._fn:
            fwd = functools.partial(_forward_rows, spec=self.spec,
                                    rows=self.rows, control=control)
            if image:
                self._fn[key] = jax.jit(fwd)
            else:
                self._fn[key] = jax.jit(
                    lambda p, ids, start: fwd(p, None, ids, start))
        return self._fn[key]

    def logits(self, params, patches, ids, start: int,
               control: bool = False) -> jax.Array:
        """Rows ``start .. start + rows`` of the logits of the sequence
        [patches, ids], with ``ids`` right-padded to the fixed length.
        Padding sits after every row read, so causal attention never
        sees it."""
        n_img = 0 if patches is None else patches.shape[0]
        pad = self.seq_len - n_img - ids.shape[0]
        if pad < 0:
            raise ValueError("request longer than the reference length")
        ids = jnp.pad(jnp.asarray(ids, jnp.int32), (0, pad))
        fn = self._jit(patches is not None, control)
        if patches is None:
            return fn(params, ids, jnp.int32(start))
        return fn(params, jnp.asarray(patches, jnp.float32), ids,
                  jnp.int32(start))
