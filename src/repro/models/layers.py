"""Shared layers: norms, rotary embeddings, MLPs, embeddings."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import AxisRules, ParamSpec, constrain


def rms_norm(x: jax.Array, w: jax.Array | None, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    if w is not None:
        x = x * w.astype(jnp.float32)
    return x.astype(dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    return rotate(x, positions, rope_freqs(x.shape[-1], theta))


def rotate(x: jax.Array, positions: jax.Array, freqs: jax.Array,
           scale: float = 1.0) -> jax.Array:
    """Rotary embedding of the (first half, second half) pairs of x's last
    dim at ``freqs`` (head_dim/2,); cos and sin times ``scale``.
    x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    half = x.shape[-1] // 2
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., s, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., s, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [
            x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin,
            x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin,
        ],
        axis=-1,
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def mlp_params(cfg: ModelConfig, d_ff: int) -> dict:
    d = cfg.d_model
    dt = cfg.dtype
    p = {
        "wi_gate": ParamSpec((d, d_ff), dt, ("embed", "mlp")),
        "wi_up": ParamSpec((d, d_ff), dt, ("embed", "mlp")),
        "wo": ParamSpec((d_ff, d), dt, ("mlp", "embed")),
    }
    if cfg.mlp_bias:
        p["bi_gate"] = ParamSpec((d_ff,), dt, ("mlp",), init="zeros")
        p["bi_up"] = ParamSpec((d_ff,), dt, ("mlp",), init="zeros")
        p["bo"] = ParamSpec((d,), dt, ("norm",), init="zeros")
    return p


def mlp_apply(p: dict, x: jax.Array, cfg: ModelConfig, rules: AxisRules | None) -> jax.Array:
    act = act_fn(cfg.act)
    g = x @ p["wi_gate"]
    u = x @ p["wi_up"]
    if "bi_gate" in p:
        g = g + p["bi_gate"]
        u = u + p["bi_up"]
    h = act(g) * u
    if rules is not None:
        h = constrain(h, rules, ("batch", "seq", "act_mlp"))
    if rules is not None and rules.rowp_bf16:
        from repro.distributed.collectives import row_parallel_matmul

        out = row_parallel_matmul(h, p["wo"], rules)
    else:
        out = h @ p["wo"]
    if "bo" in p:
        out = out + p["bo"]
    if rules is not None:
        out = constrain(out, rules, ("batch", "seq", "act_embed"))
    return out


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_params(cfg: ModelConfig, tp: int) -> dict:
    v = cfg.padded_vocab(tp)
    p = {"table": ParamSpec((v, cfg.d_model), cfg.dtype, ("vocab", "embed"))}
    if not cfg.tie_embeddings:
        p["head"] = ParamSpec((cfg.d_model, v), cfg.dtype, ("embed", "vocab"))
    return p


def embed_apply(p: dict, tokens: jax.Array, rules: AxisRules | None) -> jax.Array:
    out = jnp.take(p["table"], tokens, axis=0)
    if rules is not None:
        out = constrain(out, rules, ("batch", "seq", "act_embed"))
    return out


def unembed_apply(p: dict, x: jax.Array, rules: AxisRules | None) -> jax.Array:
    head = p["head"] if "head" in p else p["table"].T
    logits = x @ head.astype(x.dtype)
    logits = logits.astype(jnp.float32)
    if rules is not None:
        logits = constrain(logits, rules, ("batch", "seq", "act_vocab"))
    return logits


def norm_params(cfg: ModelConfig) -> dict:
    if cfg.nonparametric_ln:
        return {}
    return {"w": ParamSpec((cfg.d_model,), cfg.dtype, ("norm",), init="ones")}


def norm_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    return rms_norm(x, p.get("w"), cfg.norm_eps)
