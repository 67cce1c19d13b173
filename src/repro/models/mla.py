"""Multi-head latent attention (MLA), as published for DeepSeek-V2/V3.

Per layer, with x the normed input, h heads, and positions p:

    c_q       = RMSNorm(x W_DQ)                          (q_lora_rank)
    [q_n|q_r] = c_q W_UQ, per head                       (nope, rope)
    [c|k_r]   = x W_DKV;  c = RMSNorm(c);  k_r = rope(k_r, p)
                                                         (kv_lora_rank, rope;
                                                          k_r shared by all heads)
    scores    = (q_n . k_n + rope(q_r, p) . k_r) * scale
    out       = softmax(scores) v  W_O

    expanded: [k_n|v] = c W_UKV, per head                (nope, v)

with ``scale = (nope + rope)^-0.5 * mscale^2`` and ``mscale = 0.1 ln(factor)
+ 1`` under YaRN. The cache holds one row a token and layer, ``[c | k_r]``
(kv_lora_rank + rope wide): the latent after its norm, the key after rope,
in the weights' dtype. Attention reads the rows as the cache holds them, in
prefill as in extend and decode.

Absorbed form: W_UK, the k_n half of W_UKV, folds into the query,
``q_lat = q_n W_UK^T`` (kv_lora_rank wide), so the scores are
``[q_lat | rope(q_r)] . [c | k_r]`` straight over the cached rows, and W_UV,
the v half, applies after attending: ``out = (softmax . c) W_UV W_O``.

Prefill runs the expanded form: every position is new, so rebuilding k_n and
v costs what the query side costs, and the attention runs 192 / 128 wide
instead of 576 / 512. Extend and decode run the absorbed form over the
cached rows, which expanding would multiply by W_UKV again on every call.

Rope rotates (first half, second half) pairs, not DeepSeek's interleaved
pairs: a fixed permutation of the rope columns of W_UQ and W_DKV.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamSpec
from repro.models.attention import NEG_INF, flash_attention
from repro.models.layers import rms_norm, rotate

Q_BLOCK = 32  # absorbed attention: query rows a block (x heads x cached rows of scores)


def mla_params(cfg: ModelConfig) -> dict:
    d, h, dt = cfg.d_model, cfg.n_heads, cfg.dtype
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    return {
        "wq_a": ParamSpec((d, qr), dt, ("embed", None)),
        "q_norm": ParamSpec((qr,), dt, (None,), init="ones"),
        "wq_b": ParamSpec((qr, h, nope + rope), dt, (None, "heads", "head_dim")),
        "wkv_a": ParamSpec((d, kr + rope), dt, ("embed", None)),
        "kv_norm": ParamSpec((kr,), dt, (None,), init="ones"),
        "wkv_b": ParamSpec((kr, h, nope + vd), dt, (None, "heads", "head_dim")),
        "wo": ParamSpec((h, vd, d), dt, ("heads", "head_dim", "embed")),
    }


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn is not None:
        scale *= _mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return scale


def rope_freqs(cfg: ModelConfig) -> tuple[np.ndarray, float]:
    """(frequencies of the rope pairs, cos/sin scale): YaRN as in the
    published ``DeepseekV3YarnRotaryEmbedding``, else plain rope."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = cfg.yarn
    if y is None:
        return extra.astype(np.float32), 1.0

    def corr(rotations: float) -> float:
        return dim * math.log(y.original_len / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: the original frequency, 0: interpolated by factor
    freqs = extra / y.factor * (1.0 - keep) + extra * keep
    scale = _mscale(y.factor, y.mscale) / _mscale(y.factor, y.mscale_all_dim)
    return freqs.astype(np.float32), scale


def _rope(x, positions, cfg):
    freqs, scale = rope_freqs(cfg)
    return rotate(x, positions, jnp.asarray(freqs), scale)


def project(p: dict, x: jax.Array, positions: jax.Array, cfg: ModelConfig):
    """x: (b, s, d) -> (q_nope (b,s,h,nope), q_rope (b,s,h,rope) after rope,
    latent rows (b, s, kv_lora_rank + rope) as the cache holds them)."""
    with jax.named_scope("mla.project"):
        nope, kr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        c_q = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", c_q, p["wq_b"])
        q_rope = _rope(q[..., nope:], positions, cfg)
        kv = x @ p["wkv_a"]
        c = rms_norm(kv[..., :kr], p["kv_norm"], cfg.norm_eps)
        k_r = _rope(kv[..., None, kr:], positions, cfg)[:, :, 0]
        return q[..., :nope], q_rope, jnp.concatenate([c, k_r], axis=-1)


def _out(p: dict, o: jax.Array) -> jax.Array:
    """(b, s, h, v) attention output, in the activations' dtype -> (b, s, d)."""
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"])


def attend_expanded(p, q_nope, q_rope, rows, cfg, runtime) -> jax.Array:
    """Causal attention of a whole sequence over its own latent rows, with
    k_nope and v rebuilt from the latent. Returns (b, s, d)."""
    with jax.named_scope("mla.attend"):
        nope, kr, h = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.n_heads
        kv = jnp.einsum("bsr,rhk->bshk", rows[..., :kr], p["wkv_b"])
        b, s = rows.shape[:2]
        k_r = jnp.broadcast_to(rows[:, :, None, kr:], (b, s, h, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
        o = flash_attention(
            q, k, kv[..., nope:], causal=True, softmax_scale=softmax_scale(cfg),
            chunk_q=runtime.attn_chunk_q, chunk_kv=runtime.attn_chunk_kv,
        )
    return _out(p, o)


def absorb(p: dict, q_nope: jax.Array, q_rope: jax.Array) -> jax.Array:
    """Queries in the latent basis: [q_nope W_UK^T | q_rope], (b,s,h,kr+rope)."""
    with jax.named_scope("mla.absorb"):
        nope = q_nope.shape[-1]
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, p["wkv_b"][..., :nope])
        return jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], axis=-1)


def attend_absorbed(p, qf, rows, q_pos, kv_len, cfg) -> jax.Array:
    """Attention of the absorbed queries ``qf`` (b, c, h, W) over cached
    latent rows (b, T, W): the query at position q_pos (b, c) reads rows
    0..q_pos that lie below ``kv_len`` (b,). Returns (b, c, d)."""
    with jax.named_scope("mla.attend"):
        b, c, h, w = qf.shape
        kr = cfg.kv_lora_rank
        blk = min(Q_BLOCK, c)
        assert c % blk == 0, (c, blk)
        n = c // blk
        qs = (qf * softmax_scale(cfg)).reshape(b, n, blk, h, w)
        qp = q_pos.reshape(b, n, blk)
        kpos = jnp.arange(rows.shape[1])
        lat = rows[..., :kr]

        def block(args):
            qb, pb = args  # (b, blk, h, w), (b, blk)
            s = jnp.einsum("bqhw,btw->bhqt", qb, rows, preferred_element_type=jnp.float32)
            mask = (kpos <= pb[..., None]) & (kpos < kv_len[:, None, None])  # (b, blk, T)
            s = jnp.where(mask[:, None], s, NEG_INF)
            prob = jax.nn.softmax(s, axis=-1).astype(qf.dtype)
            return jnp.einsum("bhqt,btr->bqhr", prob, lat, preferred_element_type=jnp.float32)

        o_lat = jax.lax.map(block, (qs.swapaxes(0, 1), qp.swapaxes(0, 1)))
        o_lat = o_lat.swapaxes(0, 1).reshape(b, c, h, kr)
        o = jnp.einsum("bshr,rhv->bshv", o_lat.astype(qf.dtype),
                       p["wkv_b"][..., cfg.qk_nope_head_dim:])
    return _out(p, o)


def mla_full(p, x, positions, cfg, runtime, collect_cache: bool, max_len: int | None):
    """Whole-sequence MLA (prefill): (out, {"latent": rows padded to max_len})."""
    q_nope, q_rope, rows = project(p, x, positions, cfg)
    # the rows as the cache holds them (the weights' dtype), which is what
    # extend and decode read: a prefix computed here or fetched attends alike
    rows = rows.astype(cfg.dtype)
    out = attend_expanded(p, q_nope, q_rope, rows.astype(x.dtype), cfg, runtime)
    cache = None
    if collect_cache:
        cache = {"latent": jnp.pad(rows, ((0, 0), (0, max_len - x.shape[1]), (0, 0)))}
    return out, cache


def mla_decode(p, x, cache: dict, pos: jax.Array, cfg):
    """One token a sequence, x (b, 1, d), written at ``pos`` (b,) then
    attending over rows 0..pos. Returns (out (b, 1, d), cache)."""
    q_nope, q_rope, row = project(p, x, pos[:, None], cfg)
    lat = cache["latent"]
    lat = lat.at[jnp.arange(lat.shape[0]), pos].set(row[:, 0].astype(lat.dtype))
    out = attend_absorbed(p, absorb(p, q_nope, q_rope), lat, pos[:, None], pos + 1, cfg)
    return out, {"latent": lat}


def mla_extend(p, x, cache: dict, start, n_valid, positions, cfg):
    """A chunk x (b, c, d) at ``positions`` = start..start+c-1, written into
    the cache at ``start``, attending causally over rows below
    start + n_valid. Returns (out (b, c, d), cache)."""
    q_nope, q_rope, rows = project(p, x, positions, cfg)
    lat = cache["latent"]
    lat = jax.lax.dynamic_update_slice_in_dim(lat, rows.astype(lat.dtype), start, axis=1)
    kv_len = jnp.broadcast_to(start + n_valid, (x.shape[0],))
    out = attend_absorbed(p, absorb(p, q_nope, q_rope), lat, positions, kv_len, cfg)
    return out, {"latent": lat}
