"""Attention: chunked flash (jnp portable path) + GQA decode.

Two decode strategies (RuntimeConfig.decode_kv):

* ``replicated``       — paper-faithful baseline: KV heads replicated across
                         TP shards, every chip reads the full KV cache.
* ``pool_interleaved`` — beyond-paper (Beluga O9 made TPU-native): the KV
                         sequence dimension is interleaved across chips; each
                         chip attends over its local shard and partial results
                         are merged with a log-sum-exp ``psum`` (distributed
                         flash-decode) inside ``shard_map``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.distributed.sharding import AxisRules, ParamSpec, constrain

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def kv_heads_sharded(cfg: ModelConfig, rules: AxisRules | None) -> bool:
    """True when the KV heads themselves divide the TP degree."""
    return rules is not None and cfg.n_kv_heads % rules.tp == 0


def attn_params(cfg: ModelConfig, tp: int) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    hq = cfg.padded_heads(tp)
    hkv = cfg.n_kv_heads
    dt = cfg.dtype
    # KV projections are stored flattened (d, hkv*hd) and TP-sharded over
    # `model` on the flattened dim: the matmul is always balanced; when
    # hkv % tp != 0 the (small) activation is all-gathered before attention
    # instead of replicating the projection compute 16x.
    p = {
        "wq": ParamSpec((d, hq, hd), dt, ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, hkv * hd), dt, ("embed", "kv_flat")),
        "wv": ParamSpec((d, hkv * hd), dt, ("embed", "kv_flat")),
        "wo": ParamSpec((hq, hd, d), dt, ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((hq, hd), dt, ("heads", "head_dim"), init="zeros")
        p["bk"] = ParamSpec((hkv * hd,), dt, ("kv_flat",), init="zeros")
        p["bv"] = ParamSpec((hkv * hd,), dt, ("kv_flat",), init="zeros")
    if cfg.attn_out_bias:
        p["bo"] = ParamSpec((d,), dt, ("norm",), init="zeros")
    return p


def qkv_proj(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
             rules: AxisRules | None):
    """x: (b, s, d) -> q (b,s,hq,hd), k/v (b,s,hkv,hd), with RoPE applied."""
    b, s, _ = x.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k2 = x @ p["wk"]  # (b, s, hkv*hd) sharded over model
    v2 = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k2 = k2 + p["bk"]
        v2 = v2 + p["bv"]
    if rules is not None:
        k2 = constrain(k2, rules, ("batch", "seq", "act_mlp"))
        v2 = constrain(v2, rules, ("batch", "seq", "act_mlp"))
    k = k2.reshape(b, s, hkv, hd)
    v = v2.reshape(b, s, hkv, hd)
    q = apply_rope_heads(q, positions, cfg.rope_theta)
    k = apply_rope_heads(k, positions, cfg.rope_theta)
    if rules is not None:
        kv_ax = "act_heads" if kv_heads_sharded(cfg, rules) else None
        q = constrain(q, rules, ("batch", "seq", "act_heads", None))
        k = constrain(k, rules, ("batch", "seq", kv_ax, None))
        v = constrain(v, rules, ("batch", "seq", kv_ax, None))
    return q, k, v


def apply_rope_heads(x, positions, theta):
    from repro.models.layers import apply_rope

    return apply_rope(x, positions, theta)


def out_proj(p: dict, attn_out: jax.Array, rules: AxisRules | None) -> jax.Array:
    if rules is not None and rules.rowp_bf16:
        from repro.distributed.collectives import row_parallel_matmul

        b, s, hq, hd = attn_out.shape
        out = row_parallel_matmul(
            attn_out.reshape(b, s, hq * hd), p["wo"].reshape(hq * hd, -1), rules
        )
    else:
        out = jnp.einsum("bshk,hkd->bsd", attn_out, p["wo"])
    if "bo" in p:
        out = out + p["bo"]
    if rules is not None:
        out = constrain(out, rules, ("batch", "seq", "act_embed"))
    return out


# ---------------------------------------------------------------------------
# Chunked flash attention (portable jnp path; the TPU hot path is the Pallas
# kernel in repro.kernels.flash_attention, numerics-checked against this).
# ---------------------------------------------------------------------------


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(b, s, hkv, d) -> (b, s, hkv*n_rep, d) by group broadcast."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: jax.Array | int = 0,
    kv_len: jax.Array | None = None,
    chunk_q: int = 512,
    chunk_kv: int = 1024,
    softmax_scale: float | None = None,
) -> jax.Array:
    """Chunked (flash-style) attention with running softmax.

    q, k: (b, s, h, d); v: (b, skv, hkv, dv), dv may differ from d (MLA);
    GQA via on-the-fly repeat of the kv chunk.  ``q_offset`` is the absolute position of q[:, 0] for
    causal masking against the kv positions; ``kv_len`` masks a ragged tail.
    """
    b, sq_in, hq, d = q.shape
    _, skv_in, hkv, _ = k.shape
    dv = v.shape[-1]
    n_rep = hq // hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)

    chunk_q = min(chunk_q, sq_in)
    chunk_kv = min(chunk_kv, skv_in)
    # pad ragged tails up to chunk multiples; tail is masked via kv_len
    sq = -(-sq_in // chunk_q) * chunk_q
    skv = -(-skv_in // chunk_kv) * chunk_kv
    if sq != sq_in:
        q = jnp.pad(q, ((0, 0), (0, sq - sq_in), (0, 0), (0, 0)))
    if skv != skv_in:
        k = jnp.pad(k, ((0, 0), (0, skv - skv_in), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv - skv_in), (0, 0), (0, 0)))
        kv_len = jnp.minimum(
            skv_in if kv_len is None else kv_len, jnp.asarray(skv_in)
        )
    nq = sq // chunk_q
    nkv = skv // chunk_kv

    q = q * scale
    qs = q.reshape(b, nq, chunk_q, hq, d).transpose(1, 0, 2, 3, 4)

    def per_q_chunk(qi, q_chunk):
        q_pos = q_offset + qi * chunk_q + jnp.arange(chunk_q)

        def kv_step(carry, ci):
            acc, m, l = carry
            k_chunk = jax.lax.dynamic_slice_in_dim(k, ci * chunk_kv, chunk_kv, 1)
            v_chunk = jax.lax.dynamic_slice_in_dim(v, ci * chunk_kv, chunk_kv, 1)
            k_chunk = _repeat_kv(k_chunk, n_rep)
            v_chunk = _repeat_kv(v_chunk, n_rep)
            s_ij = jnp.einsum(
                "bqhd,bkhd->bhqk", q_chunk, k_chunk, preferred_element_type=jnp.float32
            )
            kv_pos = ci * chunk_kv + jnp.arange(chunk_kv)
            mask = jnp.ones((chunk_q, chunk_kv), jnp.bool_)
            if causal:
                mask &= q_pos[:, None] >= kv_pos[None, :]
            if kv_len is not None:
                mask &= kv_pos[None, :] < kv_len
            s_ij = jnp.where(mask[None, None], s_ij, NEG_INF)
            m_new = jnp.maximum(m, s_ij.max(axis=-1))
            p_ij = jnp.exp(s_ij - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p_ij.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p_ij.astype(v_chunk.dtype), v_chunk,
                preferred_element_type=jnp.float32,
            )
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, hq, chunk_q, dv), jnp.float32)
        m0 = jnp.full((b, hq, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hq, chunk_q), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), jnp.arange(nkv)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 2, 1, 3)  # (b, cq, hq, dv)

    outs = jax.lax.map(
        lambda args: per_q_chunk(args[0], args[1]), (jnp.arange(nq), qs)
    )  # (nq, b, cq, hq, dv)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, dv)
    return out[:, :sq_in].astype(v.dtype)


# ---------------------------------------------------------------------------
# Decode attention
# ---------------------------------------------------------------------------


def decode_attention_replicated(
    q: jax.Array,  # (b, 1, hq, d)
    k_cache: jax.Array,  # (b, s_max, hkv, d)
    v_cache: jax.Array,
    cache_len: jax.Array,  # (b,) or scalar
) -> jax.Array:
    """Baseline: every chip reads the full KV cache (KV replicated over TP)."""
    b, _, hq, d = q.shape
    k_cache, v_cache = _dequant(k_cache), _dequant(v_cache)
    hkv = k_cache.shape[2]
    n_rep = hq // hkv
    scale = 1.0 / math.sqrt(d)
    # keep q in the cache dtype: a mixed-dtype einsum would make XLA
    # materialize an f32 copy of the whole cache (seen in the roofline HLO)
    qg = (q[:, 0] * scale).astype(k_cache.dtype).reshape(b, hkv, n_rep, d)
    s = jnp.einsum(
        "bgrd,bkgd->bgrk", qg, k_cache, preferred_element_type=jnp.float32
    )
    pos = jnp.arange(k_cache.shape[1])
    mask = pos[None, :] < jnp.reshape(cache_len, (-1, 1))
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bgrk,bkgd->bgrd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def _local_partial_attn(q, k_shard, v_shard, local_mask):
    """Per-shard partial flash-decode: returns (num, den, max) for LSE merge.

    q: (b, hq, d) pre-scaled; k/v_shard: (b, s_loc, hkv, d);
    local_mask: (b, s_loc) bool validity.
    """
    b, hq, d = q.shape
    k_shard, v_shard = _dequant(k_shard), _dequant(v_shard)
    hkv = k_shard.shape[2]
    n_rep = hq // hkv
    qg = q.astype(k_shard.dtype).reshape(b, hkv, n_rep, d)  # no f32 cache copy
    s = jnp.einsum(
        "bgrd,bkgd->bgrk", qg, k_shard, preferred_element_type=jnp.float32
    )
    s = jnp.where(local_mask[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)  # (b, g, r)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(local_mask[:, None, None, :], p, 0.0)
    den = p.sum(axis=-1)
    num = jnp.einsum(
        "bgrk,bkgd->bgrd", p.astype(v_shard.dtype), v_shard,
        preferred_element_type=jnp.float32,
    )
    return num, den, m


def decode_attention_interleaved(
    q: jax.Array,  # (b, 1, hq, d) -- globally replicated heads inside shard_map
    k_cache: jax.Array,  # (b, s_max, hkv, d) seq-sharded over `axes`
    v_cache: jax.Array,
    cache_len: jax.Array,  # (b,)
    mesh,
    axes: tuple[str, ...],
    batch_axes: tuple[str, ...] = (),
) -> jax.Array:
    """Beluga-O9 decode: KV seq interleaved across `axes`; LSE-merge psum.

    Entered from the GSPMD world via shard_map. q must be replicated over
    `axes`; the kv caches are sharded on their seq dim.
    """
    b, _, hq, d = q.shape
    scale = 1.0 / math.sqrt(d)

    b_ax = batch_axes if batch_axes else None

    def local_fn(q, k_shard, v_shard, cache_len):
        # row-major shard id across the (possibly multiple) kv axes
        shard_id = 0
        for ax in axes:
            shard_id = shard_id * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        b_loc, s_loc = k_shard.shape[0], k_shard.shape[1]
        pos = shard_id * s_loc + jnp.arange(s_loc)
        local_mask = pos[None, :] < jnp.reshape(cache_len, (-1, 1))
        num, den, m = _local_partial_attn(q[:, 0] * scale, k_shard, v_shard, local_mask)
        # LSE merge across shards
        g_m = jax.lax.pmax(m, axes)
        corr = jnp.exp(m - g_m)
        num = jax.lax.psum(num * corr[..., None], axes)
        den = jax.lax.psum(den * corr, axes)
        out = num / jnp.maximum(den, 1e-30)[..., None]
        return out.reshape(b_loc, 1, hq, d).astype(q.dtype)

    in_specs = (
        P(b_ax, None, None, None),  # q: (b, 1, hq, d)
        P(b_ax, axes, None, None),  # k: seq interleaved across `axes`
        P(b_ax, axes, None, None),  # v
        P(b_ax),  # cache_len
    )
    out_specs = P(b_ax, None, None, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, cache_len)


def update_kv_cache(
    k_cache: jax.Array,  # (b, s_max, hkv, d)
    v_cache: jax.Array,
    k_new: jax.Array,  # (b, 1, hkv, d)
    v_new: jax.Array,
    pos: jax.Array,  # (b,) write positions
):
    """Scatter one new token into the ring cache at per-sequence positions.

    Handles quantized (fp8) caches: new KV is cast to the cache dtype (keys
    after RoPE are O(1), within e4m3 range — standard scale-free fp8 KV).
    """
    b = k_cache.shape[0]
    bidx = jnp.arange(b)
    k_cache = k_cache.at[bidx, pos].set(k_new[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, pos].set(v_new[:, 0].astype(v_cache.dtype))
    return k_cache, v_cache


def _dequant(kv: jax.Array) -> jax.Array:
    """fp8 caches are dequantized to bf16 at the attention boundary (on TPU
    the convert fuses into the attention kernel's tile loads)."""
    if kv.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2):
        return kv.astype(jnp.bfloat16)
    return kv
