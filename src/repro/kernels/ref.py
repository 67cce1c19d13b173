"""Pure-jnp oracles for every Pallas kernel (the numerics ground truth).

Each ``*_ref`` mirrors its kernel's exact signature and is used by
``tests/test_kernels.py`` for allclose sweeps over shapes/dtypes, and by
``ops.py`` when a caller asks for ``mode="jnp"``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# flash_attention: causal GQA attention (prefill / train)
# ---------------------------------------------------------------------------


def flash_attention_ref(
    q: jax.Array,  # (b, sq, hq, d)
    k: jax.Array,  # (b, skv, hkv, d)
    v: jax.Array,
    causal: bool = True,
    q_offset: int = 0,
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, hkv, g, d)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        qpos = q_offset + jnp.arange(sq)
        kpos = jnp.arange(skv)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged_attention: decode attention through a block table (Beluga pool read)
# ---------------------------------------------------------------------------


def paged_attention_ref(
    q: jax.Array,  # (b, hq, d)
    kv_pool: jax.Array,  # (n_blocks, 2, bt, hkv, d)  [k=0, v=1]
    block_table: jax.Array,  # (b, max_blocks) int32, -1 padded
    context_lens: jax.Array,  # (b,) int32
) -> jax.Array:
    b, hq, d = q.shape
    n_blocks, _, bt, hkv, _ = kv_pool.shape
    max_blocks = block_table.shape[1]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)

    tbl = jnp.maximum(block_table, 0)  # (b, mb)
    k = kv_pool[tbl, 0]  # (b, mb, bt, hkv, d)
    v = kv_pool[tbl, 1]
    k = k.reshape(b, max_blocks * bt, hkv, d)
    v = v.reshape(b, max_blocks * bt, hkv, d)
    pos = jnp.arange(max_blocks * bt)
    valid = pos[None, :] < context_lens[:, None]

    qg = (q * scale).reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32), k.astype(jnp.float32))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# kv_gather_write: pack fragmented per-layer KV slots into pool blocks
# ---------------------------------------------------------------------------


def kv_gather_write_ref(
    k_cache: jax.Array,  # (L, T, *row) dense per-layer cache
    v_cache: jax.Array | None,  # (L, T, *row); None: one part a layer
    slot_ids: jax.Array,  # (n_blocks,) int32: block-aligned slot index
    block_tokens: int,
) -> jax.Array:
    """Returns pool payload (n_blocks, P*L, block_tokens, *row)."""
    parts = (k_cache,) if v_cache is None else (k_cache, v_cache)
    L = k_cache.shape[0]

    def one(slot):
        start = slot * block_tokens
        frags = [jax.lax.dynamic_slice_in_dim(c, start, block_tokens, 1) for c in parts]
        # interleave each layer's parts: [k0, v0, k1, v1, ...]
        kv = jnp.stack(frags, axis=1)  # (L, P, bt, *row)
        return kv.reshape(len(parts) * L, block_tokens, *frags[0].shape[2:])

    return jax.vmap(one)(slot_ids)


def kv_scatter_read_ref(
    pool_blocks: jax.Array,  # (n_blocks, P*L, bt, *row)
    slot_ids: jax.Array,  # (n_blocks,) destination slots
    caches: tuple[jax.Array, ...],  # P caches (L, T, *row) to scatter into
    block_tokens: int,
) -> tuple[jax.Array, ...]:
    n_blocks, PL = pool_blocks.shape[0], pool_blocks.shape[1]
    P = len(caches)
    kv = pool_blocks.reshape(n_blocks, PL // P, P, block_tokens, *pool_blocks.shape[3:])

    def body(carry, i):
        start = slot_ids[i] * block_tokens
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(c, kv[i, :, p].astype(c.dtype), start, 1)
            for p, c in enumerate(carry)
        ), None

    caches, _ = jax.lax.scan(body, tuple(caches), jnp.arange(n_blocks))
    return caches


# ---------------------------------------------------------------------------
# sparse_kv_gather: top-k token gather (Exp #10 sparse reads)
# ---------------------------------------------------------------------------


def sparse_kv_gather_ref(
    kv: jax.Array,  # (N, hkv, hd) token-major pool view
    token_ids: jax.Array,  # (n_sel,) int32
) -> jax.Array:
    return jnp.take(kv, token_ids, axis=0)


# ---------------------------------------------------------------------------
# ssd_chunk: Mamba-2 intra-chunk SSD (one chunk, quadratic within chunk)
# ---------------------------------------------------------------------------


def ssd_chunk_ref(
    x: jax.Array,  # (L, nh, hp)  dt-scaled inputs, one chunk
    a_log: jax.Array,  # (L, nh) per-step log decay
    b_mat: jax.Array,  # (L, nh, n)
    c_mat: jax.Array,  # (L, nh, n)
) -> tuple[jax.Array, jax.Array]:
    """Returns (y_intra (L, nh, hp), chunk_state (nh, n, hp))."""
    Lc = x.shape[0]
    cum = jnp.cumsum(a_log.astype(jnp.float32), axis=0)  # (L, nh)
    seg = cum[:, None, :] - cum[None, :, :]  # (L, L, nh)
    li = jnp.arange(Lc)
    causal = li[:, None] >= li[None, :]
    decay = jnp.where(causal[..., None], jnp.exp(seg), 0.0)
    scores = jnp.einsum(
        "lhn,mhn->lmh", c_mat.astype(jnp.float32), b_mat.astype(jnp.float32)
    )
    y = jnp.einsum("lmh,lmh,mhp->lhp", scores, decay, x.astype(jnp.float32))
    decay_to_end = jnp.exp(cum[-1:, :] - cum)  # (L, nh)
    state = jnp.einsum(
        "lhn,lh,lhp->hnp", b_mat.astype(jnp.float32), decay_to_end,
        x.astype(jnp.float32),
    )
    return y, state
