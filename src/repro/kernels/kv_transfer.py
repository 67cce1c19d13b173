"""KV gather-write / scatter-read — Pallas TPU kernels (paper §6.1).

The paper's custom CUDA copy kernel collapses a block's 2L non-contiguous
fragments into ONE kernel launch; these are the TPU twins:

  * ``kv_gather_write``  — pack per-layer cache slots -> contiguous pool
    blocks (pool payload layout: (n_blocks, 2L, bt, hkv, hd), fragments
    interleaved [k0, v0, k1, v1, ...]; or, for a latent cache with one
    part a layer, (n_blocks, L, bt, width));
  * ``kv_scatter_read``  — pool blocks -> per-layer cache slots;
  * ``sparse_kv_gather`` — top-k token rows out of a token-major pool view
    (Exp #10: thousands of tiny pieces, one launch).

Dynamic slot/block indices arrive via scalar prefetch; each grid step's
BlockSpec index_map dereferences them — data movement at memory semantics,
no per-fragment request list (the RDMA sglist pathology this replaces).

Grid shape: ONE step per pool block. Each step moves a fused
(L, P, bt, *row) block of every layer's parts over the collapsed layer axis —
a single fat DMA per pool block instead of an (n_blocks, L) grid of tiny
(1, 1, bt, hkv, hd) copies, so grid/launch overhead is O(blocks), not
O(blocks * layers).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# gather write: cache slots -> pool blocks
# ---------------------------------------------------------------------------


def _gather_write_body(slot_ref, *refs):
    # each part's ref: (L, 1, bt, *row) — every layer of one cache slot;
    # o_ref: (1, L, P, bt, *row) — one fused pool block, the parts paired
    *part_refs, o_ref = refs
    for p, ref in enumerate(part_refs):
        o_ref[0, :, p] = ref[:, 0]


def kv_gather_write(
    k_cache: jax.Array,  # (L, T, *row), T = n_slots * bt
    v_cache: jax.Array | None,  # like k_cache; None: one part a layer
    slot_ids: jax.Array,  # (n_blocks,) int32 block-aligned slots
    block_tokens: int,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns pool blocks (n_blocks, P*L, bt, *row), P = 2 parts (k, v)
    or 1 part (a latent row of any width), fragments layer-major."""
    parts = (k_cache,) if v_cache is None else (k_cache, v_cache)
    L, T, *row = k_cache.shape
    n_blocks = slot_ids.shape[0]
    bt = block_tokens
    n_slots = T // bt
    P = len(parts)
    tail = (0,) * len(row)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(
                (L, 1, bt, *row),
                lambda bi, slot_ref: (0, slot_ref[bi], 0, *tail),
            )
            for _ in parts
        ],
        out_specs=pl.BlockSpec(
            (1, L, P, bt, *row), lambda bi, slot_ref: (bi, 0, 0, 0, *tail)
        ),
    )
    out = pl.pallas_call(
        _gather_write_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, L, P, bt, *row), k_cache.dtype),
        interpret=interpret,
    )(slot_ids.astype(jnp.int32), *(c.reshape(L, n_slots, bt, *row) for c in parts))
    # (n_blocks, L, P, ...) -> (n_blocks, P*L, ...) fragment-interleaved
    return out.reshape(n_blocks, P * L, bt, *row)


# ---------------------------------------------------------------------------
# scatter read: pool blocks -> cache slots
# ---------------------------------------------------------------------------


def _scatter_read_body(slot_ref, pool_ref, *refs):
    # pool_ref: (1, L, P, bt, *row); the P outputs: (L, 1, bt, *row) each.
    # The first P refs are the zeroed outputs' aliases, left in HBM untouched.
    outs = refs[len(refs) // 2:]
    for p, ref in enumerate(outs):
        ref[:, 0] = pool_ref[0, :, p]


def kv_scatter_read(
    pool_blocks: jax.Array,  # (n_blocks, P*L, bt, *row)
    slot_ids: jax.Array,  # (n_blocks,) destination block-aligned slots
    n_slots: int,
    *,
    n_parts: int = 2,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """Returns one cache per part, (k_cache, v_cache) or (latent,), each of
    shape (L, n_slots*bt, *row).

    Unwritten slots are zero: the outputs alias zero-filled buffers, and the
    grid writes only the mapped slots over them.
    """
    n_blocks, PL, bt, *row = pool_blocks.shape
    P = n_parts
    L = PL // P
    tail = (0,) * len(row)
    pool = pool_blocks.reshape(n_blocks, L, P, bt, *row)
    zeros = jnp.zeros((L, n_slots, bt, *row), pool_blocks.dtype)
    out_spec = pl.BlockSpec(
        (L, 1, bt, *row), lambda bi, slot_ref: (0, slot_ref[bi], 0, *tail)
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(
                (1, L, P, bt, *row),
                lambda bi, slot_ref: (bi, 0, 0, 0, *tail),
            ),
        ] + [pl.BlockSpec(memory_space=pl.ANY)] * P,
        out_specs=[out_spec] * P,
    )
    outs = pl.pallas_call(
        _scatter_read_body,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((L, n_slots, bt, *row), pool_blocks.dtype)] * P,
        # operand indices count the scalar-prefetch slot ids: 2.. = zeros
        input_output_aliases={2 + p: p for p in range(P)},
        interpret=interpret,
    )(slot_ids.astype(jnp.int32), pool, *(zeros,) * P)
    return tuple(o.reshape(L, n_slots * bt, *row) for o in outs)


# ---------------------------------------------------------------------------
# sparse gather: top-k token rows (one launch for thousands of pieces)
# ---------------------------------------------------------------------------


def _sparse_body(idx_ref, kv_ref, o_ref):
    o_ref[0] = kv_ref[0]


def sparse_kv_gather(
    kv: jax.Array,  # (N, hkv, hd) token-major
    token_ids: jax.Array,  # (n_sel,) int32
    *,
    interpret: bool = False,
) -> jax.Array:
    n, hkv, hd = kv.shape
    n_sel = token_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_sel,),
        in_specs=[
            pl.BlockSpec((1, hkv, hd), lambda i, idx_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, hd), lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _sparse_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_sel, hkv, hd), kv.dtype),
        interpret=interpret,
    )(token_ids.astype(jnp.int32), kv)
