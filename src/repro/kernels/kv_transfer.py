"""KV gather-write / scatter-read — Pallas TPU kernels (paper §6.1).

The paper's custom CUDA copy kernel collapses a block's 2L non-contiguous
fragments into ONE kernel launch; these are the TPU twins:

  * ``kv_gather_write``  — pack per-layer cache slots -> contiguous pool
    blocks (pool payload layout: (n_blocks, 2L, bt, hkv, hd), fragments
    interleaved [k0, v0, k1, v1, ...]);
  * ``kv_scatter_read``  — pool blocks -> per-layer cache slots;
  * ``sparse_kv_gather`` — top-k token rows out of a token-major pool view
    (Exp #10: thousands of tiny pieces, one launch).

Dynamic slot/block indices arrive via scalar prefetch; each grid step's
BlockSpec index_map dereferences them — data movement at memory semantics,
no per-fragment request list (the RDMA sglist pathology this replaces).

Grid shape: ONE step per pool block. Each step moves a fused
(L, 2, bt, hkv, hd) fragment-pair block over the collapsed layer axis —
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


def _gather_write_body(slot_ref, k_ref, v_ref, o_ref):
    # k_ref/v_ref: (L, 1, bt, hkv, hd) — every layer of one cache slot;
    # o_ref: (1, L, 2, bt, hkv, hd) — one fused pool block, (k, v) paired
    o_ref[0, :, 0] = k_ref[:, 0]
    o_ref[0, :, 1] = v_ref[:, 0]


def kv_gather_write(
    k_cache: jax.Array,  # (L, T, hkv, hd), T = n_slots * bt
    v_cache: jax.Array,
    slot_ids: jax.Array,  # (n_blocks,) int32 block-aligned slots
    block_tokens: int,
    *,
    interpret: bool = False,
) -> jax.Array:
    L, T, hkv, hd = k_cache.shape
    n_blocks = slot_ids.shape[0]
    bt = block_tokens
    n_slots = T // bt
    kc = k_cache.reshape(L, n_slots, bt, hkv, hd)
    vc = v_cache.reshape(L, n_slots, bt, hkv, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(
                (L, 1, bt, hkv, hd),
                lambda bi, slot_ref: (0, slot_ref[bi], 0, 0, 0),
            ),
            pl.BlockSpec(
                (L, 1, bt, hkv, hd),
                lambda bi, slot_ref: (0, slot_ref[bi], 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, L, 2, bt, hkv, hd), lambda bi, slot_ref: (bi, 0, 0, 0, 0, 0)
        ),
    )
    out = pl.pallas_call(
        _gather_write_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, L, 2, bt, hkv, hd), k_cache.dtype),
        interpret=interpret,
    )(slot_ids.astype(jnp.int32), kc, vc)
    # (n_blocks, L, 2, ...) -> (n_blocks, 2L, ...) fragment-interleaved
    return out.reshape(n_blocks, 2 * L, bt, hkv, hd)


# ---------------------------------------------------------------------------
# scatter read: pool blocks -> cache slots
# ---------------------------------------------------------------------------


def _scatter_read_body(slot_ref, pool_ref, k0_ref, v0_ref, k_ref, v_ref):
    # pool_ref: (1, L, 2, bt, hkv, hd); k_ref/v_ref: (L, 1, bt, hkv, hd).
    # k0_ref/v0_ref are the zeroed outputs' aliases, left in HBM untouched.
    del k0_ref, v0_ref
    k_ref[:, 0] = pool_ref[0, :, 0]
    v_ref[:, 0] = pool_ref[0, :, 1]


def kv_scatter_read(
    pool_blocks: jax.Array,  # (n_blocks, 2L, bt, hkv, hd)
    slot_ids: jax.Array,  # (n_blocks,) destination block-aligned slots
    n_slots: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (k_cache, v_cache) of shape (L, n_slots*bt, hkv, hd).

    Unwritten slots are zero: the outputs alias zero-filled buffers, and the
    grid writes only the mapped slots over them.
    """
    n_blocks, twoL, bt, hkv, hd = pool_blocks.shape
    L = twoL // 2
    pool = pool_blocks.reshape(n_blocks, L, 2, bt, hkv, hd)
    zeros = jnp.zeros((L, n_slots, bt, hkv, hd), pool_blocks.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(
                (1, L, 2, bt, hkv, hd),
                lambda bi, slot_ref: (bi, 0, 0, 0, 0, 0),
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(
                (L, 1, bt, hkv, hd),
                lambda bi, slot_ref: (0, slot_ref[bi], 0, 0, 0),
            ),
            pl.BlockSpec(
                (L, 1, bt, hkv, hd),
                lambda bi, slot_ref: (0, slot_ref[bi], 0, 0, 0),
            ),
        ],
    )
    k, v = pl.pallas_call(
        _scatter_read_body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((L, n_slots, bt, hkv, hd), pool_blocks.dtype),
            jax.ShapeDtypeStruct((L, n_slots, bt, hkv, hd), pool_blocks.dtype),
        ],
        # operand indices count the scalar-prefetch slot ids: 2, 3 = zeros
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
    )(slot_ids.astype(jnp.int32), pool, zeros, zeros)
    return (
        k.reshape(L, n_slots * bt, hkv, hd),
        v.reshape(L, n_slots * bt, hkv, hd),
    )


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
