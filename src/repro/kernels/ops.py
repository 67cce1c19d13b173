"""Jit'd public wrappers for the Pallas kernels.

``mode`` is always named by the caller; nothing falls back in silence:
  * "pallas"    — the compiled Pallas kernel; raises off a TPU;
  * "interpret" — the Pallas kernel body run by the interpreter (any
                  backend: correctness on CPU, never speed);
  * "jnp"       — the pure-jnp oracle (ref.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import kv_transfer as _kv
from repro.kernels import paged_attention as _pa
from repro.kernels import ref as _ref

MODES = ("pallas", "interpret", "jnp")


def _use_pallas(mode: str) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    if mode == "pallas":
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                f"mode='pallas' needs a TPU, backend is {jax.default_backend()!r};"
                " ask for mode='interpret' or mode='jnp' by name"
            )
        return True, False
    if mode == "interpret":
        return True, True
    if mode == "jnp":
        return False, False
    raise ValueError(f"unknown kernel mode {mode!r}; expected one of {MODES}")


@functools.partial(jax.jit, static_argnames=("causal", "mode", "block_q", "block_kv"))
def flash_attention(q, k, v, *, causal=True, mode="pallas", block_q=256, block_kv=512):
    use, interp = _use_pallas(mode)
    if use:
        return _fa.flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
            interpret=interp,
        )
    return _ref.flash_attention_ref(q, k, v, causal=causal)


@functools.partial(jax.jit, static_argnames=("mode",))
def paged_attention(q, kv_pool, block_table, context_lens, *, mode="pallas"):
    use, interp = _use_pallas(mode)
    if use:
        return _pa.paged_attention(
            q, kv_pool, block_table, context_lens, interpret=interp
        )
    return _ref.paged_attention_ref(q, kv_pool, block_table, context_lens)


@functools.partial(jax.jit, static_argnames=("block_tokens", "mode"))
def kv_gather_write(k_cache, v_cache, slot_ids, block_tokens, *, mode="pallas"):
    """Cache slots -> pool blocks; ``v_cache=None``: one part a layer."""
    use, interp = _use_pallas(mode)
    if use:
        return _kv.kv_gather_write(
            k_cache, v_cache, slot_ids, block_tokens, interpret=interp
        )
    return _ref.kv_gather_write_ref(k_cache, v_cache, slot_ids, block_tokens)


@functools.partial(jax.jit, static_argnames=("n_slots", "n_parts", "mode"))
def kv_scatter_read(pool_blocks, slot_ids, n_slots, *, n_parts=2, mode="pallas"):
    """Pool blocks -> one cache per part: (k, v), or (latent,) with n_parts=1."""
    use, interp = _use_pallas(mode)
    if use:
        return _kv.kv_scatter_read(
            pool_blocks, slot_ids, n_slots, n_parts=n_parts, interpret=interp
        )
    bt = pool_blocks.shape[2]
    L = pool_blocks.shape[1] // n_parts
    shape = (L, n_slots * bt, *pool_blocks.shape[3:])
    zeros = tuple(jnp.zeros(shape, pool_blocks.dtype) for _ in range(n_parts))
    return _ref.kv_scatter_read_ref(pool_blocks, slot_ids, zeros, bt)


@functools.partial(jax.jit, static_argnames=("mode",))
def sparse_kv_gather(kv, token_ids, *, mode="pallas"):
    use, interp = _use_pallas(mode)
    if use:
        return _kv.sparse_kv_gather(kv, token_ids, interpret=interp)
    return _ref.sparse_kv_gather_ref(kv, token_ids)


@functools.partial(jax.jit, static_argnames=("nh_tile", "mode"))
def ssd_chunk(x, a_log, b_mat, c_mat, *, nh_tile=8, mode="pallas"):
    """Intra-chunk SSD + chunk states; (nb, Lc, nh, hp) tiles."""
    use, interp = _use_pallas(mode)
    if use:
        from repro.kernels import ssd_chunk as _ssd

        return _ssd.ssd_chunk(
            x, a_log, b_mat, c_mat, nh_tile=nh_tile, interpret=interp
        )
    ys, ss = jax.vmap(_ref.ssd_chunk_ref)(x, a_log, b_mat, c_mat)
    return ys, ss
