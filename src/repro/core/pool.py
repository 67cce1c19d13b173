"""BelugaPool: the shared, interleaved KV block pool (the paper's §4 + O9).

One pool instance represents the rack-scale shared memory (8 TB behind the
CXL switch in the paper; the sharded host/HBM capacity tier on a TPU pod).
The pool is paged: fixed-size *blocks* of ``block_tokens`` tokens, each
holding every layer's K and V fragments for those tokens, packed contiguous.

Two backings:
  * ``numpy`` — the serving control plane (real allocator + real copies);
  * ``jax``   — device-side pool array used by the Pallas/XLA data path
                (gather/scatter reads feed attention directly).

Interleaving (O9): block b lives on shard ``b % n_shards``; the allocator
balances allocation across shards and exposes per-shard occupancy so the
benchmarks can show the skew/queueing effect of turning interleaving off.

Allocator design (control plane must be O(blocks touched), never O(pool)):
  * one persistent free stack per shard — ``allocate`` pops round-robin
    across shards (fullest-first order, as the seed allocator placed
    blocks) without ever walking the whole free set;
  * occupancy counters are maintained incrementally, so
    ``shard_occupancy()`` is O(n_shards) and ``free_blocks()`` is O(1);
  * per-block metadata (epoch / refcount / committed) lives in flat numpy
    arrays so retain/release/validate batch under ONE lock acquisition
    with vectorized index arithmetic.

Single-writer / multi-reader coherence (§5.1) is enforced with per-block
epochs — see ``repro.core.coherence``.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass

import numpy as np

from repro.configs.base import ModelConfig
from repro.core import diag
from repro.core.locks import make_lock


@dataclass(frozen=True)
class PoolLayout:
    """Byte layout of one pool block for a model config.

    A layer stores ``n_parts`` fragments a block: a key/value pair of
    ``n_kv_heads`` x ``head_dim`` rows (2), or one latent part of
    ``head_dim``-wide rows (1, with one "head"; see ``latent``).
    """

    block_tokens: int
    n_layers_kv: int  # attention layers
    n_kv_heads: int
    head_dim: int
    dtype_bytes: int = 2
    n_parts: int = 2

    @classmethod
    def latent(cls, block_tokens: int, n_layers: int, width: int) -> "PoolLayout":
        """One part a layer: a ``width``-wide latent row a token (MLA)."""
        return cls(block_tokens, n_layers, 1, width, n_parts=1)

    @property
    def fragment_bytes(self) -> int:
        """One (layer, part) fragment of a block (Qwen3-32B: 32 KiB)."""
        return self.block_tokens * self.n_kv_heads * self.head_dim * self.dtype_bytes

    @property
    def n_fragments(self) -> int:
        """Fragments per block: n_parts * n_layers (Qwen3-32B: 128)."""
        return self.n_parts * self.n_layers_kv

    @property
    def row_shape(self) -> tuple[int, ...]:
        """One token's row of a fragment in the device pool: (heads,
        head_dim) for a key/value pair, (width,) for a latent part."""
        if self.n_parts == 1:
            return (self.n_kv_heads * self.head_dim,)
        return (self.n_kv_heads, self.head_dim)

    @property
    def block_bytes(self) -> int:
        return self.n_fragments * self.fragment_bytes

    @property
    def token_bytes(self) -> int:
        return self.block_bytes // self.block_tokens

    @classmethod
    def for_model(cls, cfg: ModelConfig, block_tokens: int = 16) -> "PoolLayout":
        if cfg.is_mla:
            return cls.latent(block_tokens, cfg.n_layers, cfg.latent_width)
        return cls(
            block_tokens=block_tokens,
            n_layers_kv=max(1, len(cfg.attn_layer_ids())),
            n_kv_heads=max(1, cfg.n_kv_heads),
            head_dim=max(1, cfg.head_dim),
        )


class OutOfPoolMemory(RuntimeError):
    pass


class BelugaPool:
    """Block allocator + storage over interleaved shards."""

    def __init__(
        self,
        layout: PoolLayout,
        n_blocks: int,
        n_shards: int = 32,
        backing: str = "numpy",
        interleave: bool = True,
    ):
        assert n_blocks % n_shards == 0, (n_blocks, n_shards)
        self.layout = layout
        self.n_blocks = n_blocks
        self.n_shards = n_shards
        self.interleave = interleave
        self.backing = backing
        self._lock = make_lock("pool.BelugaPool._lock")
        # vectorized per-block metadata (re-homed into a named shared
        # segment by ``share_meta`` for cross-process metadata services)
        self.epochs = np.zeros(n_blocks, np.int64)
        self.refcounts = np.zeros(n_blocks, np.int32)
        self.committed = np.zeros(n_blocks, bool)
        self._meta_segment = None
        self._meta_spec: dict | None = None
        self._data_segment = None
        self._data_spec: dict | None = None
        # free structures: per-shard LIFO stacks (interleave) or one FIFO
        # queue (no interleave: fill shard 0 first, the §5.3 bottleneck)
        if interleave:
            self._free_by_shard: list[list[int]] = [
                list(range(s, n_blocks, n_shards)) for s in range(n_shards)
            ]
            self._free_fifo: deque[int] | None = None
        else:
            self._free_by_shard = []
            self._free_fifo = deque(range(n_blocks))
        # free-age stamps: ties between equally-full shards resolve toward
        # the shard whose oldest free block has been free longest — the
        # order the seed allocator's by-shard rebuild produced implicitly
        self._age = np.arange(n_blocks, dtype=np.int64)
        self._stamp = n_blocks
        self._n_free = n_blocks
        self._occ = [0] * n_shards  # allocated (non-free) blocks per shard
        self.alloc_count = 0
        if backing == "meta":
            # control-plane only (cluster sim at paper scale): allocator,
            # epochs and index run for real; payloads are not stored.
            self.data = None
        elif backing == "numpy":
            # (n_blocks, block_bytes) uint8 — fragment-addressable
            self.data = np.zeros((n_blocks, layout.block_bytes), np.uint8)
        elif backing == "jax":
            import jax.numpy as jnp

            # (n_blocks, n_parts*L, block_tokens, *row) device-side pool
            self.data = jnp.zeros(
                (n_blocks, layout.n_fragments, layout.block_tokens, *layout.row_shape),
                jnp.bfloat16,
            )
        else:
            raise ValueError(backing)

    # ------------------------------------------------------------------
    # Cross-process metadata export (paper: pool state IS shared memory)
    # ------------------------------------------------------------------
    def share_meta(self) -> dict:
        """Re-home epochs/refcounts/committed into a named shared segment.

        An out-of-process metadata service (``repro.core.procserver``)
        attaches the SAME arrays by name (``SharedPoolMeta``) and reads
        the truth the engines write — epoch validation and refcount
        checks are plain loads on the shared pool state, exactly the
        paper's trust model (the service owns no copy of anything).
        Idempotent; returns the attach spec (plain data, picklable).
        The pool keeps sole ownership of allocation/release — attachers
        never mutate.
        """
        if self._meta_spec is not None:
            return self._meta_spec
        from repro.core.shm import create_segment

        n = self.n_blocks
        seg = create_segment(13 * n)  # 8 B epoch + 4 B refcount + 1 B flag
        eps = np.frombuffer(seg.buf, np.int64, n, 0)
        rcs = np.frombuffer(seg.buf, np.int32, n, 8 * n)
        com = np.frombuffer(seg.buf, np.bool_, n, 12 * n)
        with self._lock:
            eps[:] = self.epochs
            rcs[:] = self.refcounts
            com[:] = self.committed
            self.epochs, self.refcounts, self.committed = eps, rcs, com
        self._meta_segment = seg
        self._meta_spec = {
            "shm_name": seg.name,
            "n_blocks": n,
            "block_tokens": self.layout.block_tokens,
        }
        import atexit

        atexit.register(self.unshare_meta)  # no leaked /dev/shm entries
        return self._meta_spec

    def unshare_meta(self) -> None:
        """Copy metadata back to private arrays and unlink the segment.

        Safe to call repeatedly / when never shared; the pool stays fully
        functional afterwards (values preserved)."""
        seg = self._meta_segment
        if seg is None:
            return
        from repro.core.shm import close_segment

        with self._lock:
            self.epochs = np.array(self.epochs, np.int64)
            self.refcounts = np.array(self.refcounts, np.int32)
            self.committed = np.array(self.committed, bool)
        self._meta_segment = None
        self._meta_spec = None
        close_segment(seg, unlink=True)
        import atexit

        try:
            atexit.unregister(self.unshare_meta)
        except Exception:  # noqa: BLE001
            diag.note("pool.unshare_meta.unregister_failed")

    # ------------------------------------------------------------------
    # Cross-process DATA export (the paper's headline: the block payloads
    # themselves are one shared pool every participant loads/stores)
    # ------------------------------------------------------------------
    def share_data(self) -> dict:
        """Re-home the block payload array into a named shared segment.

        Engine worker processes (``repro.serving.engineproc``) attach the
        SAME ``(n_blocks, block_bytes)`` array by name
        (``repro.core.shmpool.SharedPoolData``) and scatter/gather KV
        blocks against it directly — zero payload copies through the
        parent, the modeled CXL load/store path crossing a real OS
        process boundary.  Allocation stays with this pool (served over
        a ring); writers own freshly-allocated blocks exclusively until
        publish, so payload stores need no cross-process lock (§5.1
        single-writer).  Implies ``share_meta`` (epoch validation is a
        plain load on the shared metadata).  Idempotent; returns the
        attach spec (plain data, picklable).
        """
        if self._data_spec is not None:
            return self._data_spec
        if self.backing != "numpy":
            raise ValueError(
                f"share_data requires backing='numpy', not {self.backing!r}"
            )
        meta = self.share_meta()
        from repro.core.shm import create_segment

        lay = self.layout
        seg = create_segment(self.n_blocks * lay.block_bytes)
        view = np.frombuffer(seg.buf, np.uint8).reshape(
            self.n_blocks, lay.block_bytes
        )
        with self._lock:
            view[:] = self.data
            self.data = view
        self._data_segment = seg
        self._data_spec = {
            "data_shm_name": seg.name,
            "meta": meta,
            "n_blocks": self.n_blocks,
            "block_tokens": lay.block_tokens,
            "n_layers_kv": lay.n_layers_kv,
            "n_kv_heads": lay.n_kv_heads,
            "head_dim": lay.head_dim,
            "dtype_bytes": lay.dtype_bytes,
        }
        import atexit

        atexit.register(self.unshare_data)  # no leaked /dev/shm entries
        return self._data_spec

    def unshare_data(self) -> None:
        """Copy payloads back to a private array and unlink the segment.

        Safe to call repeatedly / when never shared; leaves ``share_meta``
        as-is (its own unshare handles it)."""
        seg = self._data_segment
        if seg is None:
            return
        from repro.core.shm import close_segment

        with self._lock:
            self.data = np.array(self.data, np.uint8)
        self._data_segment = None
        self._data_spec = None
        close_segment(seg, unlink=True)
        import atexit

        try:
            atexit.unregister(self.unshare_data)
        except Exception:  # noqa: BLE001
            diag.note("pool.unshare_data.unregister_failed")

    # ------------------------------------------------------------------
    def shard_of(self, block_id: int) -> int:
        if self.interleave:
            return block_id % self.n_shards
        # no interleaving: fill shard 0 first (the paper's §5.3 bottleneck)
        return block_id // (self.n_blocks // self.n_shards)

    def free_blocks(self) -> int:
        with self._lock:
            return self._n_free

    def shard_occupancy(self) -> list[int]:
        with self._lock:
            return list(self._occ)

    # ------------------------------------------------------------------
    def allocate(self, n: int) -> list[int]:
        """Allocate n blocks, round-robin across shards when interleaving."""
        with self._lock:
            if self._n_free < n:
                raise OutOfPoolMemory(f"need {n}, have {self._n_free}")
            out: list[int] = []
            if self.interleave:
                stacks = self._free_by_shard
                # fullest shards first, then round-robin over that order —
                # the same placement policy as the seed allocator, but over
                # persistent stacks instead of a per-call full-list rebuild
                age = self._age
                order = sorted(
                    (s for s in range(self.n_shards) if stacks[s]),
                    key=lambda s: (-len(stacks[s]), age[stacks[s][0]]),
                )
                i = 0
                while len(out) < n:
                    s = order[i % len(order)]
                    if stacks[s]:
                        out.append(stacks[s].pop())
                        self._occ[s] += 1
                    i += 1
                    if i > 4 * self.n_shards + n * 2:  # degenerate fallback
                        # seed parity: sweep the remaining free blocks in
                        # by-shard build order (oldest free block first),
                        # oldest-to-newest within each shard
                        rem = sorted(
                            (s for s in range(self.n_shards) if stacks[s]),
                            key=lambda s: age[stacks[s][0]],
                        )
                        for s in rem:
                            k = min(len(stacks[s]), n - len(out))
                            if k <= 0:
                                break
                            out.extend(stacks[s][:k])
                            del stacks[s][:k]
                            self._occ[s] += k
                        break
            else:
                fifo = self._free_fifo
                per = self.n_blocks // self.n_shards
                for _ in range(n):
                    b = fifo.popleft()
                    out.append(b)
                    self._occ[b // per] += 1
            self._n_free -= n
            ids = np.asarray(out, np.intp)
            self.refcounts[ids] = 1
            self.committed[ids] = False
            self.alloc_count += n
            return out

    def retain(self, block_ids: list[int]) -> None:
        if not len(block_ids):
            return
        ids = np.asarray(block_ids, np.intp)
        with self._lock:
            assert (self.refcounts[ids] > 0).all(), "retain of free block"
            np.add.at(self.refcounts, ids, 1)

    def release(self, block_ids: list[int]) -> None:
        if not len(block_ids):
            return
        ids = np.asarray(block_ids, np.intp)
        with self._lock:
            np.subtract.at(self.refcounts, ids, 1)
            assert (self.refcounts[ids] >= 0).all(), "double free"
            zero = self.refcounts[ids] == 0
            if not zero.any():
                return
            # freed blocks re-enter the free structures in CALLER order
            # (dedup'd), preserving the seed allocator's reuse order
            seen: set[int] = set()
            freed = [
                b for b, z in zip(ids.tolist(), zero.tolist())
                if z and not (b in seen or seen.add(b))
            ]
            farr = np.asarray(freed, np.intp)
            self.committed[farr] = False
            self.epochs[farr] += 1  # invalidate readers holding stale ids
            if self.interleave:
                for b in freed:
                    s = b % self.n_shards
                    self._free_by_shard[s].append(b)
                    self._occ[s] -= 1
                    self._age[b] = self._stamp
                    self._stamp += 1
            else:
                per = self.n_blocks // self.n_shards
                for b in freed:
                    self._free_fifo.append(b)
                    self._occ[b // per] -= 1
            self._n_free += len(freed)

    # ------------------------------------------------------------------
    # Data plane (numpy backing): fragment reads/writes
    # ------------------------------------------------------------------
    def write_block(self, block_id: int, payload: np.ndarray | None) -> int:
        """Write a full block; returns the publish epoch (see coherence)."""
        if self.data is not None and payload is not None:
            assert payload.nbytes == self.layout.block_bytes
            self.data[block_id] = payload.reshape(-1).view(np.uint8)
        with self._lock:
            self.epochs[block_id] += 1
            self.committed[block_id] = True
            return int(self.epochs[block_id])

    def write_blocks(
        self, block_ids: list[int], payloads: np.ndarray | None = None
    ) -> list[int]:
        """Batch write + publish: one fancy-indexed copy, one epoch bump.

        ``payloads``: (n, block_bytes)-viewable array, or None when the
        payload was staged elsewhere (meta backing / device-side writes).
        Returns the publish epochs.
        """
        ids = np.asarray(block_ids, np.intp)
        if self.data is not None and payloads is not None:
            assert payloads.nbytes == len(block_ids) * self.layout.block_bytes
            self.data[ids] = payloads.reshape(len(block_ids), -1).view(np.uint8)
        with self._lock:
            self.epochs[ids] += 1
            self.committed[ids] = True
            return self.epochs[ids].tolist()

    def read_block(self, block_id: int) -> tuple[np.ndarray, int]:
        with self._lock:
            e = int(self.epochs[block_id])
        if self.data is None:
            return np.zeros(self.layout.block_bytes, np.uint8), e
        return self.data[block_id].copy(), e

    def read_blocks(
        self, block_ids, out: np.ndarray | None = None
    ) -> tuple[np.ndarray | None, np.ndarray]:
        """Batch read: one batched copy + one epoch snapshot.

        Returns (payloads (n, block_bytes) or None for meta backing,
        epochs-at-read (n,)). The epoch snapshot is taken BEFORE the copy,
        mirroring the per-block read protocol (§5.1): a caller comparing
        the snapshot against its expected epochs detects concurrent
        recycling the same way the scalar path did.

        ``out``: optional (n, block_bytes) uint8 destination. Reading into
        a persistent buffer (the serving steady state: pool -> fixed HBM
        slots) skips the dominant cost of a fresh multi-hundred-MB
        allocation — per-row C memcpy into warm pages.
        """
        ids = np.asarray(block_ids, np.intp)
        with self._lock:
            eps = self.epochs[ids].copy()
        if self.data is None:
            return None, eps
        if out is None:
            return self.data[ids], eps
        assert out.shape == (len(ids), self.layout.block_bytes)
        data = self.data
        for j, b in enumerate(ids):
            out[j] = data[b]
        return out, eps

    def read_fragments(self, block_id: int, frag_ids: list[int]) -> np.ndarray:
        fb = self.layout.fragment_bytes
        block = self.data[block_id]
        return block.reshape(self.layout.n_fragments, fb)[
            np.asarray(frag_ids, np.intp)
        ]

    def validate_epoch(self, block_id: int, epoch: int) -> bool:
        with self._lock:
            return bool(self.committed[block_id]) and int(
                self.epochs[block_id]
            ) == epoch

    def validate_epochs(self, block_ids, epochs) -> np.ndarray:
        """Vectorized committed+epoch check; one lock, one compare."""
        ids = np.asarray(block_ids, np.intp)
        exp = np.asarray(epochs)
        with self._lock:
            return self.committed[ids] & (self.epochs[ids] == exp)
