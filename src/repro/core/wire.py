"""Binary wire protocol for the metadata plane (paper §6, Exp #11).

The centralized ``GlobalIndex`` is reached over the CXL-RPC shared-memory
ring (``repro.core.rpc``); this module defines what actually travels in a
slot: a compact variable-length binary codec for the index ops every
request hits, so ONE ring round-trip carries a whole request's key chain
instead of one RPC per key.

Message layout (little-endian, keys are fixed 16-byte blake2b digests):

    request  := op:u8  body
    MATCH    := n:u32  keys[n*16]
    PUBLISH  := n:u32  n_tokens:i32  keys[n*16]  block_ids[n*i64]  epochs[n*i64]
    LOOKUP   := n:u32  keys[n*16]
    FILTER   := n:u32  keys[n*16]          (writeback: lookup+validate fused)
    EVICT    := n:u32                      (evict up to n LRU blocks)
    BATCH    := k:u32  k * (len:u32 request)
    OWNERS   := n:u32  block_ids[n*i64]    (migrator pre-copy snapshot)
    REMAP    := n:u32  keys[n*16]  old_ids[n*i64]  old_epochs[n*i64]
                       new_ids[n*i64]  new_epochs[n*i64]
    EVICT_BLOCKS := n:u32  block_ids[n*i64]
    STATS    := n:u32 (ignored)            (occupancy/hit counters probe)

    responses:
    MATCH    -> n_ok:u32  block_ids[n_ok*i64]  epochs[n_ok*i64]
    PUBLISH  -> n:u32
    LOOKUP   -> n:u32  block_ids[n*i64]  epochs[n*i64]  n_tokens[n*i32]
                (block_id == -1 marks a missing key)
    FILTER   -> m:u32  positions[m*u32]
    EVICT    -> m:u32  freed_block_ids[m*i64]
    BATCH    -> k:u32  k * (len:u32 response)
    OWNERS   -> m:u32  keys[m*16]  block_ids[m*i64]  epochs[m*i64]
    REMAP    -> n:u32  ok[n*u8]
    EVICT_BLOCKS -> m:u32  freed_block_ids[m*i64]
    STATS    -> entries:u64  hits:u64  misses:u64

OWNERS / REMAP / EVICT_BLOCKS carry the tier-migration control plane, so
the ``MigrationEngine`` no longer has to be co-located with the index: its
metadata ops (pre-copy snapshot, compare-and-swap re-point, spill
eviction) travel the same ring as everything else, while the payload
copies stay on the shared pool.

``handle_request`` is the server-side dispatcher (wrap it with
``make_index_handler`` and hand it to ``CxlRpcServer``); ``RpcIndexClient``
is the engine-side proxy exposing the same API surface the
``KVCacheManager`` uses in-process (``keys_for`` hashes locally — it is
pure computation — and only the 16-byte keys cross the ring). Chains
longer than one slot are transparently split at the op level.
``ShardedRpcIndexClient`` is the multi-ring front: keys partition by
digest (``repro.core.index.shard_of_key``) across S rings, each serving
one ``GlobalIndex`` shard, and every fan-out POSTS to all shards before
collecting any reply — the S sub-requests are outstanding in parallel.
"""

from __future__ import annotations

import struct
import time

import numpy as np

from repro.core.index import (
    IndexEntry,
    PrefixHasher,
    evict_blocks_sharded,
    evict_lru_pressure,
    partition_keys,
    shard_of_key,
)
from repro.core import diag
from repro.core.pool import OutOfPoolMemory
from repro.core.rpc import (
    CTRL_BUSY_NS,
    CTRL_SERVED,
    RetryPolicy,
    RpcError,
    ServiceDiedError,
)

KEY_BYTES = 16

OP_MATCH = 1
OP_PUBLISH = 2
OP_LOOKUP = 3
OP_FILTER = 4
OP_EVICT = 5
OP_BATCH = 6
OP_OWNERS = 7
OP_REMAP = 8
OP_EVICT_BLOCKS = 9
OP_STATS = 10
OP_SNAPSHOT = 11
OP_RESTORE = 12
# pool allocator plane (engine workers -> pool-owning parent); these ops
# are served by a SEPARATE dispatcher (``make_pool_handler``) on its own
# ring — allocator state has exactly one owner, the index service never
# sees them
OP_POOL_ALLOC = 13
OP_POOL_RETAIN = 14
OP_POOL_RELEASE = 15
OP_POOL_FREE = 16
# journal proxy (engine workers -> pool-owning parent, selfheal mode):
# worker-side index clients must journal their confirmed mutations like
# every other client, but the ShardJournal segments are owned by the
# parent — these ops carry the append over the SAME allocator ring the
# worker already holds, tagged with the target shard
OP_JRNL_PUBLISH = 17
OP_JRNL_RETRACT = 18
OP_JRNL_REMAP = 19
# seed hit/miss counters into a freshly restarted shard (warm-snapshot
# restore path; served by the index dispatcher)
OP_SEED_STATS = 20
# tiered-pool extensions of the pool allocator plane (engine workers ->
# tiered-pool-owning parent): keyed allocation routes through the
# ghost-LRU admission filter, TOUCH ships the fetch-path demand signal
# so hotness/promotion state stays with the single pool owner
OP_POOL_ALLOC_KEYS = 21
OP_POOL_TOUCH = 22

_HDR = struct.Struct("<BI")  # op, count
_U32 = struct.Struct("<I")
_PUB_HDR = struct.Struct("<BIi")  # op, count, n_tokens
# entries, hits, misses + the service-side timer (ops served, busy-ns)
# measured IN the serving process — exp11 capacity is read from here
# instead of being inferred from an in-process replica
_STATS = struct.Struct("<QQQQQ")


class WireError(ValueError):
    pass


# ---------------------------------------------------------------------------
# encode (client side)
# ---------------------------------------------------------------------------
def _join_keys(keys) -> bytes:
    blob = b"".join(keys)
    if len(blob) != KEY_BYTES * len(keys):
        raise WireError("keys must be 16-byte digests")
    return blob


def encode_match(keys) -> bytes:
    return _HDR.pack(OP_MATCH, len(keys)) + _join_keys(keys)


def encode_publish(keys, block_ids, epochs, n_tokens: int) -> bytes:
    n = len(keys)
    if not (n == len(block_ids) == len(epochs)):
        raise WireError("publish arrays disagree on length")
    return (
        _PUB_HDR.pack(OP_PUBLISH, n, n_tokens)
        + _join_keys(keys)
        + np.asarray(block_ids, np.int64).tobytes()
        + np.asarray(epochs, np.int64).tobytes()
    )


def encode_lookup(keys) -> bytes:
    return _HDR.pack(OP_LOOKUP, len(keys)) + _join_keys(keys)


def encode_filter(keys) -> bytes:
    return _HDR.pack(OP_FILTER, len(keys)) + _join_keys(keys)


def encode_evict(n: int) -> bytes:
    return _HDR.pack(OP_EVICT, n)


def encode_batch(requests: list[bytes]) -> bytes:
    return _HDR.pack(OP_BATCH, len(requests)) + b"".join(
        _U32.pack(len(r)) + r for r in requests
    )


def encode_owners(block_ids) -> bytes:
    return _HDR.pack(OP_OWNERS, len(block_ids)) + np.asarray(
        block_ids, np.int64
    ).tobytes()


def encode_remap(keys, old_ids, old_epochs, new_ids, new_epochs) -> bytes:
    n = len(keys)
    if not (n == len(old_ids) == len(old_epochs) == len(new_ids) == len(new_epochs)):
        raise WireError("remap arrays disagree on length")
    return (
        _HDR.pack(OP_REMAP, n)
        + _join_keys(keys)
        + np.asarray(old_ids, np.int64).tobytes()
        + np.asarray(old_epochs, np.int64).tobytes()
        + np.asarray(new_ids, np.int64).tobytes()
        + np.asarray(new_epochs, np.int64).tobytes()
    )


def encode_evict_blocks(block_ids) -> bytes:
    return _HDR.pack(OP_EVICT_BLOCKS, len(block_ids)) + np.asarray(
        block_ids, np.int64
    ).tobytes()


def encode_stats() -> bytes:
    """Occupancy + hit/miss counters probe.  Serves two masters: the
    cluster's summary stats when the index lives in another process, and
    the per-shard occupancy signal of ``evict_lru_pressure``."""
    return _HDR.pack(OP_STATS, 0)


def encode_snapshot(start: int, max_items: int) -> bytes:
    """Page ``max_items`` index entries starting ``start`` rows in (LRU
    order) — the rebuild-verification op of the self-healing plane."""
    return _HDR.pack(OP_SNAPSHOT, max_items) + _U32.pack(start)


_SEED_STATS = struct.Struct("<QQ")


def encode_seed_stats(hits: int, misses: int) -> bytes:
    return _HDR.pack(OP_SEED_STATS, 0) + _SEED_STATS.pack(hits, misses)


def encode_restore(keys, block_ids, epochs, n_tokens) -> bytes:
    n = len(keys)
    if not (n == len(block_ids) == len(epochs) == len(n_tokens)):
        raise WireError("restore arrays disagree on length")
    return (
        _HDR.pack(OP_RESTORE, n)
        + _join_keys(keys)
        + np.asarray(block_ids, np.int64).tobytes()
        + np.asarray(epochs, np.int64).tobytes()
        + np.asarray(n_tokens, np.int32).tobytes()
    )


# ---------------------------------------------------------------------------
# decode helpers
# ---------------------------------------------------------------------------
def _need(buf: bytes, end: int) -> None:
    if len(buf) < end:
        raise WireError(f"truncated message: need {end} B, have {len(buf)} B")


def _split_keys(buf: bytes, off: int, n: int) -> tuple[list[bytes], int]:
    end = off + n * KEY_BYTES
    _need(buf, end)
    keys = [buf[i : i + KEY_BYTES] for i in range(off, end, KEY_BYTES)]
    return keys, end


def _split_i64(buf: bytes, off: int, n: int) -> tuple[np.ndarray, int]:
    end = off + 8 * n
    _need(buf, end)
    return np.frombuffer(buf, np.int64, n, off), end


def _split_i32(buf: bytes, off: int, n: int) -> tuple[np.ndarray, int]:
    end = off + 4 * n
    _need(buf, end)
    return np.frombuffer(buf, np.int32, n, off), end


def decode_match_resp(buf: bytes) -> tuple[np.ndarray, np.ndarray]:
    _need(buf, 4)
    (n,) = _U32.unpack_from(buf)
    ids, off = _split_i64(buf, 4, n)
    eps, _ = _split_i64(buf, off, n)
    return ids, eps


def decode_publish_resp(buf: bytes) -> int:
    _need(buf, 4)
    return _U32.unpack_from(buf)[0]


def decode_lookup_resp(buf: bytes) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    _need(buf, 4)
    (n,) = _U32.unpack_from(buf)
    ids, off = _split_i64(buf, 4, n)
    eps, off = _split_i64(buf, off, n)
    ntk, _ = _split_i32(buf, off, n)
    return ids, eps, ntk


def decode_filter_resp(buf: bytes) -> list[int]:
    _need(buf, 4)
    (n,) = _U32.unpack_from(buf)
    pos, _ = _split_i32(buf, 4, n)
    return pos.tolist()


def decode_evict_resp(buf: bytes) -> list[int]:
    _need(buf, 4)
    (n,) = _U32.unpack_from(buf)
    ids, _ = _split_i64(buf, 4, n)
    return ids.tolist()


def decode_evict_resp_keys(buf: bytes) -> tuple[list[int], list[bytes]]:
    """Freed block ids + the destroyed keys the server-side ``on_evict``
    hook saw — the tiered client re-arms the ghost-LRU admission filter
    with them in the pool-owning process."""
    _need(buf, 4)
    (n,) = _U32.unpack_from(buf)
    ids, off = _split_i64(buf, 4, n)
    _need(buf, off + 4)
    (k,) = _U32.unpack_from(buf, off)
    keys, _ = _split_keys(buf, off + 4, k)
    return ids.tolist(), keys


def decode_owners_resp(buf: bytes) -> tuple[list[bytes], list[int], list[int]]:
    _need(buf, 4)
    (m,) = _U32.unpack_from(buf)
    keys, off = _split_keys(buf, 4, m)
    ids, off = _split_i64(buf, off, m)
    eps, _ = _split_i64(buf, off, m)
    return keys, ids.tolist(), eps.tolist()


def decode_stats_resp(buf: bytes) -> tuple[int, int, int, int, int]:
    """(entries, hits, misses, ops_served, busy_ns) — the last two are
    the service-side timer (zero when the handler has no ring ctrl)."""
    _need(buf, _STATS.size)
    return _STATS.unpack_from(buf)


def decode_snapshot_resp(
    buf: bytes,
) -> tuple[int, list[bytes], list[int], list[int], list[int]]:
    """(total_entries, keys, block_ids, epochs, n_tokens) for one page."""
    _need(buf, 8)
    total, m = _U32.unpack_from(buf)[0], _U32.unpack_from(buf, 4)[0]
    keys, off = _split_keys(buf, 8, m)
    ids, off = _split_i64(buf, off, m)
    eps, off = _split_i64(buf, off, m)
    ntk, _ = _split_i32(buf, off, m)
    return total, keys, ids.tolist(), eps.tolist(), ntk.tolist()


def decode_restore_resp(buf: bytes) -> int:
    _need(buf, 4)
    return _U32.unpack_from(buf)[0]


def decode_remap_resp(buf: bytes) -> list[bool]:
    _need(buf, 4)
    (n,) = _U32.unpack_from(buf)
    _need(buf, 4 + n)
    return [b != 0 for b in buf[4 : 4 + n]]


def _split_frames(buf: bytes, off: int, k: int) -> list[bytes]:
    """k length-prefixed frames starting at ``off`` (the BATCH body)."""
    out = []
    for _ in range(k):
        _need(buf, off + 4)
        (ln,) = _U32.unpack_from(buf, off)
        off += 4
        _need(buf, off + ln)
        out.append(buf[off : off + ln])
        off += ln
    return out


def decode_batch_resp(buf: bytes) -> list[bytes]:
    _need(buf, 4)
    (k,) = _U32.unpack_from(buf)
    return _split_frames(buf, 4, k)


# ---------------------------------------------------------------------------
# server-side dispatch
# ---------------------------------------------------------------------------
_MAX_BATCH_DEPTH = 4  # BATCH-in-BATCH nesting cap (keeps decode O(payload))


def reply_bound(buf: bytes, _depth: int = 0) -> int:
    """Worst-case reply size for a request, WITHOUT executing it.

    Lets a transport with fixed reply capacity reject an op whose answer
    could not be shipped BEFORE any index mutation runs — otherwise an
    oversized EVICT would free blocks server-side while the caller only
    ever sees a transport error. Walks (and therefore validates) the
    whole frame structure INCLUDING each op's declared body size, so a
    BATCH with a truncated sub-op anywhere also fails up front instead
    of after its leading sub-ops mutated the index."""
    _need(buf, _HDR.size)
    op, n = _HDR.unpack_from(buf)
    if op == OP_MATCH:
        _need(buf, _HDR.size + KEY_BYTES * n)
        return 4 + 16 * n
    if op == OP_PUBLISH:
        _need(buf, _PUB_HDR.size + (KEY_BYTES + 16) * n)
        return 4
    if op == OP_LOOKUP:
        _need(buf, _HDR.size + KEY_BYTES * n)
        return 4 + 20 * n
    if op == OP_FILTER:
        _need(buf, _HDR.size + KEY_BYTES * n)
        return 4 + 4 * n
    if op == OP_EVICT:
        # ids (8 B) + destroyed keys (16 B) + the two u32 counters
        return 8 + 24 * n
    if op == OP_OWNERS:
        _need(buf, _HDR.size + 8 * n)
        return 4 + 32 * n
    if op == OP_REMAP:
        _need(buf, _HDR.size + (KEY_BYTES + 32) * n)
        return 4 + n
    if op == OP_EVICT_BLOCKS:
        _need(buf, _HDR.size + 8 * n)
        # ids (8 B) + destroyed keys (16 B) + the two u32 counters
        return 8 + 24 * n
    if op == OP_STATS:
        return _STATS.size
    if op == OP_SNAPSHOT:
        _need(buf, _HDR.size + 4)
        return 8 + 36 * n  # total+m then 16+8+8+4 per entry
    if op == OP_RESTORE:
        _need(buf, _HDR.size + (KEY_BYTES + 20) * n)
        return 4
    if op == OP_SEED_STATS:
        _need(buf, _HDR.size + _SEED_STATS.size)
        return 4
    if op == OP_BATCH:
        if _depth >= _MAX_BATCH_DEPTH:
            raise WireError(f"BATCH nesting exceeds {_MAX_BATCH_DEPTH}")
        frames = _split_frames(buf, _HDR.size, n)
        return 4 + sum(4 + reply_bound(f, _depth + 1) for f in frames)
    raise WireError(f"unknown op {op}")


def prevalidate(index, buf: bytes, _depth: int = 0) -> None:
    """Semantic validation of a request WITHOUT executing it.

    ``reply_bound`` already walks the frame structure; this pass runs the
    op-level checks (duplicate MATCH keys, out-of-range PUBLISH ids) over
    every sub-op up front, so a BATCH whose later sub-op is invalid fails
    BEFORE its leading mutating sub-ops commit — the batch either starts
    clean or not at all. ``handle_request`` repeats the same checks
    inline as defense-in-depth for direct callers."""
    _need(buf, _HDR.size)
    op, n = _HDR.unpack_from(buf)
    if op == OP_MATCH:
        keys, _ = _split_keys(buf, _HDR.size, n)
        _check_match_keys(keys)
    elif op == OP_PUBLISH:
        _need(buf, _PUB_HDR.size)
        _, n, _ = _PUB_HDR.unpack_from(buf)
        _, off = _split_keys(buf, _PUB_HDR.size, n)
        ids, _ = _split_i64(buf, off, n)
        _check_block_ids(index, ids, "PUBLISH")
    elif op in (OP_OWNERS, OP_EVICT_BLOCKS):
        ids, _ = _split_i64(buf, _HDR.size, n)
        _check_block_ids(index, ids, "OWNERS" if op == OP_OWNERS else "EVICT_BLOCKS")
    elif op == OP_RESTORE:
        _, off = _split_keys(buf, _HDR.size, n)
        ids, _ = _split_i64(buf, off, n)
        _check_block_ids(index, ids, "RESTORE")
    elif op == OP_REMAP:
        _, off = _split_keys(buf, _HDR.size, n)
        old_ids, off = _split_i64(buf, off, n)
        _check_block_ids(index, old_ids, "REMAP old")
        new_ids, _ = _split_i64(buf, off + 8 * n, n)  # skip old_epochs
        _check_block_ids(index, new_ids, "REMAP new")
    elif op == OP_BATCH:
        if _depth >= _MAX_BATCH_DEPTH:
            raise WireError(f"BATCH nesting exceeds {_MAX_BATCH_DEPTH}")
        for f in _split_frames(buf, _HDR.size, n):
            prevalidate(index, f, _depth + 1)


def _check_match_keys(keys: list[bytes]) -> None:
    if len(set(keys)) != len(keys):
        # a chain-hashed prefix never repeats a key; a duplicate would
        # also corrupt the index's batch LRU splice, so reject it at
        # the trust boundary instead of walking it
        raise WireError("duplicate keys in MATCH chain")


def _check_block_ids(index, ids: np.ndarray, what: str) -> None:
    if len(ids) and (ids.min() < 0 or ids.max() >= index.pool.n_blocks):
        # untrusted ids would index block2row out of range (numpy
        # negative indexing would silently corrupt — or leak — another
        # block's owner pointer)
        raise WireError(f"{what} block id out of pool range")


def _evict_with_keys(index, fn) -> bytes:
    """Run one eviction with the index's ``on_evict`` hook wrapped so the
    destroyed keys ALSO travel back in the reply (ids, then keys).  A
    tiered cluster over the process transport needs them client-side: the
    ghost-LRU admission filter lives with the pool owner, not with the
    metadata service that performed the eviction."""
    collected: list[bytes] = []
    prev = getattr(index, "on_evict", None)

    def hook(keys):
        collected.extend(keys)
        if prev is not None:
            prev(keys)

    index.on_evict = hook
    try:
        freed = fn()
    finally:
        index.on_evict = prev
    return (
        _U32.pack(len(freed))
        + np.asarray(freed, np.int64).tobytes()
        + _U32.pack(len(collected))
        + b"".join(collected)
    )


def handle_request(
    index, buf: bytes, _depth: int = 0, _validated: bool = False, ctrl=None
) -> bytes:
    """Decode one wire message, run it against ``index``, encode the reply.

    ``_validated`` skips the inline semantic checks when the caller
    already ran ``prevalidate`` over the whole frame (the server path) —
    direct callers keep them as defense-in-depth.  ``ctrl`` is the
    serving ring's control array when running inside a ring service: it
    lets OP_STATS report the service-side timer (ops served, busy-ns)."""
    _need(buf, _HDR.size)
    op, n = _HDR.unpack_from(buf)
    if op == OP_MATCH:
        keys, _ = _split_keys(buf, _HDR.size, n)
        if not _validated:
            _check_match_keys(keys)
        hits = index.match_prefix_keys(keys)
        ids = np.fromiter((b for _, b, _ in hits), np.int64, len(hits))
        eps = np.fromiter((e for _, _, e in hits), np.int64, len(hits))
        return _U32.pack(len(hits)) + ids.tobytes() + eps.tobytes()
    if op == OP_PUBLISH:
        _need(buf, _PUB_HDR.size)
        _, n, n_tokens = _PUB_HDR.unpack_from(buf)
        keys, off = _split_keys(buf, _PUB_HDR.size, n)
        ids, off = _split_i64(buf, off, n)
        eps, _ = _split_i64(buf, off, n)
        if not _validated:
            _check_block_ids(index, ids, "PUBLISH")
        index.publish_many(keys, ids.tolist(), eps.tolist(), n_tokens)
        return _U32.pack(n)
    if op == OP_LOOKUP:
        keys, _ = _split_keys(buf, _HDR.size, n)
        entries = index.lookup_many(keys)
        ids = np.fromiter(
            (-1 if e is None else e.block_id for e in entries), np.int64, n
        )
        eps = np.fromiter(
            (0 if e is None else e.epoch for e in entries), np.int64, n
        )
        ntk = np.fromiter(
            (0 if e is None else e.n_tokens for e in entries), np.int32, n
        )
        return _U32.pack(n) + ids.tobytes() + eps.tobytes() + ntk.tobytes()
    if op == OP_FILTER:
        keys, _ = _split_keys(buf, _HDR.size, n)
        missing = index.filter_unpublished(keys)
        return _U32.pack(len(missing)) + np.asarray(missing, np.int32).tobytes()
    if op == OP_EVICT:
        return _evict_with_keys(index, lambda: index.evict_lru(n))
    if op == OP_OWNERS:
        ids, _ = _split_i64(buf, _HDR.size, n)
        if not _validated:
            _check_block_ids(index, ids, "OWNERS")
        keys, bids, eps = index.owners_of(ids.tolist())
        return (
            _U32.pack(len(keys))
            + b"".join(keys)
            + np.asarray(bids, np.int64).tobytes()
            + np.asarray(eps, np.int64).tobytes()
        )
    if op == OP_REMAP:
        keys, off = _split_keys(buf, _HDR.size, n)
        old_ids, off = _split_i64(buf, off, n)
        old_eps, off = _split_i64(buf, off, n)
        new_ids, off = _split_i64(buf, off, n)
        new_eps, _ = _split_i64(buf, off, n)
        if not _validated:
            _check_block_ids(index, old_ids, "REMAP old")
            _check_block_ids(index, new_ids, "REMAP new")
        ok = index.remap_many(
            keys, old_ids.tolist(), old_eps.tolist(),
            new_ids.tolist(), new_eps.tolist(),
        )
        return _U32.pack(n) + bytes(bytearray(int(o) for o in ok))
    if op == OP_EVICT_BLOCKS:
        ids, _ = _split_i64(buf, _HDR.size, n)
        if not _validated:
            _check_block_ids(index, ids, "EVICT_BLOCKS")
        return _evict_with_keys(
            index, lambda: index.evict_blocks(ids.tolist())
        )
    if op == OP_STATS:
        s = index.stats()
        served = int(ctrl[CTRL_SERVED]) if ctrl is not None else 0
        busy = int(ctrl[CTRL_BUSY_NS]) if ctrl is not None else 0
        return _STATS.pack(s["entries"], s["hits"], s["misses"], served, busy)
    if op == OP_SNAPSHOT:
        _need(buf, _HDR.size + 4)
        (start,) = _U32.unpack_from(buf, _HDR.size)
        total, keys, ids, eps, ntk = index.snapshot_entries(start, n)
        return (
            _U32.pack(total)
            + _U32.pack(len(keys))
            + b"".join(keys)
            + np.asarray(ids, np.int64).tobytes()
            + np.asarray(eps, np.int64).tobytes()
            + np.asarray(ntk, np.int32).tobytes()
        )
    if op == OP_RESTORE:
        keys, off = _split_keys(buf, _HDR.size, n)
        ids, off = _split_i64(buf, off, n)
        eps, off = _split_i64(buf, off, n)
        ntk, _ = _split_i32(buf, off, n)
        if not _validated:
            _check_block_ids(index, ids, "RESTORE")
        index.restore_entries(keys, ids.tolist(), eps.tolist(), ntk.tolist())
        return _U32.pack(n)
    if op == OP_SEED_STATS:
        _need(buf, _HDR.size + _SEED_STATS.size)
        hits, misses = _SEED_STATS.unpack_from(buf, _HDR.size)
        index.seed_stats(hits, misses)
        return _U32.pack(0)
    if op == OP_BATCH:
        if _depth >= _MAX_BATCH_DEPTH:
            raise WireError(f"BATCH nesting exceeds {_MAX_BATCH_DEPTH}")
        out = [
            handle_request(index, f, _depth + 1, _validated, ctrl)
            for f in _split_frames(buf, _HDR.size, n)
        ]
        return _U32.pack(n) + b"".join(_U32.pack(len(r)) + r for r in out)
    raise WireError(f"unknown op {op}")


def make_index_handler(index, max_reply: int | None = None, ctrl=None):
    """Handler for ``CxlRpcServer``: the metadata service poll thread.

    ``max_reply`` (usually the ring's ``payload_bytes``) makes the handler
    verify — via ``reply_bound``, before executing anything — that the
    reply can be shipped, so a request whose answer cannot fit never
    half-runs a mutating op.  ``ctrl`` (the serving ring's control array)
    exposes the service timer to OP_STATS."""

    def handler(payload: bytes) -> bytes:
        if max_reply is not None and reply_bound(payload) > max_reply:
            raise WireError(f"reply would exceed {max_reply} B slot")
        prevalidate(index, payload)  # batch starts clean or not at all
        return handle_request(index, payload, _validated=True, ctrl=ctrl)

    return handler


# ---------------------------------------------------------------------------
# client-side proxy
# ---------------------------------------------------------------------------
class RpcIndexClient:
    """``GlobalIndex`` API surface over an RPC transport.

    Drop-in for the manager/engine side of the index: hashing
    (``keys_for``) runs locally, every metadata op is one batched
    round-trip. Ops whose chain exceeds one ring slot are split
    transparently (match splits stop early on a short chunk, so the
    prefix property is preserved).

    ``on_freed`` is the cross-process pool-reclaim hook: a service
    living in ANOTHER process must not mutate allocator state, so its
    evictions only drop index rows and ship the freed block ids back —
    this client then applies the real ``pool.release`` in the
    pool-owning process (None for in-process/thread transports, whose
    server releases directly).

    ``journal`` (a ``repro.core.shm.ShardJournal``) is the self-healing
    hook: confirmed publishes/evictions/remaps are appended so a
    supervisor-respawned service can replay the shard's observable state.
    ``retry`` (a ``repro.core.rpc.RetryPolicy``) turns a dying/restarting
    service into bounded backoff instead of an exception: a
    ``ServiceDiedError`` retries for every op (crash-safe — see the
    journal contract), a ``TimeoutError`` retries only ops that are
    idempotent under an applied-but-unacknowledged first attempt."""

    def __init__(self, rpc, block_tokens: int, max_payload: int | None = None,
                 hasher: PrefixHasher | None = None, on_freed=None,
                 journal=None, retry: RetryPolicy | None = None,
                 on_evict=None):
        self.rpc = rpc
        self.on_freed = on_freed
        # tiered clusters: destroyed keys from server-side evictions are
        # replayed into this hook (ghost-LRU arming in the pool owner)
        self.on_evict = on_evict
        self.journal = journal
        self.retry = retry
        # hashing is pure computation, so clients on one host can share a
        # hasher (and its request memo) instead of re-deriving the same
        # chain once per engine
        self.hasher = hasher if hasher is not None else PrefixHasher(block_tokens)
        self.block_tokens = block_tokens
        if max_payload is None:
            max_payload = getattr(
                getattr(rpc, "ring", None), "payload_bytes", 1 << 20
            )
        # per-op chain capacity of one slot (headers are <= 16 B),
        # bounding BOTH the request and its response
        self._max_match = max(1, (max_payload - 16) // KEY_BYTES)
        self._max_publish = max(1, (max_payload - 16) // (KEY_BYTES + 16))
        self._max_lookup = max(1, (max_payload - 16) // max(KEY_BYTES, 20))
        # evict replies carry 8 B id + 16 B destroyed key per block
        self._max_evict = max(1, (max_payload - 24) // 24)
        self._max_owners = max(1, (max_payload - 16) // 32)  # reply-bound
        self._max_remap = max(1, (max_payload - 16) // (KEY_BYTES + 32))
        self._max_snapshot = max(1, (max_payload - 24) // 36)  # reply-bound

    # -- hashing is local ------------------------------------------------
    def keys_for(self, tokens: list[int]) -> tuple[bytes, ...]:
        return self.hasher.keys_for(tokens)

    # -- transport with bounded retry -----------------------------------
    def _call(self, payload: bytes, idempotent: bool = True) -> bytes:
        """One round-trip under the retry policy (if any).

        ``ServiceDiedError`` (crash / supervisor ring swap) retries every
        op: the journal contract makes an applied-but-unacknowledged
        mutation safe to replay.  ``TimeoutError`` (service alive but
        slow) retries only ``idempotent`` ops — a timed-out EVICT may
        have freed blocks whose reply now sits in a quarantined slot, and
        a timed-out REMAP may have applied, so both surface the timeout
        to the caller instead."""
        pol = self.retry
        if pol is None:
            return self.rpc.call(payload)
        attempt = 0
        while True:
            try:
                return self.rpc.call(payload)
            except ServiceDiedError:
                attempt += 1
                if attempt > pol.max_retries:
                    raise
            except TimeoutError:
                if not idempotent:
                    raise
                attempt += 1
                if attempt > pol.max_retries:
                    raise
            stats = getattr(self.rpc, "stats", None)
            if stats is not None:
                stats.retries += 1
            time.sleep(pol.backoff(attempt))

    def _pipelined_rounds(self, msgs: list[bytes]) -> list[bytes]:
        """Ship independent chunk requests with the post/collect split:
        keep up to the ring's free-slot budget outstanding instead of one
        round-trip per chunk. ONLY for ops whose chunks commute (pure
        reads): the service drains slots in slot order, not post order,
        so pipelined mutations would apply out of order.  A transient
        transport failure (service died / timed out) re-runs every round
        serially under the retry policy — safe precisely because the
        callers are idempotent reads."""
        rpc = self.rpc
        if len(msgs) <= 1 or not hasattr(rpc, "post"):
            return [self._call(m) for m in msgs]
        out: list[bytes | None] = [None] * len(msgs)
        slots: list[tuple[int, int]] = []  # (msg index, slot)
        i = 0
        try:
            window = max(1, min(len(msgs), rpc.free_slots() - 1, 8))
            while i < len(msgs) or slots:
                while i < len(msgs) and len(slots) < window:
                    slots.append((i, rpc.post(msgs[i])))
                    i += 1
                j, slot = slots.pop(0)
                out[j] = rpc.collect(slot)
        except BaseException as e:
            for _, slot in slots:  # drain what was posted (or quarantine)
                try:
                    rpc.collect(slot)
                except Exception:  # noqa: BLE001
                    diag.note("wire.pipelined_drain.collect_failed")
            if self.retry is None or not isinstance(
                e, (ServiceDiedError, TimeoutError)
            ):
                raise
            return [self._call(m) for m in msgs]
        return out

    # -- one round-trip per op ------------------------------------------
    def match_prefix(self, tokens: list[int]) -> list[tuple[bytes, int, int]]:
        return self.match_prefix_keys(self.keys_for(tokens))

    def match_prefix_keys(self, keys) -> list[tuple[bytes, int, int]]:
        # chunk rounds stay SERIAL on purpose: a chunk is only sent after
        # the previous one matched in full, so the service LRU-touches
        # exactly the global all-hit prefix — pipelining would
        # speculatively touch keys past the first hole and break the
        # bit-identical differential equivalence with the in-process index
        out: list[tuple[bytes, int, int]] = []
        for off in range(0, len(keys), self._max_match):
            chunk = keys[off : off + self._max_match]
            ids, eps = decode_match_resp(self._call(encode_match(chunk)))
            out.extend(zip(chunk, ids.tolist(), eps.tolist()))
            if len(ids) < len(chunk):
                break  # prefix ended inside this chunk
        return out

    def publish_many(self, keys, block_ids, epochs, n_tokens: int) -> None:
        # serial rounds on purpose: the service drains slots in slot
        # order, so pipelined publish chunks could insert rows out of
        # chain order and scramble the LRU against the in-process index
        for off in range(0, len(keys), self._max_publish):
            end = off + self._max_publish
            self._call(
                encode_publish(
                    keys[off:end], block_ids[off:end], epochs[off:end], n_tokens
                )
            )
            if self.journal is not None:
                self.journal.append_publish(
                    keys[off:end], block_ids[off:end], epochs[off:end], n_tokens
                )

    def lookup_many(self, keys) -> list[IndexEntry | None]:
        msgs = [
            encode_lookup(keys[off : off + self._max_lookup])
            for off in range(0, len(keys), self._max_lookup)
        ]
        out: list[IndexEntry | None] = []
        for resp in self._pipelined_rounds(msgs):
            ids, eps, ntk = decode_lookup_resp(resp)
            out.extend(
                None if b < 0 else IndexEntry(int(b), int(e), int(t), 0.0)
                for b, e, t in zip(ids.tolist(), eps.tolist(), ntk.tolist())
            )
        return out

    def lookup(self, key: bytes) -> IndexEntry | None:
        return self.lookup_many([key])[0]

    def filter_unpublished(self, keys) -> list[int]:
        offs = list(range(0, len(keys), self._max_lookup))
        msgs = [encode_filter(keys[off : off + self._max_lookup]) for off in offs]
        out: list[int] = []
        for off, resp in zip(offs, self._pipelined_rounds(msgs)):
            out.extend(off + p for p in decode_filter_resp(resp))
        return out

    def evict_lru(self, n: int) -> list[int]:
        # chunked: the RESPONSE carries 8 B per freed block, so an
        # unbounded n could overflow the slot even though the request
        # always fits; a short chunk means the index ran out of victims
        freed: list[int] = []
        while n > 0:
            k = min(n, self._max_evict)
            got, gone = decode_evict_resp_keys(
                self._call(encode_evict(k), idempotent=False)
            )
            if got:
                if self.journal is not None:
                    self.journal.append_retract(got)
                if self.on_freed is not None:
                    self.on_freed(got)  # cross-process: reclaim pool blocks
            if gone and self.on_evict is not None:
                self.on_evict(gone)  # tiered: arm the admission filter
            freed.extend(got)
            if len(got) < k:
                break
            n -= k
        return freed

    # -- tier-migration control plane (the migrator over the wire) ------
    def owners_of(
        self, block_ids
    ) -> tuple[list[bytes], list[int], list[int]]:
        """One-round-trip (chunked) pre-copy snapshot; same contract as
        ``GlobalIndex.owners_of`` (indexed blocks only, input order)."""
        keys: list[bytes] = []
        ids: list[int] = []
        eps: list[int] = []
        M = self._max_owners
        msgs = [
            encode_owners(block_ids[off : off + M])
            for off in range(0, len(block_ids), M)
        ]
        for resp in self._pipelined_rounds(msgs):
            k, b, e = decode_owners_resp(resp)
            keys.extend(k)
            ids.extend(b)
            eps.extend(e)
        return keys, ids, eps

    def remap_many(
        self, keys, old_ids, old_epochs, new_ids, new_epochs
    ) -> list[bool]:
        ok: list[bool] = []
        M = self._max_remap
        for off in range(0, len(keys), M):
            end = off + M
            sub = decode_remap_resp(
                # NOT timeout-idempotent: a timed-out remap may have
                # applied, and a retry would then misreport ok=False
                self._call(
                    encode_remap(
                        keys[off:end], old_ids[off:end], old_epochs[off:end],
                        new_ids[off:end], new_epochs[off:end],
                    ),
                    idempotent=False,
                )
            )
            if self.journal is not None and any(sub):
                sel = [i for i, o in enumerate(sub) if o]
                self.journal.append_remap(
                    [keys[off:end][i] for i in sel],
                    [new_ids[off:end][i] for i in sel],
                    [new_epochs[off:end][i] for i in sel],
                )
            ok.extend(sub)
        return ok

    def evict_blocks(self, block_ids) -> list[int]:
        freed: list[int] = []
        M = self._max_evict  # 24 B per id in the reply: EVICT sizing applies
        for off in range(0, len(block_ids), M):
            got, gone = decode_evict_resp_keys(
                self._call(
                    encode_evict_blocks(block_ids[off : off + M]),
                    idempotent=False,
                )
            )
            if got:
                if self.journal is not None:
                    self.journal.append_retract(got)
                if self.on_freed is not None:
                    self.on_freed(got)  # cross-process: reclaim pool blocks
            if gone and self.on_evict is not None:
                self.on_evict(gone)  # tiered: arm the admission filter
            freed.extend(got)
        return freed

    # -- occupancy / counters -------------------------------------------
    def stats(self) -> dict:
        """Same shape as ``GlobalIndex.stats`` — lets the cluster report
        index stats when the index lives in another process.  The wire's
        service-timer fields are deliberately NOT in this dict (the
        differential harness bit-compares it against the in-process
        index); read them via ``service_stats``."""
        entries, hits, misses, _, _ = decode_stats_resp(
            self._call(encode_stats())
        )
        return {
            "entries": entries,
            "hits": hits,
            "misses": misses,
            "hit_rate": hits / max(1, hits + misses),
        }

    def service_stats(self) -> dict:
        """Service-side timer: requests served + ns spent in handlers,
        measured IN the serving thread/process (exp11's direct capacity
        signal — no in-process replica needed)."""
        _, _, _, served, busy = decode_stats_resp(self._call(encode_stats()))
        return {"ops_served": served, "busy_ns": busy}

    def n_entries(self) -> int:
        """Occupancy probe (the ``evict_lru_pressure`` signal)."""
        return self.stats()["entries"]

    # -- crash-restart support ------------------------------------------
    def snapshot_entries(
        self, start: int = 0, max_items: int | None = None
    ) -> tuple[int, list[bytes], list[int], list[int], list[int]]:
        """One OP_SNAPSHOT page (defaults to the slot-capacity page size)."""
        if max_items is None:
            max_items = self._max_snapshot
        return decode_snapshot_resp(
            self._call(encode_snapshot(start, max_items))
        )

    def snapshot_all(self) -> list[tuple[bytes, int, int, int]]:
        """Page the WHOLE index in LRU order: [(key, id, epoch, n_tokens)].
        Rebuild-verification helper — call against a quiesced shard."""
        out: list[tuple[bytes, int, int, int]] = []
        start = 0
        while True:
            total, keys, ids, eps, ntk = self.snapshot_entries(start)
            out.extend(zip(keys, ids, eps, ntk))
            start += len(keys)
            if start >= total or not keys:
                return out

    def restore_entries(self, keys, block_ids, epochs, n_tokens) -> int:
        """Push entries into the (freshly restarted) shard: OP_RESTORE,
        chunked at 36 B/entry (same geometry as snapshot pages)."""
        done = 0
        M = self._max_snapshot
        for off in range(0, len(keys), M):
            end = off + M
            done += decode_restore_resp(
                self._call(
                    encode_restore(
                        keys[off:end], block_ids[off:end],
                        epochs[off:end], n_tokens[off:end],
                    )
                )
            )
        return done

    def seed_stats(self, hits: int, misses: int) -> None:
        """Seed the shard's hit/miss counters (warm-restore path)."""
        self._call(encode_seed_stats(hits, misses))

    def call_batch(self, requests: list[bytes]) -> list[bytes]:
        """Ship k already-encoded ops in ONE ring round-trip."""
        return decode_batch_resp(self._call(encode_batch(requests)))


# ---------------------------------------------------------------------------
# sharded client: one ring per index shard, parallel outstanding RPCs
# ---------------------------------------------------------------------------
class ShardedRpcIndexClient:
    """``GlobalIndex`` API over S metadata rings (one ``GlobalIndex``
    shard behind each), keys partitioned by digest hash.

    The partition/merge semantics are identical to the in-process
    ``repro.core.index.ShardedIndex`` (same ``shard_of_key`` routing, same
    longest-all-hit-prefix merge) — the only difference is the transport:
    every fan-out POSTS the per-shard requests to all rings BEFORE
    collecting any reply, so one op keeps S RPCs outstanding in parallel
    instead of visiting the shards one round-trip at a time. Chains longer
    than a slot run in chunk rounds, still posting each round to every
    still-active shard first.

    S=1 degenerates to a plain ``RpcIndexClient`` over the single ring
    (bit-identical message sequence to the unsharded ``index_rpc`` mode).
    """

    def __init__(self, rpcs, block_tokens: int, max_payload: int | None = None,
                 hasher: PrefixHasher | None = None, on_freed=None,
                 journals=None, retry: RetryPolicy | None = None,
                 degrade: bool = False, on_evict=None):
        if not rpcs:
            raise ValueError("need at least one rpc transport")
        self.rpcs = list(rpcs)
        self.n_shards = len(self.rpcs)
        self.block_tokens = block_tokens
        self.hasher = hasher if hasher is not None else PrefixHasher(block_tokens)
        self.retry = retry
        # degraded mode: a shard that stays unreachable through its
        # retries fails SOFT on the match path — its positions become
        # holes, the merged prefix cuts there, and serving recomputes
        # instead of erroring (worse TTFT, no failure)
        self.degrade = degrade
        self.degraded_ops = 0
        if journals is None:
            journals = [None] * self.n_shards
        self.journals = list(journals)
        # per-shard proxies share the hasher (hash once per front); they
        # also carry the per-op slot-capacity maths, the cross-process
        # pool-reclaim hook (see RpcIndexClient.on_freed), that shard's
        # publish journal, and the retry policy
        self.shards = [
            RpcIndexClient(
                r, block_tokens, max_payload, hasher=self.hasher,
                on_freed=on_freed, journal=self.journals[i], retry=retry,
                on_evict=on_evict,
            )
            for i, r in enumerate(self.rpcs)
        ]
        # rings may differ in slot size: fan-out chunks use the tightest
        self._max_match = min(s._max_match for s in self.shards)
        self._max_publish = min(s._max_publish for s in self.shards)
        self._max_lookup = min(s._max_lookup for s in self.shards)
        self._max_evict = min(s._max_evict for s in self.shards)
        self._max_owners = min(s._max_owners for s in self.shards)
        self._max_remap = min(s._max_remap for s in self.shards)

    # -- transport: post-all, then collect-all ---------------------------
    def _call_shard(
        self, s: int, msg: bytes, timeout: float, idempotent: bool
    ) -> bytes:
        """Single-shard call with the bounded-retry semantics of
        ``RpcIndexClient._call`` (see there for the idempotency rules)."""
        pol = self.retry
        attempt = 0
        while True:
            try:
                return self.rpcs[s].call(msg, timeout)
            except ServiceDiedError:
                attempt += 1
                if pol is None or attempt > pol.max_retries:
                    raise
            except TimeoutError:
                if pol is None or not idempotent:
                    raise
                attempt += 1
                if attempt > pol.max_retries:
                    raise
            st = getattr(self.rpcs[s], "stats", None)
            if st is not None:
                st.retries += 1
            time.sleep(pol.backoff(attempt))

    def _fanout(
        self, msgs: dict[int, bytes], timeout: float = 5.0,
        idempotent: bool = True, failed: set[int] | None = None,
    ) -> dict[int, bytes]:
        """One parallel round: post every shard's request, then collect.

        A failed post stops posting (nothing else enters the rings); every
        slot that WAS posted is still collected (or quarantined by its own
        collect).  Shards that failed transiently (service died/restarted,
        idempotent timeout) — or never got posted because an earlier
        shard's post raised — then get a bounded-backoff second chance via
        ``_call_shard``.  A shard still missing after that either raises
        the first recorded failure, or (``failed`` not None — degraded
        mode) is recorded in ``failed`` and simply omitted from the
        result, the caller treating its positions as holes."""
        slots: dict[int, int] = {}
        errs: dict[int, BaseException] = {}
        for s, m in msgs.items():
            try:
                slots[s] = self.rpcs[s].post(m)
            except BaseException as e:  # noqa: BLE001
                errs[s] = e
                break
        out: dict[int, bytes] = {}
        for s, slot in slots.items():
            try:
                out[s] = self.rpcs[s].collect(slot, timeout)
            except BaseException as e:  # noqa: BLE001
                errs[s] = e
        for s in msgs:
            if s in out:
                continue
            e = errs.get(s)
            if e is not None and not isinstance(
                e, (ServiceDiedError, TimeoutError)
            ):
                continue  # handler/protocol error: never retried
            if isinstance(e, TimeoutError) and not idempotent:
                continue  # may have applied server-side: surface it
            if self.retry is None and failed is None:
                continue  # no second chance configured
            try:
                out[s] = self._call_shard(s, msgs[s], timeout, idempotent)
                errs.pop(s, None)
            except BaseException as e2:  # noqa: BLE001
                errs[s] = e2
        missing = [s for s in msgs if s not in out]
        if missing:
            # a hard error (handler/protocol failure) is a caller bug and
            # raises even in degraded mode — only transient transport
            # failures degrade to holes
            degradable = failed is not None and all(
                isinstance(errs[s], (ServiceDiedError, TimeoutError))
                for s in missing
                if s in errs
            )
            if not degradable:
                for s in msgs:
                    if s in errs:
                        raise errs[s]
                raise RuntimeError("fan-out incomplete without an error")
            for s in missing:
                failed.add(s)
                st = getattr(self.rpcs[s], "stats", None)
                if st is not None:
                    st.degraded_ops += 1
            self.degraded_ops += len(missing)
        return out

    # -- hashing is local ------------------------------------------------
    def keys_for(self, tokens: list[int]) -> tuple[bytes, ...]:
        return self.hasher.keys_for(tokens)

    # -- chain ops: partition, parallel rounds, merge by position --------
    def match_prefix(self, tokens: list[int]) -> list[tuple[bytes, int, int]]:
        return self.match_prefix_keys(self.keys_for(tokens))

    def match_prefix_keys(self, keys) -> list[tuple[bytes, int, int]]:
        if self.n_shards == 1:
            if not self.degrade:
                return self.shards[0].match_prefix_keys(keys)
            try:
                return self.shards[0].match_prefix_keys(keys)
            except (ServiceDiedError, TimeoutError):
                # the single shard is down: every position is a hole —
                # serving recomputes the whole prefix instead of erroring
                self.degraded_ops += 1
                st = getattr(self.rpcs[0], "stats", None)
                if st is not None:
                    st.degraded_ops += 1
                return []
        key_lists, pos_lists = partition_keys(keys, self.n_shards)
        found: list[tuple[int, int] | None] = [None] * len(keys)
        offs = [0] * self.n_shards
        active = {s for s in range(self.n_shards) if key_lists[s]}
        failed: set[int] | None = set() if self.degrade else None
        M = self._max_match
        while active:
            msgs = {
                s: encode_match(key_lists[s][offs[s] : offs[s] + M])
                for s in active
            }
            resp = self._fanout(msgs, failed=failed)
            for s in list(active):
                if s not in resp:
                    # degraded: shard down — its unanswered positions
                    # stay None and the merge cuts at the first hole
                    active.discard(s)
                    continue
                ids, eps = decode_match_resp(resp[s])
                kl, pl = key_lists[s], pos_lists[s]
                o = offs[s]
                for j, (b, e) in enumerate(zip(ids.tolist(), eps.tolist())):
                    found[pl[o + j]] = (b, e)
                chunk = min(M, len(kl) - o)
                offs[s] = o + chunk
                if len(ids) < chunk or offs[s] >= len(kl):
                    active.discard(s)  # shard prefix ended (or exhausted)
        out: list[tuple[bytes, int, int]] = []
        for i, k in enumerate(keys):
            f = found[i]
            if f is None:
                break  # first hole ends the global all-hit prefix
            out.append((k, f[0], f[1]))
        return out

    def publish_many(self, keys, block_ids, epochs, n_tokens: int) -> None:
        if self.n_shards == 1:
            return self.shards[0].publish_many(keys, block_ids, epochs, n_tokens)
        key_lists, pos_lists = partition_keys(keys, self.n_shards)
        parts = {
            s: (
                key_lists[s],
                [block_ids[i] for i in pos_lists[s]],
                [epochs[i] for i in pos_lists[s]],
            )
            for s in range(self.n_shards)
            if key_lists[s]
        }
        offs = dict.fromkeys(parts, 0)
        M = self._max_publish
        while parts:
            msgs = {}
            for s, (kl, bl, el) in parts.items():
                o = offs[s]
                msgs[s] = encode_publish(
                    kl[o : o + M], bl[o : o + M], el[o : o + M], n_tokens
                )
            self._fanout(msgs)
            for s in list(parts):
                kl, bl, el = parts[s]
                o = offs[s]
                if self.journals[s] is not None:
                    self.journals[s].append_publish(
                        kl[o : o + M], bl[o : o + M], el[o : o + M], n_tokens
                    )
                offs[s] += M
                if offs[s] >= len(kl):
                    del parts[s], offs[s]

    def lookup_many(self, keys) -> list[IndexEntry | None]:
        if self.n_shards == 1:
            return self.shards[0].lookup_many(keys)
        key_lists, pos_lists = partition_keys(keys, self.n_shards)
        out: list[IndexEntry | None] = [None] * len(keys)
        offs = [0] * self.n_shards
        active = {s for s in range(self.n_shards) if key_lists[s]}
        M = self._max_lookup
        while active:
            msgs = {
                s: encode_lookup(key_lists[s][offs[s] : offs[s] + M])
                for s in active
            }
            resp = self._fanout(msgs)
            for s in list(active):
                ids, eps, ntk = decode_lookup_resp(resp[s])
                pl = pos_lists[s]
                o = offs[s]
                for j, (b, e, t) in enumerate(
                    zip(ids.tolist(), eps.tolist(), ntk.tolist())
                ):
                    if b >= 0:
                        out[pl[o + j]] = IndexEntry(b, e, t, 0.0)
                offs[s] = o + len(ids)
                if offs[s] >= len(key_lists[s]):
                    active.discard(s)
        return out

    def lookup(self, key: bytes) -> IndexEntry | None:
        return self.shards[shard_of_key(key, self.n_shards)].lookup(key)

    def filter_unpublished(self, keys) -> list[int]:
        if self.n_shards == 1:
            return self.shards[0].filter_unpublished(keys)
        key_lists, pos_lists = partition_keys(keys, self.n_shards)
        out: list[int] = []
        offs = [0] * self.n_shards
        active = {s for s in range(self.n_shards) if key_lists[s]}
        M = self._max_lookup
        while active:
            msgs = {
                s: encode_filter(key_lists[s][offs[s] : offs[s] + M])
                for s in active
            }
            resp = self._fanout(msgs)
            for s in list(active):
                kl, pl = key_lists[s], pos_lists[s]
                o = offs[s]
                out.extend(pl[o + p] for p in decode_filter_resp(resp[s]))
                offs[s] = o + min(M, len(kl) - o)
                if offs[s] >= len(kl):
                    active.discard(s)
        out.sort()
        return out

    # -- eviction + migration control plane ------------------------------
    def evict_lru(self, n: int) -> list[int]:
        """Occupancy-weighted eviction — the EXACT policy function the
        in-process ``ShardedIndex`` runs (``evict_lru_pressure``), with
        each per-shard probe/evict going over that shard's ring.  Shared
        code is what keeps the two planes in lockstep: the differential
        harness asserts identical freed lists transport-for-transport.
        Eviction is pressure-relief (not request-path) traffic, so the
        sequential rounds are fine."""
        if self.n_shards == 1:
            return self.shards[0].evict_lru(n)
        return evict_lru_pressure(self.shards, n)

    def owners_of(
        self, block_ids
    ) -> tuple[list[bytes], list[int], list[int]]:
        if self.n_shards == 1:
            return self.shards[0].owners_of(block_ids)
        owner: dict[int, tuple[bytes, int]] = {}
        M = self._max_owners
        for off in range(0, len(block_ids), M):
            chunk = block_ids[off : off + M]
            resp = self._fanout(
                {s: encode_owners(chunk) for s in range(self.n_shards)}
            )
            for r in resp.values():
                k, b, e = decode_owners_resp(r)
                for kk, bb, ee in zip(k, b, e):
                    owner[bb] = (kk, ee)
        keys_o: list[bytes] = []
        ids_o: list[int] = []
        eps_o: list[int] = []
        for b in block_ids:
            f = owner.get(int(b))
            if f is not None:
                keys_o.append(f[0])
                ids_o.append(int(b))
                eps_o.append(f[1])
        return keys_o, ids_o, eps_o

    def remap_many(
        self, keys, old_ids, old_epochs, new_ids, new_epochs
    ) -> list[bool]:
        if self.n_shards == 1:
            return self.shards[0].remap_many(
                keys, old_ids, old_epochs, new_ids, new_epochs
            )
        key_lists, pos_lists = partition_keys(keys, self.n_shards)
        ok = [False] * len(keys)
        offs = [0] * self.n_shards
        active = {s for s in range(self.n_shards) if key_lists[s]}
        M = self._max_remap
        while active:
            msgs = {}
            for s in active:
                kl, pl = key_lists[s], pos_lists[s]
                o = offs[s]
                sel = pl[o : o + M]
                msgs[s] = encode_remap(
                    kl[o : o + M],
                    [old_ids[i] for i in sel],
                    [old_epochs[i] for i in sel],
                    [new_ids[i] for i in sel],
                    [new_epochs[i] for i in sel],
                )
            resp = self._fanout(msgs, idempotent=False)
            for s in list(active):
                kl, pl = key_lists[s], pos_lists[s]
                o = offs[s]
                sub = decode_remap_resp(resp[s])
                for v, i in zip(sub, pl[o : o + M]):
                    ok[i] = v
                if self.journals[s] is not None and any(sub):
                    done = [i for v, i in zip(sub, pl[o : o + M]) if v]
                    self.journals[s].append_remap(
                        [keys[i] for i in done],
                        [new_ids[i] for i in done],
                        [new_epochs[i] for i in done],
                    )
                offs[s] = o + min(M, len(kl) - o)
                if offs[s] >= len(kl):
                    active.discard(s)
        return ok

    def evict_blocks(self, block_ids) -> list[int]:
        if self.n_shards == 1:
            return self.shards[0].evict_blocks(block_ids)
        # sequential per shard (each self.shards[s] chunks its own wire
        # round-trips); this op is background-migrator traffic, so the
        # lost parallelism is not on the request path
        return evict_blocks_sharded(self.shards, block_ids)

    def stats(self) -> dict:
        """Aggregate per-shard counters — same shape as
        ``ShardedIndex.stats`` (``shards`` occupancy list for S>1)."""
        if self.n_shards == 1:
            return self.shards[0].stats()
        per = [s.stats() for s in self.shards]
        hits = sum(p["hits"] for p in per)
        misses = sum(p["misses"] for p in per)
        return {
            "entries": sum(p["entries"] for p in per),
            "hits": hits,
            "misses": misses,
            "hit_rate": hits / max(1, hits + misses),
            "shards": [p["entries"] for p in per],
        }

    def service_stats(self) -> dict:
        """Aggregate service-side timers (per-shard breakdown included)."""
        per = [s.service_stats() for s in self.shards]
        return {
            "ops_served": sum(p["ops_served"] for p in per),
            "busy_ns": sum(p["busy_ns"] for p in per),
            "shards": per,
        }


# ---------------------------------------------------------------------------
# pool allocator over the wire (the zero-copy data plane's control half)
# ---------------------------------------------------------------------------
# With the block payloads in one shared segment (``BelugaPool.share_data``
# / ``repro.core.shmpool``), engine worker processes load/store KV bytes
# directly — but the allocator's free stacks are ordinary Python state
# with exactly one owner, the pool-owning parent.  These four ops carry
# allocate/retain/release/free-count over a ring:
#
#     POOL_ALLOC   := n:u32            -> n:u32  block_ids[n*i64]
#     POOL_RETAIN  := n:u32  ids[n*i64] -> n:u32
#     POOL_RELEASE := n:u32  ids[n*i64] -> n:u32
#     POOL_FREE    := n:u32 (ignored)  -> free:u64  alloc_count:u64
#
# An allocator failure (``OutOfPoolMemory``) travels in-band as the ring's
# RESP_ERROR frame and is re-raised type-faithfully client-side, so the
# manager's evict-and-retry path works unchanged across the boundary.

_POOL_FREE_RESP = struct.Struct("<QQ")


def encode_pool_alloc(n: int) -> bytes:
    return _HDR.pack(OP_POOL_ALLOC, n)


# tiered extensions of the same plane:
#     POOL_ALLOC_KEYS := n:u32  keys[n*16]          -> n:u32  ids[n*i64]
#     POOL_TOUCH      := n:u32  now:f64  ids[n*i64] -> k:u32  counts[k*i32]
# (keys feed the ghost-LRU admission filter; TOUCH returns the per-tier
# block counts of the touched set so the worker can price the fetch)
_POOL_TOUCH_HDR = struct.Struct("<BId")  # op, count, virtual now


def encode_pool_alloc_keys(keys) -> bytes:
    return _HDR.pack(OP_POOL_ALLOC_KEYS, len(keys)) + _join_keys(keys)


def encode_pool_touch(block_ids, now: float) -> bytes:
    return _POOL_TOUCH_HDR.pack(
        OP_POOL_TOUCH, len(block_ids), now
    ) + np.asarray(block_ids, np.int64).tobytes()


def decode_pool_touch_resp(buf: bytes) -> tuple[int, ...]:
    _need(buf, 4)
    (k,) = _U32.unpack_from(buf)
    counts, _ = _split_i32(buf, 4, k)
    return tuple(int(c) for c in counts)


def encode_pool_retain(block_ids) -> bytes:
    return _HDR.pack(OP_POOL_RETAIN, len(block_ids)) + np.asarray(
        block_ids, np.int64
    ).tobytes()


def encode_pool_release(block_ids) -> bytes:
    return _HDR.pack(OP_POOL_RELEASE, len(block_ids)) + np.asarray(
        block_ids, np.int64
    ).tobytes()


def encode_pool_free() -> bytes:
    return _HDR.pack(OP_POOL_FREE, 0)


def decode_pool_alloc_resp(buf: bytes) -> list[int]:
    _need(buf, 4)
    (n,) = _U32.unpack_from(buf)
    ids, _ = _split_i64(buf, 4, n)
    return ids.tolist()


def decode_pool_free_resp(buf: bytes) -> tuple[int, int]:
    _need(buf, _POOL_FREE_RESP.size)
    return _POOL_FREE_RESP.unpack_from(buf)


# journal proxy frames (shard:u32 right after the op header):
#     JRNL_PUBLISH := op:u8 n:u32 shard:u32 n_tokens:i32
#                     keys[n*16] ids[n*i64] epochs[n*i64]       -> n:u32
#     JRNL_RETRACT := op:u8 n:u32 shard:u32 ids[n*i64]          -> n:u32
#     JRNL_REMAP   := op:u8 n:u32 shard:u32
#                     keys[n*16] ids[n*i64] epochs[n*i64]       -> n:u32
_JRNL_PUB_HDR = struct.Struct("<BIIi")  # op, count, shard, n_tokens
_JRNL_HDR = struct.Struct("<BII")  # op, count, shard


def encode_jrnl_publish(shard, keys, block_ids, epochs, n_tokens) -> bytes:
    n = len(keys)
    if not (n == len(block_ids) == len(epochs)):
        raise WireError("journal publish arrays disagree on length")
    return (
        _JRNL_PUB_HDR.pack(OP_JRNL_PUBLISH, n, shard, n_tokens)
        + _join_keys(keys)
        + np.asarray(block_ids, np.int64).tobytes()
        + np.asarray(epochs, np.int64).tobytes()
    )


def encode_jrnl_retract(shard, block_ids) -> bytes:
    return _JRNL_HDR.pack(
        OP_JRNL_RETRACT, len(block_ids), shard
    ) + np.asarray(block_ids, np.int64).tobytes()


def encode_jrnl_remap(shard, keys, new_ids, new_epochs) -> bytes:
    n = len(keys)
    if not (n == len(new_ids) == len(new_epochs)):
        raise WireError("journal remap arrays disagree on length")
    return (
        _JRNL_HDR.pack(OP_JRNL_REMAP, n, shard)
        + _join_keys(keys)
        + np.asarray(new_ids, np.int64).tobytes()
        + np.asarray(new_epochs, np.int64).tobytes()
    )


def pool_reply_bound(buf: bytes) -> int:
    """Worst-case reply size WITHOUT executing (see ``reply_bound``):
    an ALLOC whose id list could not ship must fail before any blocks
    leave the free stacks."""
    _need(buf, _HDR.size)
    op, n = _HDR.unpack_from(buf)
    if op == OP_POOL_ALLOC:
        return 4 + 8 * n
    if op == OP_POOL_ALLOC_KEYS:
        _need(buf, _HDR.size + KEY_BYTES * n)
        return 4 + 8 * n
    if op == OP_POOL_TOUCH:
        _need(buf, _POOL_TOUCH_HDR.size + 8 * n)
        return 4 + 4 * 16  # k:u32 + per-tier i32 counts (chain cap 16)
    if op in (OP_POOL_RETAIN, OP_POOL_RELEASE):
        _need(buf, _HDR.size + 8 * n)
        return 4
    if op == OP_POOL_FREE:
        return _POOL_FREE_RESP.size
    if op == OP_JRNL_PUBLISH:
        _need(buf, _JRNL_PUB_HDR.size + (KEY_BYTES + 16) * n)
        return 4
    if op == OP_JRNL_RETRACT:
        _need(buf, _JRNL_HDR.size + 8 * n)
        return 4
    if op == OP_JRNL_REMAP:
        _need(buf, _JRNL_HDR.size + (KEY_BYTES + 16) * n)
        return 4
    raise WireError(f"unknown pool op {op}")


def handle_journal_request(buf: bytes, journals, ledger=None, worker=None) -> bytes:
    """Dispatch one journal-proxy op against the parent-held journals.

    ``ShardJournal._append`` is thread-locked, so this handler (running
    on the allocator service thread) appends safely alongside the parent
    main thread's own index clients.  A JRNL_PUBLISH additionally clears
    the posting worker's lease on the published blocks: the alloc-ref's
    ownership transfers to the index (eviction releases it via
    ``on_freed``), so those blocks must NOT be reclaimed if the worker
    later dies."""
    _need(buf, _HDR.size)
    op, n = _HDR.unpack_from(buf)
    if op == OP_JRNL_PUBLISH:
        _need(buf, _JRNL_PUB_HDR.size)
        _, n, shard, n_tokens = _JRNL_PUB_HDR.unpack_from(buf)
        if shard >= len(journals):
            raise WireError(f"journal shard {shard} out of range")
        keys, off = _split_keys(buf, _JRNL_PUB_HDR.size, n)
        ids, off = _split_i64(buf, off, n)
        eps, _ = _split_i64(buf, off, n)
        journals[shard].append_publish(keys, ids.tolist(), eps.tolist(), n_tokens)
        if ledger is not None and worker is not None:
            # the lease mirror is shared with the supervisor's reconcile
            # (parent main thread): every mutation goes under the mutex
            with ledger.mutex:
                ledger.on_publish(worker, ids.tolist())
        return _U32.pack(n)
    if op in (OP_JRNL_RETRACT, OP_JRNL_REMAP):
        _need(buf, _JRNL_HDR.size)
        _, n, shard = _JRNL_HDR.unpack_from(buf)
        if shard >= len(journals):
            raise WireError(f"journal shard {shard} out of range")
        if op == OP_JRNL_RETRACT:
            ids, _ = _split_i64(buf, _JRNL_HDR.size, n)
            journals[shard].append_retract(ids.tolist())
        else:
            keys, off = _split_keys(buf, _JRNL_HDR.size, n)
            ids, off = _split_i64(buf, off, n)
            eps, _ = _split_i64(buf, off, n)
            journals[shard].append_remap(keys, ids.tolist(), eps.tolist())
        return _U32.pack(n)
    raise WireError(f"unknown journal op {op}")


def handle_pool_request(pool: "BelugaPool", buf: bytes) -> bytes:  # noqa: F821
    """Dispatch one pool-allocator op against the OWNING pool."""
    _need(buf, _HDR.size)
    op, n = _HDR.unpack_from(buf)
    if op == OP_POOL_ALLOC:
        ids = pool.allocate(n)  # OutOfPoolMemory -> in-band RESP_ERROR
        return _U32.pack(len(ids)) + np.asarray(ids, np.int64).tobytes()
    if op == OP_POOL_ALLOC_KEYS:
        keys, _ = _split_keys(buf, _HDR.size, n)
        # tiered parent: keys route through the ghost-LRU admission
        # filter exactly as an in-process writeback allocation would
        ids = pool.allocate(n, keys=keys)
        return _U32.pack(len(ids)) + np.asarray(ids, np.int64).tobytes()
    if op == OP_POOL_TOUCH:
        _need(buf, _POOL_TOUCH_HDR.size)
        _, n, now = _POOL_TOUCH_HDR.unpack_from(buf)
        ids, _ = _split_i64(buf, _POOL_TOUCH_HDR.size, n)
        _check_block_ids(pool_index_shim(pool), ids, "POOL_TOUCH")
        counts = pool.touch_demand(ids.tolist(), now)
        return _U32.pack(len(counts)) + np.asarray(
            counts, np.int32
        ).tobytes()
    if op in (OP_POOL_RETAIN, OP_POOL_RELEASE):
        ids, _ = _split_i64(buf, _HDR.size, n)
        what = "POOL_RETAIN" if op == OP_POOL_RETAIN else "POOL_RELEASE"
        _check_block_ids(pool_index_shim(pool), ids, what)
        if op == OP_POOL_RETAIN:
            pool.retain(ids.tolist())
        else:
            pool.release(ids.tolist())
        return _U32.pack(n)
    if op == OP_POOL_FREE:
        return _POOL_FREE_RESP.pack(pool.free_blocks(), pool.alloc_count)
    raise WireError(f"unknown pool op {op}")


class pool_index_shim:
    """Adapter so ``_check_block_ids`` (written against an index) can
    range-check untrusted ids against a bare pool."""

    def __init__(self, pool):
        self.pool = pool


def make_pool_handler(pool, max_reply: int | None = None, *, ledger=None,
                      slot_owner=None, journals=None):
    """Handler for the parent-side pool-allocator ring service.

    Plain mode (all keyword hooks None) is the PR-7 hot path, unchanged.
    With ``ledger`` (a ``repro.core.shmpool.WorkerLeaseLedger``) the
    handler declares ``wants_slot`` so ``drain_ready`` also passes the
    posting slot: ``slot_owner(slot)`` maps it to the worker index (the
    pool ring is partitioned per worker) and every ALLOC/RETAIN/RELEASE
    is mirrored into the ledger — the raw material of lease
    reconciliation when that worker dies.  ``journals`` additionally
    enables the journal-proxy ops (selfheal mode), serving worker-side
    journal appends against the parent-held ``ShardJournal``s.  Ledger
    mode serializes pool mutation against ``ledger.mutex`` so the
    supervisor's reconcile path (parent main thread) cannot race the
    allocator thread on the pool's free stacks."""
    if ledger is None and journals is None:

        def handler(payload: bytes) -> bytes:
            if max_reply is not None and pool_reply_bound(payload) > max_reply:
                raise WireError(f"reply would exceed {max_reply} B slot")
            return handle_pool_request(pool, payload)

        return handler

    jrnls = list(journals) if journals is not None else []

    def handler(payload: bytes, slot: int) -> bytes:  # noqa: F811
        if max_reply is not None and pool_reply_bound(payload) > max_reply:
            raise WireError(f"reply would exceed {max_reply} B slot")
        op, n = _HDR.unpack_from(payload)
        worker = slot_owner(slot) if slot_owner is not None else None
        if op in (OP_JRNL_PUBLISH, OP_JRNL_RETRACT, OP_JRNL_REMAP):
            return handle_journal_request(payload, jrnls, ledger, worker)
        if ledger is None or worker is None:
            return handle_pool_request(pool, payload)
        with ledger.mutex:
            reply = handle_pool_request(pool, payload)
            if op in (OP_POOL_ALLOC, OP_POOL_ALLOC_KEYS):
                ledger.on_alloc(worker, decode_pool_alloc_resp(reply), pool)
            elif op == OP_POOL_RETAIN:
                ids, _ = _split_i64(payload, _HDR.size, n)
                ledger.on_retain(worker, ids.tolist(), pool)
            elif op == OP_POOL_RELEASE:
                ids, _ = _split_i64(payload, _HDR.size, n)
                ledger.on_release(worker, ids.tolist())
        return reply

    handler.wants_slot = True
    return handler


class RemoteJournal:
    """Worker-side proxy for a parent-held ``ShardJournal``.

    Exposes the exact append surface the index clients call after a
    confirmed reply (``append_publish`` / ``append_retract`` /
    ``append_remap``), but ships each append over the worker's pool
    allocator ring tagged with the target shard — the journal segments
    themselves have exactly one writer side, the parent.  Appends are
    idempotent under ``live_entries`` folding (a duplicated publish or
    retract folds to the same live state), so transient transport
    failures retry under the same policy as the data ops."""

    def __init__(self, rpc, shard: int, max_payload: int | None = None,
                 retry: RetryPolicy | None = None):
        self.rpc = rpc
        self.shard = shard
        self.retry = retry
        if max_payload is None:
            max_payload = getattr(
                getattr(rpc, "ring", None), "payload_bytes", 1 << 20
            )
        self._max_pub = max(1, (max_payload - 24) // (KEY_BYTES + 16))
        self._max_ids = max(1, (max_payload - 24) // 8)

    def _call(self, payload: bytes) -> bytes:
        pol = self.retry
        if pol is None:
            return self.rpc.call(payload)
        attempt = 0
        while True:
            try:
                return self.rpc.call(payload)
            except (ServiceDiedError, TimeoutError):
                attempt += 1
                if attempt > pol.max_retries:
                    raise
            stats = getattr(self.rpc, "stats", None)
            if stats is not None:
                stats.retries += 1
            time.sleep(pol.backoff(attempt))

    def append_publish(self, keys, block_ids, epochs, n_tokens: int) -> None:
        M = self._max_pub
        for off in range(0, len(keys), M):
            end = off + M
            self._call(encode_jrnl_publish(
                self.shard, keys[off:end], block_ids[off:end],
                epochs[off:end], n_tokens,
            ))

    def append_retract(self, block_ids) -> None:
        M = self._max_ids
        for off in range(0, len(block_ids), M):
            self._call(encode_jrnl_retract(self.shard, block_ids[off : off + M]))

    def append_remap(self, keys, new_ids, new_epochs) -> None:
        M = self._max_pub
        for off in range(0, len(keys), M):
            end = off + M
            self._call(encode_jrnl_remap(
                self.shard, keys[off:end], new_ids[off:end], new_epochs[off:end]
            ))


class PoolRpcClient:
    """Worker-side proxy for the pool allocator (one ring round-trip per
    op, chunked at slot capacity).

    Allocation is ATOMIC across chunks: if a later chunk hits
    ``OutOfPoolMemory``, every block the earlier chunks handed out is
    released before the error re-raises — the caller never leaks a
    partial allocation.  The error itself is recognized in the in-band
    ``RpcError`` frame ("OutOfPoolMemory: ...") and re-raised with its
    real type so ``KVCacheManager``'s evict-and-retry path is oblivious
    to the process boundary.
    """

    def __init__(self, rpc, n_blocks: int, max_payload: int | None = None):
        self.rpc = rpc
        self.n_blocks = n_blocks
        if max_payload is None:
            max_payload = getattr(
                getattr(rpc, "ring", None), "payload_bytes", 1 << 20
            )
        self._max_ids = max(1, (max_payload - 16) // 8)
        # keyed allocation ships 16 B per key in the request
        self._max_keyed = max(1, (max_payload - 16) // KEY_BYTES)

    def _call(self, payload: bytes) -> bytes:
        try:
            return self.rpc.call(payload)
        except ServiceDiedError:
            raise
        except RpcError as e:
            msg = str(e)
            if msg.startswith("OutOfPoolMemory"):
                _, _, detail = msg.partition(": ")
                raise OutOfPoolMemory(detail or msg) from e
            raise

    def allocate(self, n: int, keys=None) -> list[int]:
        out: list[int] = []
        M = self._max_ids if keys is None else self._max_keyed
        try:
            while len(out) < n:
                k = min(n - len(out), M)
                if keys is None:
                    msg = encode_pool_alloc(k)
                else:  # tiered parent: ghost-LRU admission sees the keys
                    msg = encode_pool_alloc_keys(
                        keys[len(out) : len(out) + k]
                    )
                out.extend(decode_pool_alloc_resp(self._call(msg)))
        except OutOfPoolMemory:
            if out:
                self.release(out)  # atomic: no partial allocation leaks
            raise
        return out

    def touch_demand(self, block_ids, now: float) -> tuple[int, ...]:
        """Ship the fetch-path demand signal to the tiered pool owner;
        returns the summed per-tier counts of the touched blocks."""
        totals: list[int] = []
        M = self._max_ids
        for off in range(0, len(block_ids), M):
            counts = decode_pool_touch_resp(
                self._call(encode_pool_touch(block_ids[off : off + M], now))
            )
            if len(counts) > len(totals):
                totals.extend([0] * (len(counts) - len(totals)))
            for i, c in enumerate(counts):
                totals[i] += c
        return tuple(totals) if totals else (0, 0)

    def retain(self, block_ids) -> None:
        for off in range(0, len(block_ids), self._max_ids):
            self._call(encode_pool_retain(block_ids[off : off + self._max_ids]))

    def release(self, block_ids) -> None:
        for off in range(0, len(block_ids), self._max_ids):
            self._call(encode_pool_release(block_ids[off : off + self._max_ids]))

    def free_blocks(self) -> int:
        return decode_pool_free_resp(self._call(encode_pool_free()))[0]

    def alloc_count(self) -> int:
        return decode_pool_free_resp(self._call(encode_pool_free()))[1]
