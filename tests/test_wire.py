"""Metadata wire protocol + RPC-ring hardening (PR 3).

Covers the ISSUE-3 satellite surface:
  * wire codec round-trip + truncation/garbage fuzz (never a crash,
    always ``WireError`` for malformed frames);
  * ``RpcIndexClient`` equivalence against the in-process ``GlobalIndex``,
    including chunked ops through a tiny ring slot;
  * timeout slot quarantine: a timed-out slot is NOT recycled while the
    server still owes it a response, so a late response can never leak
    into an unrelated caller;
  * concurrent clients under slot exhaustion;
  * ``keys_for`` aliasing: the shared cached chain is immutable and
    mutating the caller's token list cannot poison the memo;
  * the flat-array index internals (LRU order, growth, batch splice).
"""

import threading
import time

import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core import wire
from repro.core.index import GlobalIndex, PrefixHasher
from repro.core.pool import BelugaPool, PoolLayout
from repro.core.rpc import (
    IDLE,
    REQ_READY,
    RESP_ERROR,
    RESP_READY,
    CxlRpcClient,
    CxlRpcServer,
    RpcError,
    ShmRing,
)

LAYOUT = PoolLayout(block_tokens=16, n_layers_kv=4, n_kv_heads=2, head_dim=8)


def _pool(n_blocks=1024, **kw):
    return BelugaPool(LAYOUT, n_blocks=n_blocks, n_shards=8, backing="meta", **kw)


def _published(n_chains=3, chain_len=8):
    pool = _pool()
    idx = GlobalIndex(pool)
    chains = []
    for d in range(n_chains):
        tokens = [d * 10_000 + i for i in range(chain_len * 16)]
        keys = idx.keys_for(tokens)
        blocks = pool.allocate(len(keys))
        idx.publish_many(keys, blocks, pool.write_blocks(blocks), 16)
        chains.append((tokens, keys, blocks))
    return pool, idx, chains


# ---------------------------------------------------------------------------
# codec round-trips + fuzz
# ---------------------------------------------------------------------------


def test_wire_roundtrip_all_ops():
    pool, idx, chains = _published()
    tokens, keys, blocks = chains[0]
    # match
    ids, eps = wire.decode_match_resp(
        wire.handle_request(idx, wire.encode_match(keys))
    )
    assert ids.tolist() == blocks
    # lookup with a hole
    probe = list(keys[:3]) + [b"\x99" * 16]
    li, le, lt = wire.decode_lookup_resp(
        wire.handle_request(idx, wire.encode_lookup(probe))
    )
    assert li.tolist()[:3] == blocks[:3] and li[3] == -1
    assert lt.tolist()[:3] == [16, 16, 16]
    # filter: everything valid -> empty; poke a hole -> position comes back
    assert wire.decode_filter_resp(
        wire.handle_request(idx, wire.encode_filter(keys))
    ) == []
    pool.release([blocks[2]])
    assert wire.decode_filter_resp(
        wire.handle_request(idx, wire.encode_filter(keys))
    ) == [2]
    # publish the hole back
    [nb] = pool.allocate(1)
    [ne] = pool.write_blocks([nb])
    n = wire.decode_publish_resp(
        wire.handle_request(idx, wire.encode_publish([keys[2]], [nb], [ne], 16))
    )
    assert n == 1
    assert idx.lookup(keys[2]).block_id == nb
    # evict
    freed = wire.decode_evict_resp(
        wire.handle_request(idx, wire.encode_evict(2))
    )
    assert len(freed) == 2
    # batch: two ops in one envelope
    resps = wire.decode_batch_resp(
        wire.handle_request(
            idx, wire.encode_batch([wire.encode_match(keys), wire.encode_evict(1)])
        )
    )
    assert len(resps) == 2


def test_wire_rejects_malformed():
    _, idx, _ = _published(1, 2)
    with pytest.raises(wire.WireError):
        wire.handle_request(idx, b"")
    with pytest.raises(wire.WireError):
        wire.handle_request(idx, bytes([99, 0, 0, 0, 0]))  # unknown op
    good = wire.encode_match([b"k" * 16, b"j" * 16])
    for cut in (1, 4, len(good) - 1):
        with pytest.raises(wire.WireError):
            wire.handle_request(idx, good[:cut])
    with pytest.raises(wire.WireError):
        wire.encode_match([b"short"])  # not a 16-byte digest


def test_publish_many_duplicate_key_resolves_to_last_occurrence():
    """A batch carrying the same key twice (only craftable via a wire
    OP_PUBLISH) must not leave a stale block->row reverse pointer at the
    first occurrence's block (regression vs the per-key seed loop)."""
    pool = _pool()
    idx = GlobalIndex(pool)
    [b1, b2] = pool.allocate(2)
    [e1, e2] = pool.write_blocks([b1, b2])
    k = b"\x42" * 16
    wire.handle_request(idx, wire.encode_publish([k, k], [b1, b2], [e1, e2], 16))
    assert idx.lookup(k).block_id == b2  # last occurrence wins
    assert idx.keys_of_blocks([b1, b2]) == [None, k]
    # evicting the orphaned first block must be a no-op, not destroy k
    assert idx.evict_blocks([b1]) == []
    assert idx.lookup(k) is not None
    assert idx.evict_blocks([b2]) == [b2]
    assert idx.lookup(k) is None


def test_wire_publish_rejects_out_of_range_block_ids():
    """Untrusted block ids must not scatter into block2row (negative ids
    would silently alias another block's owner pointer)."""
    pool, idx, chains = _published(1, 2)
    k = b"\x07" * 16
    for bad in (-1, pool.n_blocks, pool.n_blocks + 5):
        with pytest.raises(wire.WireError):
            wire.handle_request(idx, wire.encode_publish([k], [bad], [1], 16))
    assert idx.lookup(k) is None  # nothing was inserted
    # pre-existing entries untouched
    assert idx.keys_of_blocks(chains[0][2]) == list(chains[0][1])


def test_wire_reply_bound_rejects_before_mutation():
    """An op whose REPLY cannot fit the slot is refused up front — the
    index must not mutate server-side while the client only sees an
    error (e.g. an oversized EVICT silently freeing blocks)."""
    pool, idx, chains = _published(n_chains=1, chain_len=50)
    ring = ShmRing(n_slots=4, payload_bytes=128)
    server = CxlRpcServer(
        ring, wire.make_index_handler(idx, max_reply=ring.payload_bytes)
    ).start()
    try:
        client = CxlRpcClient(ring)
        entries_before = idx.stats()["entries"]
        with pytest.raises(RpcError):
            client.call(wire.encode_evict(1000))  # reply needs 8 KB
        assert idx.stats()["entries"] == entries_before  # NOT half-run
        # same guard for an EVICT smuggled through OP_BATCH (which the
        # proxy's per-op chunking does not cover)
        with pytest.raises(RpcError):
            client.call(wire.encode_batch([wire.encode_evict(1000)]))
        assert idx.stats()["entries"] == entries_before
        # a BATCH whose LATER sub-op is body-truncated must fail before
        # its leading mutating sub-op runs
        import struct as _struct

        bad_tail = _struct.pack("<BI", wire.OP_MATCH, 100)  # claims 100 keys
        with pytest.raises(RpcError):
            client.call(wire.encode_batch([wire.encode_evict(3), bad_tail]))
        assert idx.stats()["entries"] == entries_before
        # ... and the same for a SEMANTICALLY invalid later sub-op
        # (out-of-range publish): the batch starts clean or not at all
        bad_pub = wire.encode_publish([b"\x01" * 16], [10**6], [1], 16)
        with pytest.raises(RpcError):
            client.call(wire.encode_batch([wire.encode_evict(3), bad_pub]))
        assert idx.stats()["entries"] == entries_before
        # a fitting evict still works
        freed = wire.decode_evict_resp(client.call(wire.encode_evict(4)))
        assert len(freed) == 4
    finally:
        server.stop()


def test_wire_match_rejects_duplicate_keys():
    """Duplicate keys in one MATCH chain are invalid (chain hashes never
    repeat) and would corrupt the batch LRU splice — rejected up front."""
    _, idx, chains = _published(1, 4)
    k = chains[0][1][0]
    with pytest.raises(wire.WireError):
        wire.handle_request(idx, wire.encode_match([k, k]))
    # the LRU list is untouched: normal traffic still works
    assert len(idx.match_prefix(chains[0][0])) == 4
    assert idx.evict_lru(4) == chains[0][2]


def test_wire_batch_nesting_is_bounded():
    """A BATCH-of-BATCH bomb must fail as WireError, not RecursionError."""
    _, idx, chains = _published(1, 2)
    msg = wire.encode_match(chains[0][1])
    for _ in range(2000):
        msg = wire.encode_batch([msg])
    with pytest.raises(wire.WireError):
        wire.handle_request(idx, msg)
    # shallow nesting still works
    shallow = wire.encode_batch([wire.encode_batch([wire.encode_evict(0)])])
    wire.handle_request(idx, shallow)


@settings(max_examples=30, deadline=None)
@given(st.binary(min_size=0, max_size=200))
@example(b"\x14\x00\x00\x00\x00")  # SEED_STATS cut short after its header
def test_wire_fuzz_never_crashes(blob):
    """Arbitrary bytes either decode to a valid op or raise WireError."""
    pool = _pool(64)
    idx = GlobalIndex(pool)
    try:
        wire.handle_request(idx, blob)
    except wire.WireError:
        pass


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 40),
    n_tokens=st.integers(1, 4096),
    seed=st.integers(0, 2**31),
)
def test_wire_publish_match_property(n, n_tokens, seed):
    """encode->handle->decode publish+match round-trips arbitrary rows."""
    rng = np.random.default_rng(seed)
    pool = _pool()
    idx = GlobalIndex(pool)
    keys = [rng.bytes(16) for _ in range(n)]
    blocks = pool.allocate(n)
    epochs = pool.write_blocks(blocks)
    wire.handle_request(idx, wire.encode_publish(keys, blocks, epochs, n_tokens))
    ids, eps = wire.decode_match_resp(
        wire.handle_request(idx, wire.encode_match(keys))
    )
    assert ids.tolist() == blocks and eps.tolist() == epochs


def test_wire_migration_ops_roundtrip():
    """OWNERS / REMAP / EVICT_BLOCKS — the migrator's control plane —
    behave over the codec exactly like the in-process index calls."""
    pool, idx, chains = _published(n_chains=1, chain_len=6)
    tokens, keys, blocks = chains[0]
    # OWNERS: only indexed blocks answer, input order, epochs attached
    [free] = pool.allocate(1)
    k, b, e = wire.decode_owners_resp(
        wire.handle_request(idx, wire.encode_owners(blocks[:3] + [free]))
    )
    assert (k, b) == (list(keys[:3]), blocks[:3])
    ref = idx.owners_of(blocks[:3] + [free])
    assert (k, b, e) == ref
    # REMAP: stale (old_id, old_epoch) loses the race, fresh one wins
    [nb] = pool.allocate(1)
    [ne] = pool.write_blocks([nb])
    ok = wire.decode_remap_resp(
        wire.handle_request(
            idx,
            wire.encode_remap(
                [keys[0], keys[1]], [blocks[0], blocks[1]], [e[0], 10**6],
                [nb, nb], [ne, ne],
            ),
        )
    )
    assert ok == [True, False]  # second had a wrong old epoch
    assert idx.lookup(keys[0]).block_id == nb
    assert idx.lookup(keys[1]).block_id == blocks[1]
    # EVICT_BLOCKS: frees exactly the indexed, unreferenced targets
    freed = wire.decode_evict_resp(
        wire.handle_request(idx, wire.encode_evict_blocks([nb, blocks[1], free]))
    )
    assert freed == [nb, blocks[1]]
    assert idx.lookup(keys[0]) is None and idx.lookup(keys[1]) is None


def test_wire_stats_op_roundtrip():
    """OP_STATS mirrors GlobalIndex.stats — the probe the cluster uses
    when the index lives in another process, and the occupancy signal of
    the sharded eviction policy."""
    pool, idx, chains = _published(n_chains=2, chain_len=5)
    idx.match_prefix(chains[0][0])
    idx.match_prefix(chains[1][0][: 3 * 16] + [-1] * 16)  # 3 hits + misses
    entries, hits, misses, ops, busy = wire.decode_stats_resp(
        wire.handle_request(idx, wire.encode_stats())
    )
    s = idx.stats()
    assert (entries, hits, misses) == (s["entries"], s["hits"], s["misses"])
    # service-side timer fields ride the same reply; without a ring ctrl
    # block wired in they read 0 (handle_request called directly here)
    assert (ops, busy) == (0, 0)
    assert wire.reply_bound(wire.encode_stats()) == 40
    # and over a live ring via the proxy (hit_rate computed client-side)
    ring = ShmRing(n_slots=2, payload_bytes=256)
    server = CxlRpcServer(ring, wire.make_index_handler(idx)).start()
    try:
        proxy = wire.RpcIndexClient(CxlRpcClient(ring), block_tokens=16)
        assert proxy.stats() == idx.stats()
        assert proxy.n_entries() == s["entries"]
    finally:
        server.stop()


def test_evict_never_rereleases_stale_rows():
    """Eviction-safety regression (found by the differential harness):
    a row whose block was already released — refcount 0, epoch bumped,
    possibly REALLOCATED to a new owner — must be GC'd by evict_lru /
    evict_blocks WITHOUT a second pool.release.  The old refcount<=1
    victim rule double-freed it (and against a reallocated block would
    have freed the new owner's live payload)."""
    pool, idx, chains = _published(n_chains=1, chain_len=6)
    tokens, keys, blocks = chains[0]
    pool.release([blocks[1], blocks[4]])  # stale rows, refcount 0
    free_before = pool.free_blocks()
    # evict_lru walks past the stale rows: they are dropped, not "freed"
    freed = idx.evict_lru(2)
    assert freed == [blocks[0], blocks[2]]  # live LRU victims only
    assert idx.lookup(keys[1]) is None  # stale row GC'd
    assert pool.free_blocks() == free_before + 2  # no double count
    # evict_blocks on a stale target: same rule
    assert idx.evict_blocks([blocks[4]]) == []
    assert idx.lookup(keys[4]) is None
    assert pool.free_blocks() == free_before + 2
    # a REALLOCATED block with a SURVIVING stale row must not be freed
    # out from under its new owner: publish a fresh key, release its
    # block (stale row, never walked), then reallocate that same block
    k = b"\x55" * 16
    [b] = pool.allocate(1)
    idx.publish(k, b, pool.write_blocks([b])[0], 16)
    pool.release([b])  # stale row for k survives, b back in the free pool
    got, held = [], []
    while b not in got:  # reacquire b (bounded: pool is finite)
        got = pool.allocate(1)
        held += got
    assert idx.lookup(k) is not None  # the stale row is still there
    assert idx.evict_blocks([b]) == []  # NOT freed under its new owner
    assert pool.refcounts[b] == 1  # new owner untouched
    assert idx.lookup(k) is None  # stale row GC'd instead
    pool.release(held)
    # on_evict (ghost arming) never fires for stale-row GC
    seen = []
    idx.on_evict = seen.append
    pool.release([blocks[5]])
    assert idx.evict_lru(10) == [blocks[3]]
    assert seen == [[keys[3]]]


def test_wire_migration_ops_reject_out_of_range_ids():
    pool, idx, chains = _published(1, 2)
    keys, blocks = chains[0][1], chains[0][2]
    bad = pool.n_blocks + 7
    for msg in (
        wire.encode_owners([bad]),
        wire.encode_evict_blocks([-1]),
        wire.encode_remap([keys[0]], [bad], [1], [blocks[0]], [1]),
        wire.encode_remap([keys[0]], [blocks[0]], [1], [-2], [1]),
    ):
        with pytest.raises(wire.WireError):
            wire.handle_request(idx, msg)
        with pytest.raises(wire.WireError):
            wire.prevalidate(idx, msg)
    # nothing mutated
    assert idx.keys_of_blocks(blocks) == list(keys)


# ---------------------------------------------------------------------------
# RpcIndexClient over a live ring
# ---------------------------------------------------------------------------


def test_rpc_index_client_matches_in_process_index():
    pool, idx, chains = _published(n_chains=2, chain_len=20)
    ring = ShmRing(n_slots=8, payload_bytes=4096)
    server = CxlRpcServer(ring, wire.make_index_handler(idx)).start()
    try:
        proxy = wire.RpcIndexClient(CxlRpcClient(ring), block_tokens=16)
        for tokens, keys, blocks in chains:
            assert proxy.match_prefix(tokens) == idx.match_prefix(tokens)
            assert proxy.filter_unpublished(keys) == []
            got = proxy.lookup_many(keys)
            assert [e.block_id for e in got] == blocks
        # divergent suffix matches the shared prefix only
        tokens = chains[0][0]
        assert len(proxy.match_prefix(tokens[:64] + [5] * 32)) == 4
    finally:
        server.stop()


def test_rpc_index_client_chunks_long_chains():
    """A chain longer than one ring slot splits without changing results."""
    pool, idx, chains = _published(n_chains=1, chain_len=40)
    tokens, keys, blocks = chains[0]
    ring = ShmRing(n_slots=4, payload_bytes=256)  # ~15 keys per slot
    server = CxlRpcServer(ring, wire.make_index_handler(idx)).start()
    try:
        proxy = wire.RpcIndexClient(CxlRpcClient(ring), block_tokens=16)
        assert proxy._max_match < len(keys)
        assert [b for _, b, _ in proxy.match_prefix(tokens)] == blocks
        pool.release([blocks[1]])  # early stale: later chunks must not run
        assert len(proxy.match_prefix(tokens)) == 1
        assert proxy.filter_unpublished(keys) == [1]
    finally:
        server.stop()


def test_rpc_index_client_chunks_evict_lru():
    """The EVICT response carries 8 B per freed id, so big evictions must
    split client-side instead of overflowing the reply slot."""
    pool, idx, chains = _published(n_chains=1, chain_len=60)
    ring = ShmRing(n_slots=4, payload_bytes=128)  # <= 14 ids per response
    server = CxlRpcServer(ring, wire.make_index_handler(idx)).start()
    try:
        proxy = wire.RpcIndexClient(CxlRpcClient(ring), block_tokens=16)
        assert proxy._max_evict < 60
        freed = proxy.evict_lru(60)
        assert sorted(freed) == sorted(chains[0][2])
        assert idx.stats()["entries"] == 0
    finally:
        server.stop()


def test_server_survives_handler_failure():
    """A malformed frame (or any handler exception) comes back as an
    in-band RpcError; the metadata service thread keeps serving."""
    pool, idx, chains = _published(1, 4)
    ring = ShmRing(n_slots=4, payload_bytes=1024)
    server = CxlRpcServer(ring, wire.make_index_handler(idx)).start()
    try:
        client = CxlRpcClient(ring)
        proxy = wire.RpcIndexClient(client, block_tokens=16)
        with pytest.raises(RpcError):
            client.call(wire.encode_match(chains[0][1])[:10])  # truncated
        assert server._thread.is_alive()
        # well-formed traffic flows normally afterwards
        assert len(proxy.match_prefix(chains[0][0])) == 4
        assert client.free_slots() == ring.n_slots
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# RPC error accounting + in-band error frames
# ---------------------------------------------------------------------------


def test_rpc_stats_account_failed_round_trips():
    """RESP_ERROR and timeouts must be VISIBLE in RpcStats: counted, and
    their wait time folded into total_wait (the old client raised before
    touching the stats, so error-heavy runs looked like rosy QD=1 runs
    over the successes only)."""
    gate = threading.Event()

    def handler(payload: bytes) -> bytes:
        if payload == b"hang":
            gate.wait(5)
            return b"late"
        if payload == b"boom":
            raise ValueError("no")
        return payload

    ring = ShmRing(n_slots=2, payload_bytes=64)
    server = CxlRpcServer(ring, handler).start()
    try:
        client = CxlRpcClient(ring)
        client.call(b"fine")
        with pytest.raises(RpcError):
            client.call(b"boom")
        wait_after_error = client.stats.total_wait
        with pytest.raises(TimeoutError):
            client.call(b"hang", timeout=0.05)
        s = client.stats
        assert (s.requests, s.errors, s.timeouts) == (1, 1, 1)
        assert s.round_trips == 3
        # the timeout contributed >= its 50 ms deadline of wait
        assert s.total_wait >= wait_after_error + 0.05
        assert s.avg_wait() == s.total_wait / 3
    finally:
        gate.set()
        server.stop()


def test_error_frame_truncates_on_utf8_character_boundary():
    """A long non-ASCII handler error must be cut on a CHARACTER boundary
    when it exceeds the slot: the byte-slice truncation could split a
    multi-byte UTF-8 sequence and ship mojibake to the caller."""
    boom = "кэш-блок недействителен: " + "デ" * 40  # >64 B encoded

    def handler(payload: bytes) -> bytes:
        raise RuntimeError(boom)

    ring = ShmRing(n_slots=1, payload_bytes=64)
    assert len(f"RuntimeError: {boom}".encode()) > ring.payload_bytes
    server = CxlRpcServer(ring, handler).start()
    try:
        client = CxlRpcClient(ring)
        with pytest.raises(RpcError) as ei:
            client.call(b"x")
        msg = str(ei.value)
        assert "�" not in msg  # decoded cleanly: no replacement char
        assert msg.startswith("RuntimeError: кэш-блок")
        assert len(msg.encode()) <= ring.payload_bytes
        # a whole number of characters survived the cut
        full = f"RuntimeError: {boom}"
        assert full.startswith(msg)
    finally:
        server.stop()


def test_post_collect_split_round_trip():
    """post() keeps several requests outstanding; collect() in any order."""
    ring = ShmRing(n_slots=4, payload_bytes=64)
    server = CxlRpcServer(ring, lambda p: b"ok:" + p).start()
    try:
        client = CxlRpcClient(ring)
        slots = [client.post(bytes([65 + i]) * 4) for i in range(3)]
        outs = [client.collect(s) for s in reversed(slots)]
        assert outs == [b"ok:CCCC", b"ok:BBBB", b"ok:AAAA"]
        assert client.free_slots() == 4
        assert client.stats.requests == 3
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# ring hardening: timeout quarantine + slot exhaustion
# ---------------------------------------------------------------------------


def test_timeout_quarantines_slot_until_server_responds():
    ring = ShmRing(n_slots=1, payload_bytes=64)
    release = threading.Event()

    def slow_handler(payload: bytes) -> bytes:
        release.wait(5)
        return b"LATE:" + payload

    server = CxlRpcServer(ring, slow_handler).start()
    try:
        client = CxlRpcClient(ring)
        with pytest.raises(TimeoutError):
            client.call(b"victim", timeout=0.05)
        assert client.stats.timeouts == 1
        # the slot is NOT back on the free list: the only slot is
        # quarantined, so the next call reports exhaustion instead of
        # reusing a slot the server may still write into
        assert client.free_slots() == 0
        with pytest.raises(RuntimeError):
            client.call(b"second")
        # server finally answers the stale request
        release.set()
        deadline = time.time() + 5
        while ring.status[0] != RESP_READY and time.time() < deadline:
            time.sleep(0.01)
        # next acquire reclaims the slot and the late response is
        # dropped, never handed to the new caller
        out = client.call(b"fresh", timeout=5)
        assert out == b"LATE:fresh"
        assert client.free_slots() == 1
    finally:
        release.set()
        server.stop()


def test_concurrent_clients_slot_exhaustion_and_recovery():
    ring = ShmRing(n_slots=2, payload_bytes=64)
    gate = threading.Event()

    def handler(payload: bytes) -> bytes:
        if payload.startswith(b"block"):
            gate.wait(5)
        return bytes((x + 1) % 256 for x in payload)

    server = CxlRpcServer(ring, handler).start()
    try:
        client = CxlRpcClient(ring)
        errors, oks = [], []

        def blocked():
            try:
                oks.append(client.call(b"block", timeout=5))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=blocked) for _ in range(2)]
        for t in ts:
            t.start()
        deadline = time.time() + 5
        while client.free_slots() > 0 and time.time() < deadline:
            time.sleep(0.01)
        # both slots in flight: an extra caller is rejected, not corrupted
        with pytest.raises(RuntimeError):
            client.call(b"extra")
        gate.set()
        for t in ts:
            t.join()
        assert not errors and len(oks) == 2
        # ring fully recovered: responses flow again with correct payloads
        for i in range(8):
            payload = bytes([i]) * 8
            assert client.call(payload) == bytes((x + 1) % 256 for x in payload)
        assert client.free_slots() == 2
    finally:
        gate.set()
        server.stop()


# ---------------------------------------------------------------------------
# keys_for aliasing (shared cached chain) — regression
# ---------------------------------------------------------------------------


def test_keys_for_shared_cache_is_immutable_and_mutation_safe():
    h = PrefixHasher(16)
    tokens = list(range(160))
    first = h.keys_for(tokens)
    assert isinstance(first, tuple)  # structurally immutable: no aliasing bug
    assert h.keys_for(list(tokens)) is first  # shared cached chain
    with pytest.raises(TypeError):
        first[0] = b"boom"  # type: ignore[index]
    # mutating the CALLER's list must not poison the memo for other users
    tokens[32] = -7
    mutated = h.keys_for(tokens)
    assert mutated is not first
    assert mutated[:2] == first[:2] and mutated[2] != first[2]
    assert h.keys_for(list(range(160))) == first


def test_cluster_index_rpc_mode_end_to_end():
    from repro.serving.request import Request
    from repro.serving.scheduler import Cluster, ClusterConfig

    c = Cluster(
        ClusterConfig(
            n_engines=2, pool_blocks=2048, hbm_slots_per_engine=256,
            index_rpc=True, index_rpc_slots=8,
        ),
        LAYOUT,
    )
    try:
        base = list(range(512))
        for i in range(8):
            c.dispatch(Request(f"r{i}", base, 8, 0.0))
        s1 = c.run()
        assert s1["n_done"] == 8
        assert s1["index"]["hits"] > 0  # ops really reached the index
        assert c._rpc_client.stats.requests > 0  # ... over the ring
        t0 = max(e.clock for e in c.engines)
        tail = [Request(f"h{i}", base, 8, t0) for i in range(4)]
        for r in tail:
            c.dispatch(r)
        c.run()
        assert all(r.hit_tokens > 0 for r in tail)  # pool hits via RPC
    finally:
        c.close()


# ---------------------------------------------------------------------------
# flat-array index internals
# ---------------------------------------------------------------------------


def test_index_lru_order_tracks_matches():
    pool, idx, chains = _published(n_chains=3, chain_len=4)
    # touch chains 2 then 0; chain 1 becomes LRU
    idx.match_prefix(chains[2][0])
    idx.match_prefix(chains[0][0])
    freed = idx.evict_lru(4)
    assert sorted(freed) == sorted(chains[1][2])
    assert len(idx.match_prefix(chains[1][0])) == 0
    assert len(idx.match_prefix(chains[0][0])) == 4
    assert len(idx.match_prefix(chains[2][0])) == 4


def test_index_grows_past_initial_capacity():
    pool = BelugaPool(LAYOUT, n_blocks=8192, n_shards=8, backing="meta")
    idx = GlobalIndex(pool)
    tokens = list(range(5000 * 16))  # 5000 rows > initial 1024 capacity
    keys = idx.keys_for(tokens)
    blocks = pool.allocate(len(keys))
    idx.publish_many(keys, blocks, pool.write_blocks(blocks), 16)
    assert idx.stats()["entries"] == 5000
    hits = idx.match_prefix(tokens)
    assert [b for _, b, _ in hits] == blocks


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 5), min_size=1, max_size=30), st.integers(1, 6))
def test_index_lru_eviction_matches_ordered_dict_model(touch_order, n_evict):
    """Eviction order of the array-intrusive LRU == an OrderedDict model
    under an arbitrary interleaving of matches (the batch-splice path)."""
    from collections import OrderedDict

    pool, idx, chains = _published(n_chains=6, chain_len=3)
    model: OrderedDict[int, None] = OrderedDict((d, None) for d in range(6))
    for d in touch_order:
        assert len(idx.match_prefix(chains[d][0])) == 3
        model.move_to_end(d)
    freed = idx.evict_lru(3 * n_evict)
    want: list[int] = []
    for d in list(model)[:n_evict]:
        want.extend(chains[d][2])
    assert freed == want


# ---------------------------------------------------------------------------
# codec exhaustiveness: every OP_* in the registry round-trips (PR 9)
# ---------------------------------------------------------------------------
def _wire_registry() -> dict[str, int]:
    return {
        name: val
        for name, val in vars(wire).items()
        if name.startswith("OP_") and isinstance(val, int)
    }


def test_wire_registry_values_are_unique_and_dense():
    ops = _wire_registry()
    vals = sorted(ops.values())
    assert len(set(vals)) == len(vals), "duplicate opcode values"
    assert vals == list(range(1, len(vals) + 1)), "opcode space has holes"


def test_every_opcode_round_trips_with_boundary_payloads():
    """Exhaustiveness is DERIVED, not hand-maintained: the table below is
    keyed by ``OP_*`` name and the test fails outright if the module's
    registry grows an opcode the table doesn't exercise (the runtime
    companion of the ``wire_protocol`` lint pass).  Each op ships at
    least an empty/zero frame and a populated frame; every reply must
    fit its declared ``reply_bound`` and decode cleanly."""
    from repro.core.shm import ShardJournal

    pool, idx, chains = _published(n_chains=2, chain_len=4)
    tokens, keys, blocks = chains[0]
    keys = list(keys)  # keys_for returns an immutable (cached) tuple
    eps = [idx.lookup_many(keys)[i].epoch for i in range(len(keys))]
    fresh = pool.allocate(len(keys))
    fresh_eps = pool.write_blocks(fresh)
    spare = pool.allocate(4)
    jrnl = ShardJournal.create(capacity=64)
    jkeys = [bytes([i]) * wire.KEY_BYTES for i in range(3)]

    def index_route(frame: bytes) -> tuple[bytes, int]:
        bound = wire.reply_bound(frame)
        wire.prevalidate(idx, frame)
        return wire.handle_request(idx, frame, _validated=True), bound

    def pool_route(frame: bytes) -> tuple[bytes, int]:
        return wire.handle_pool_request(pool, frame), wire.pool_reply_bound(frame)

    # the keyed-alloc / touch ops only exist on tiered parents
    from repro.tiering.tiers import TieredPool, TieringConfig

    tpool = TieredPool(
        LAYOUT, fast_blocks=16, spill_blocks=16, n_shards=4,
        backing="meta", cfg=TieringConfig(enabled=True),
    )
    tblocks = tpool.allocate(4)

    def tiered_route(frame: bytes) -> tuple[bytes, int]:
        return (
            wire.handle_pool_request(tpool, frame),
            wire.pool_reply_bound(frame),
        )

    def jrnl_route(frame: bytes) -> tuple[bytes, int]:
        return (
            wire.handle_journal_request(frame, [jrnl]),
            wire.pool_reply_bound(frame),
        )

    def u32_resp(buf: bytes):
        assert len(buf) == 4
        return buf

    # OP name -> (route, decoder, [boundary frames])
    table = {
        "OP_MATCH": (index_route, wire.decode_match_resp, [
            wire.encode_match([]),
            wire.encode_match(keys),
        ]),
        "OP_PUBLISH": (index_route, wire.decode_publish_resp, [
            wire.encode_publish([], [], [], 0),
            wire.encode_publish(keys, blocks, eps, 16),
        ]),
        "OP_LOOKUP": (index_route, wire.decode_lookup_resp, [
            wire.encode_lookup([]),
            wire.encode_lookup(keys + [b"\xff" * wire.KEY_BYTES]),
        ]),
        "OP_FILTER": (index_route, wire.decode_filter_resp, [
            wire.encode_filter([]),
            wire.encode_filter(keys + [b"\xfe" * wire.KEY_BYTES]),
        ]),
        "OP_EVICT": (index_route, wire.decode_evict_resp, [
            wire.encode_evict(0),
            wire.encode_evict(2),
        ]),
        "OP_BATCH": (index_route, wire.decode_batch_resp, [
            wire.encode_batch([]),
            wire.encode_batch([wire.encode_stats(), wire.encode_match(keys)]),
        ]),
        "OP_OWNERS": (index_route, wire.decode_owners_resp, [
            wire.encode_owners([]),
            wire.encode_owners(blocks + spare),  # spare: unindexed ids
        ]),
        "OP_REMAP": (index_route, wire.decode_remap_resp, [
            wire.encode_remap([], [], [], [], []),
            wire.encode_remap(keys, blocks, eps, fresh, fresh_eps),
        ]),
        "OP_EVICT_BLOCKS": (index_route, wire.decode_evict_resp, [
            wire.encode_evict_blocks([]),
            wire.encode_evict_blocks(spare),  # in range, nothing to evict
        ]),
        "OP_STATS": (index_route, wire.decode_stats_resp, [
            wire.encode_stats(),
        ]),
        "OP_SNAPSHOT": (index_route, wire.decode_snapshot_resp, [
            wire.encode_snapshot(0, 0),
            wire.encode_snapshot(0, 64),
        ]),
        "OP_RESTORE": (index_route, wire.decode_restore_resp, [
            wire.encode_restore([], [], [], []),
            wire.encode_restore(keys, blocks, eps, [16] * len(keys)),
        ]),
        "OP_SEED_STATS": (index_route, u32_resp, [
            wire.encode_seed_stats(0, 0),
            wire.encode_seed_stats(2**40, 2**40),
        ]),
        "OP_POOL_ALLOC": (pool_route, wire.decode_pool_alloc_resp, [
            wire.encode_pool_alloc(0),
            wire.encode_pool_alloc(8),
        ]),
        "OP_POOL_RETAIN": (pool_route, u32_resp, [
            wire.encode_pool_retain([]),
            # published blocks: live refs regardless of table order
            # (OP_POOL_RELEASE sorts earlier and frees `spare`)
            wire.encode_pool_retain(blocks),
        ]),
        "OP_POOL_RELEASE": (pool_route, u32_resp, [
            wire.encode_pool_release([]),
            wire.encode_pool_release(spare),
        ]),
        "OP_POOL_FREE": (pool_route, wire.decode_pool_free_resp, [
            wire.encode_pool_free(),
        ]),
        "OP_POOL_ALLOC_KEYS": (tiered_route, wire.decode_pool_alloc_resp, [
            wire.encode_pool_alloc_keys([]),
            wire.encode_pool_alloc_keys(jkeys),
        ]),
        "OP_POOL_TOUCH": (tiered_route, wire.decode_pool_touch_resp, [
            wire.encode_pool_touch([], 0.0),
            wire.encode_pool_touch(tblocks, 1.0),
        ]),
        "OP_JRNL_PUBLISH": (jrnl_route, u32_resp, [
            wire.encode_jrnl_publish(0, [], [], [], 0),
            wire.encode_jrnl_publish(0, jkeys, [1, 2, 3], [7, 7, 7], 16),
        ]),
        "OP_JRNL_RETRACT": (jrnl_route, u32_resp, [
            wire.encode_jrnl_retract(0, []),
            wire.encode_jrnl_retract(0, [1, 2, 3]),
        ]),
        "OP_JRNL_REMAP": (jrnl_route, u32_resp, [
            wire.encode_jrnl_remap(0, [], [], []),
            wire.encode_jrnl_remap(0, jkeys, [4, 5, 6], [8, 8, 8]),
        ]),
    }

    try:
        registry = _wire_registry()
        missing = set(registry) - set(table)
        stale = set(table) - set(registry)
        assert not missing, f"opcodes without codec coverage: {sorted(missing)}"
        assert not stale, f"table entries for removed opcodes: {sorted(stale)}"

        for name, (route, decoder, frames) in sorted(table.items()):
            assert frames, f"{name}: no boundary frames"
            for frame in frames:
                assert frame[0] == registry[name], f"{name}: wrong op byte"
                reply, bound = route(frame)
                assert len(reply) <= bound, (
                    f"{name}: reply {len(reply)} B exceeds bound {bound} B"
                )
                decoder(reply)  # must decode without raising
    finally:
        jrnl.close()
