"""Serving runtime: engine/scheduler behavior, elastic scaling, real e2e."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.pool import PoolLayout
from repro.kvcache.hbm_cache import HbmPagedCache, OutOfHbmBlocks
from repro.serving.request import Request, summarize
from repro.serving.scheduler import Cluster, ClusterConfig


LAYOUT = PoolLayout(block_tokens=16, n_layers_kv=4, n_kv_heads=2, head_dim=8)


def _reqs(n, in_len=512, out_len=8, tag="r", arrival=0.0, shared_frac=0.5):
    base = list(range(in_len))
    reqs = []
    for i in range(n):
        cut = int(in_len * shared_frac)
        toks = base[:cut] + [10_000 + i] * (in_len - cut)
        reqs.append(Request(f"{tag}{i}", toks, out_len, arrival))
    return reqs


def _cluster(**kw):
    kw.setdefault("n_engines", 4)
    kw.setdefault("pool_blocks", 8192)
    kw.setdefault("hbm_slots_per_engine", 512)
    return Cluster(ClusterConfig(**kw), LAYOUT)


# ---------------------------------------------------------------------------
# hbm paged cache
# ---------------------------------------------------------------------------


def test_hbm_cache_lifecycle():
    h = HbmPagedCache(16, 16)
    slots = h.allocate(4, keys=[b"a", b"b", b"c", b"d"])
    h.register_sequence("s1", slots)
    new = h.extend_sequence("s1", 16, 64)
    assert len(h.table("s1")) == 5 and len(new) == 1
    h.finish_sequence("s1")
    assert h.free_slots() == 16
    with pytest.raises(OutOfHbmBlocks):
        h.allocate(17)


def test_hbm_shared_key_refcount():
    h = HbmPagedCache(8, 16)
    [s] = h.allocate(1, keys=[b"k"])
    assert h.lookup_shared(b"k") == s  # refcount 2 now
    h.release([s])
    assert h.lookup_shared(b"k") == s  # still alive
    h.release([s])
    h.release([s])
    assert h.lookup_shared(b"k") is None
    assert h.free_slots() == 8


# ---------------------------------------------------------------------------
# engine / cluster
# ---------------------------------------------------------------------------


def test_cluster_all_requests_complete():
    c = _cluster()
    for r in _reqs(24):
        c.dispatch(r)
    stats = c.run()
    assert stats["n_done"] == 24
    assert stats["avg_ttft_s"] > 0


def test_cache_hit_run_is_faster_and_hits():
    c = _cluster(transfer_mode="beluga")
    for r in _reqs(16):
        c.dispatch(r)
    s1 = c.run()
    t0 = max(e.clock for e in c.engines)
    for r in _reqs(16, tag="h", arrival=t0):
        c.dispatch(r)
    c.run()
    hits = [r for r in c.requests if r.req_id.startswith("h")]
    s2 = summarize(hits, max(r.t_done for r in hits) - t0)
    assert s2["hit_tokens"] > 0
    assert s2["avg_ttft_s"] < s1["avg_ttft_s"]


def test_beluga_beats_rdma_on_hits():
    res = {}
    for mode in ("beluga", "rdma"):
        c = _cluster(transfer_mode=mode, super_block_tokens=256 if mode == "rdma" else 0)
        for r in _reqs(16, in_len=2048):
            c.dispatch(r)
        c.run()
        t0 = max(e.clock for e in c.engines)
        for r in _reqs(16, in_len=2048, tag="h", arrival=t0):
            c.dispatch(r)
        c.run()
        hits = [r for r in c.requests if r.req_id.startswith("h")]
        res[mode] = summarize(hits, max(r.t_done for r in hits) - t0)
    assert res["beluga"]["avg_ttft_s"] < res["rdma"]["avg_ttft_s"]


def test_straggler_cutover_bounds_fetch():
    """With the cutover on, a pathologically slow fetch path falls back to
    recompute instead of waiting (paper §6.3 / beyond-paper mitigation)."""
    c = _cluster(transfer_mode="rdma", super_block_tokens=16,
                 straggler_cutover=1.0)
    for r in _reqs(8, in_len=4096):
        c.dispatch(r)
    c.run()
    t0 = max(e.clock for e in c.engines)
    for r in _reqs(8, in_len=4096, tag="h", arrival=t0):
        c.dispatch(r)
    c.run()
    cutovers = sum(e.manager.stats.recompute_cutovers for e in c.engines)
    assert cutovers > 0


def test_elastic_remove_engine_requeues_and_completes():
    c = _cluster()
    for r in _reqs(20, out_len=64):
        c.dispatch(r)
    for e in c.engines:
        e.advance(0.5)  # partial progress
    orphans = c.remove_engine(0)  # simulate instance failure
    stats = c.run()
    assert stats["n_done"] == 20  # everything still completes
    assert len(c.engines) == 3


def test_remove_engine_redispatch_is_linear_and_preserves_order(monkeypatch):
    """k orphans -> exactly k routing decisions + k submits, no duplicate
    append to (or O(n) scan of) ``cluster.requests``, original order kept."""
    c = _cluster()
    for r in _reqs(20, out_len=64):
        c.dispatch(r)
    order_before = list(c.requests)
    routed = []
    orig_select = c._select_engine
    monkeypatch.setattr(
        c, "_select_engine", lambda r: routed.append(r) or orig_select(r)
    )
    monkeypatch.setattr(
        c, "dispatch",
        lambda r: pytest.fail("orphan re-dispatch must not re-append"),
    )
    orphans = c.remove_engine(0)
    assert len(routed) == len(orphans) > 0  # O(k) dispatches
    assert c.requests == order_before  # same objects, same order, no dupes
    queued = [r for e in c.engines for r in e.waiting]
    assert sum(1 for r in queued if r in orphans) == len(orphans)
    stats = c.run()
    assert stats["n_done"] == 20


def test_admit_survives_fetch_failure_with_full_recompute(monkeypatch):
    """A fetch_into_hbm failure mid-admission must fall back to recompute
    (empty sequence registered), not KeyError on the table lookup."""
    c = _cluster(n_engines=1)
    for r in _reqs(2, tag="p"):
        c.dispatch(r)
    c.run()  # populate the pool so the next round has prefix hits
    t0 = max(e.clock for e in c.engines)
    eng = c.engines[0]

    def boom(seq_id, plan):
        raise RuntimeError("injected fetch failure")

    monkeypatch.setattr(eng.manager, "fetch_into_hbm", boom)
    reqs = _reqs(2, tag="h", arrival=t0)
    for r in reqs:
        c.dispatch(r)
    c.run()
    assert all(r.state == "done" for r in reqs)
    assert all(r.tokens_out == r.n_output for r in reqs)
    assert eng.manager.hbm.free_slots() == eng.manager.hbm.n_slots


def test_fetch_failure_rolls_back_slots_and_registers_empty_seq():
    """Manager-level hardening: an epoch race inside scatter_read leaks
    neither pool refs nor HBM slots, and the sequence table exists."""
    from repro.core.coherence import CoherenceError

    c = _cluster(n_engines=1)
    for r in _reqs(1, tag="p"):
        c.dispatch(r)
    c.run()
    mgr = c.engines[0].manager
    plan = mgr.plan_fetch(_reqs(1, tag="x")[0].tokens)
    assert plan.hit_blocks
    # rewrite every hit block between plan and fetch: epochs move on
    stale = [b for _, b, _ in plan.hit_blocks]
    mgr.pool.write_blocks(stale)
    free_before = mgr.hbm.free_slots()
    with pytest.raises(CoherenceError):
        mgr.fetch_into_hbm("victim", plan)
    assert mgr.hbm.seq_tables["victim"] == []
    assert mgr.hbm.free_slots() == free_before
    assert (mgr.pool.refcounts >= 0).all()


def test_hbm_has_key_is_public_locality_probe():
    h = HbmPagedCache(8, 16)
    [s] = h.allocate(1, keys=[b"k"])
    assert h.has_key(b"k")
    assert not h.has_key(b"other")
    assert h.refcounts[s] == 1  # no refcount side effect (vs lookup_shared)
    h.release([s])
    assert not h.has_key(b"k")


def test_submit_is_not_a_clock_barrier():
    """Pre-dispatching an open-loop stream with future arrivals must not
    fast-forward the engine clock (the old ``clock = max(clock, now)``
    inflated TTFT for every earlier request); the clock only advances to
    an arrival when the engine actually idles up to it."""
    c = _cluster(n_engines=1)
    eng = c.engines[0]
    early = _reqs(1, in_len=256, out_len=4)[0]
    late = _reqs(1, in_len=256, out_len=4, tag="late", arrival=100.0)[0]
    c.dispatch(early)
    c.dispatch(late)  # pre-dispatched, arrives at t=100
    assert eng.clock == 0.0  # submit left the clock alone
    eng.advance(1.0)
    assert early.t_done is not None and early.ttft < 1.0
    assert eng.clock < 100.0
    assert eng.n_queued == 1 and eng.next_arrival() == 100.0
    c.run()
    assert late.state == "done" and late.t_first_token >= 100.0


def test_drain_survives_arrival_gaps_beyond_advance_horizon():
    """Without the submit clock barrier, a pre-dispatched request arriving
    further out than one drain window (3600 s) must still be served —
    drain's horizon has to reach the next arrival, not misread the idle
    gap as a capacity deadlock."""
    c = _cluster(n_engines=1)
    a = _reqs(1, in_len=256, out_len=4)[0]
    b = _reqs(1, in_len=256, out_len=4, tag="b", arrival=5000.0)[0]
    c.dispatch(a)
    c.dispatch(b)
    stats = c.run()
    assert stats["n_done"] == 2
    assert a.state == "done" and b.state == "done"
    assert b.t_first_token >= 5000.0


def test_elastic_add_engine_no_rebalance_needed():
    c = _cluster(transfer_mode="beluga")
    for r in _reqs(12):
        c.dispatch(r)
    c.run()
    t0 = max(e.clock for e in c.engines)
    eng = c.add_engine()  # scale out; pool is shared -> no KV migration
    reqs = _reqs(4, tag="h", arrival=t0)
    for r in reqs:
        eng.submit(r, t0)
        c.requests.append(r)
    c.run()
    assert all(r.state == "done" for r in reqs)
    assert any(r.hit_tokens > 0 for r in reqs)  # new engine reads old KV


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(1, 20),
    in_len=st.sampled_from([64, 256, 1024]),
    policy=st.sampled_from(["cache_oblivious", "cache_aware", "round_robin"]),
)
def test_cluster_liveness_property(n, in_len, policy):
    """Every dispatched request finishes with sane timestamps, any policy."""
    c = _cluster(policy=policy)
    for r in _reqs(n, in_len=in_len, out_len=4):
        c.dispatch(r)
    stats = c.run()
    assert stats["n_done"] == n
    for r in c.requests:
        assert r.t_done >= r.t_first_token >= r.arrival
        assert r.tokens_out == r.n_output
    # no leaked HBM slots
    for e in c.engines:
        assert e.manager.hbm.free_slots() == e.manager.hbm.n_slots


# ---------------------------------------------------------------------------
# real end-to-end engine (actual tokens, actual pool reuse)
# ---------------------------------------------------------------------------


def test_real_engine_pool_reuse_is_exact():
    from repro.serving.real_runner import RealEngine

    eng = RealEngine.create(
        "olmo-1b", max_len=96, pool_blocks=64, kernel_mode="interpret"
    )
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, eng.cfg.vocab_size, size=48).tolist()
    out1, info1 = eng.generate(prompt, max_new=8)
    assert info1["hit_tokens"] == 0
    out2, info2 = eng.generate(prompt, max_new=8)
    assert info2["hit_tokens"] == 48  # full-prefix pool hit
    assert out1 == out2  # pool roundtrip preserves numerics exactly


def test_real_engine_params_are_program_arguments():
    """The weights reach the compiled prefill, decode and tail extend as
    arguments of ``main``, not as constants baked into the program."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.serving.real_runner import RealEngine

    eng = RealEngine.create("olmo-1b", max_len=64, pool_blocks=64, kernel_mode="jnp")
    n_params = len(jax.tree.leaves(eng.params))
    batch = {"tokens": jnp.zeros((1, 32), jnp.int32)}

    def n_main_args(lowered) -> int:
        sig = re.search(r"func\.func public @main\((.*?)\) ->", lowered.as_text())
        return len(re.findall(r"%arg\d+:", sig.group(1)))

    assert n_main_args(eng._prefill.lower(eng.params, batch)) == n_params + 1
    _, cache = eng._prefill(eng.params, batch)
    tok = jnp.zeros((1,), jnp.int32)
    low = eng._decode.lower(eng.params, cache, tok, tok)
    assert n_main_args(low) == n_params + len(jax.tree.leaves(cache)) + 2
    chunk = np.zeros((1, 64), np.int32)
    low = eng._extend.lower(eng.params, cache, chunk, np.int32(32), np.int32(8))
    assert n_main_args(low) == n_params + len(jax.tree.leaves(cache)) + 3
