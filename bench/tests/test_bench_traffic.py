"""The traffic generator and each cell's plan (CPU only, no model)."""

from __future__ import annotations

import collections

import pytest

from bench import run, traffic

CELLS = [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]]
SECONDS = run.load_json(run.ROOT, "BENCHMARK.json")["run_seconds"]


def plan(name: str, seed: int) -> traffic.Plan:
    cell = run.Cell.load(name)
    return traffic.plan(cell.mix, cell.params["rate_per_s"], cell.sizes["vocab"], seed, SECONDS)


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_requests(name):
    a, b = plan(name, 2**31 + 12345), plan(name, 2**31 + 12345)
    assert [(r.due_s, r.prompt, r.max_new) for r in a.timed] == [
        (r.due_s, r.prompt, r.max_new) for r in b.timed]
    assert a.setup_prompts == b.setup_prompts


@pytest.mark.parametrize("name", CELLS)
def test_seeds_change_tokens_not_the_schedule(name):
    """Every seed gets the same lengths, outputs, prefix choices and due
    times, in the same order; only the tokens differ."""
    a, b = plan(name, 1), plan(name, 2**31 + 5)
    assert a.timed[0].prompt != b.timed[0].prompt
    assert [(len(r.prompt), r.max_new, r.prefix, r.due_s) for r in a.timed] == [
        (len(r.prompt), r.max_new, r.prefix, r.due_s) for r in b.timed]
    assert all(0 <= r.due_s < SECONDS for r in a.timed)
    outs = collections.Counter(r.max_new for r in a.timed)
    assert len(outs) > 1 and min(outs) >= 8 and max(outs) <= 64


@pytest.mark.parametrize("name", CELLS)
def test_timed_shapes_are_warmed_and_fit(name):
    p = plan(name, 7)
    bt = traffic.BLOCK_TOKENS
    warm_lens = {len(r.prompt) for r in p.warm if r.prefix < 0}
    for r in p.timed:
        assert len(r.prompt) + r.max_new <= p.max_len
        if r.prefix >= 0:
            assert len(p.setup_prompts[r.prefix]) // bt in p.shapes["hit_blocks"]
        else:
            assert len(r.prompt) in p.shapes["prefill_tokens"] and len(r.prompt) in warm_lens
            assert len(r.prompt) // bt in p.shapes["write_blocks"]
    for s in p.setup_prompts:
        assert len(s) in p.shapes["prefill_tokens"] and len(s) // bt in p.shapes["write_blocks"]


@pytest.mark.parametrize("name", CELLS)
def test_planned_pool_writes_fit_the_pool(name):
    cell = run.Cell.load(name)
    assert plan(name, 3).pool_writes() <= cell.params["pool_blocks"]


@pytest.mark.parametrize("name", [c for c in CELLS if c.endswith("shared-sysprompt")])
def test_every_timed_shared_request_hits_its_whole_prefix(name):
    from repro.core.index import GlobalIndex
    from repro.core.pool import BelugaPool, PoolLayout

    p = plan(name, 11)
    pool = BelugaPool(PoolLayout(16, 1, 1, 1), n_blocks=4096, backing="meta")
    index = GlobalIndex(pool)
    for prompt in p.setup_prompts:
        keys = index.keys_for(prompt)
        ids = pool.allocate(len(keys))
        index.publish_many(list(keys), ids, pool.write_blocks(ids), 16)
    assert len(p.setup_prompts) == 4 and all(len(s) == 8192 for s in p.setup_prompts)
    for r in p.timed + p.warm:
        assert len(index.match_prefix(r.prompt)) == 512


def test_unshared_prompts_share_nothing():
    p = plan("qwen3-32b-noqknorm-4L.unshared", 5)
    firsts = [tuple(r.prompt[: traffic.BLOCK_TOKENS]) for r in p.timed + p.warm]
    assert len(set(firsts)) == len(firsts) and not p.setup_prompts


def test_sizes_follow_the_mix():
    lu = traffic.draw_sizes({"dist": "log_uniform", "min": 8, "max": 64}, 100)
    assert lu.min() >= 8 and lu.max() <= 64 and 20 < lu.mean() < 32
    grid = traffic.draw_sizes({"dist": "grid", "values": [1, 2], "weights": [0.75, 0.25]}, 8)
    assert sorted(grid.tolist()) == [1] * 6 + [2] * 2
