"""``RealEngine.generate``'s spans, read back from a profiler trace on the
CPU at reduced widths: one miss, then one hit of the same prompt."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spans as spans_lib
from bench import trace
from repro.serving import real_runner
from repro.serving.real_runner import SPANS, RealEngine

N_OUT = 4
MISS = {"engine.prefill", "engine.writeback", "engine.allocate", "engine.publish"}
HIT = {"engine.fetch", "engine.tail"}


def _serve(eng, prompt):
    return [eng.generate(prompt, max_new=N_OUT) for _ in range(2)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(tokens and info with the profiler off, the same with it on, the
    spans read back, the trace file)."""
    prompt = np.random.default_rng(3).integers(0, 256, size=40).tolist()
    off = _serve(RealEngine.create("olmo-1b", max_len=64, pool_blocks=16, kernel_mode="jnp"),
                 prompt)
    eng = RealEngine.create("olmo-1b", max_len=64, pool_blocks=16, kernel_mode="jnp")
    log_dir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(log_dir):
        on = _serve(eng, prompt)
    path = trace.find_xplane(log_dir)
    return off, on, spans_lib.load(path), path


def test_tokens_same_with_profiler_on_and_off(traced):
    off, on, _, _ = traced
    assert [out for out, _ in off] == [out for out, _ in on]
    assert [info["hit_tokens"] for _, info in on] == [0, 32]
    assert all(info["logits_finite"] for _, info in on)


def test_every_span_named_and_on_the_host_plane(traced):
    *_, spans, path = traced
    assert {s.name for s in spans} == set(SPANS)
    data = jax.profiler.ProfileData.from_file(path)
    reqs = [dict(e.stats).get("req") for p in data.planes if p.name == "/host:CPU"
            for ln in p.lines for e in ln.events if e.name == "engine.generate"]
    assert sorted(reqs) == [0, 1]


def test_spans_nest_in_their_request(traced):
    *_, spans, _ = traced
    roots = [s for s in spans if s.depth == 0]
    assert [s.name for s in roots] == ["engine.generate"] * 2
    assert [s.req for s in roots] == [0, 1]
    for s in spans:
        root = next(r for r in roots if r.start <= s.start and s.end <= r.end)
        assert s.req == root.req, s
    parents = {"engine.allocate": "engine.writeback", "engine.publish": "engine.writeback",
               "engine.step": "engine.decode", "engine.sync": "engine.decode"}
    for s in spans:
        if s.name in parents:
            assert s.depth == 2
            assert any(p.name == parents[s.name] and p.start <= s.start and s.end <= p.end
                       for p in spans), s
        elif s.name != "engine.generate":
            assert s.depth == 1, s


def test_miss_and_hit_paths(traced):
    *_, spans, _ = traced
    names = [{s.name for s in spans if s.req == r} for r in (0, 1)]
    assert MISS <= names[0] and not HIT & names[0]
    assert HIT <= names[1] and not MISS & names[1]
    for r in (0, 1):
        assert sum(s.name == "engine.step" and s.req == r for s in spans) == N_OUT - 1
        assert sum(s.name == "engine.sync" and s.req == r for s in spans) == N_OUT - 1


def test_tail_span_counts_tokens_and_chunks(traced):
    """The hit's tail is prefilled by ``extend_fn`` in ceil(T / C) chunks,
    with no decode step before the first token."""
    *_, spans, path = traced
    data = jax.profiler.ProfileData.from_file(path)
    stats = [dict(e.stats) for p in data.planes if p.name == "/host:CPU"
             for ln in p.lines for e in ln.events if e.name == "engine.tail"]
    chunk = min(real_runner.TAIL_CHUNK, 64)
    assert [(s["tokens"], s["chunks"]) for s in stats] == [(8, -(-8 // chunk))]
    tail = next(s for s in spans if s.name == "engine.tail")
    assert not [s for s in spans if s.name == "engine.step"
                and tail.start <= s.start and s.end <= tail.end]


def test_fetch_span_counts_blocks_and_bytes(traced):
    """``engine.fetch`` carries the blocks a hit fetches and their bytes."""
    from repro.configs.registry import reduced_config
    from repro.core.pool import PoolLayout

    *_, path = traced
    data = jax.profiler.ProfileData.from_file(path)
    stats = [dict(e.stats) for p in data.planes if p.name == "/host:CPU"
             for ln in p.lines for e in ln.events if e.name == "engine.fetch"]
    block = PoolLayout.for_model(reduced_config("olmo-1b")).block_bytes
    assert [(s["blocks"], s["bytes"]) for s in stats] == [(2, 2 * block)]


def test_served_programs_have_names():
    """The served path's device programs carry the names the benchmark's
    trace reduction looks up."""
    eng = RealEngine.create("olmo-1b", max_len=64, pool_blocks=16, kernel_mode="jnp")
    prompt = list(range(40))
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    assert "jit_prefill_fn" in eng._prefill.lower(eng.params, batch).as_text()
    _, cache = eng._prefill(eng.params, batch)
    chunk = np.zeros((1, min(real_runner.TAIL_CHUNK, 64)), np.int32)
    lowered = eng._extend.lower(eng.params, cache, chunk, np.int32(32), np.int32(8))
    assert "jit_extend_fn" in lowered.as_text()
    ids = jnp.arange(2, dtype=jnp.int32)
    data = eng.pool.data
    assert "jit_pool_gather" in real_runner.pool_gather.lower(data, ids).as_text()
    assert "jit_pool_write" in real_runner.pool_write.lower(data, ids, data[:2]).as_text()


def _compiles() -> list:
    """The names of the programs compiled from now on, as they compile."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(kw.get("fun_name"))
        if event == "/jax/core/compile/backend_compile_duration" else None)
    return compiles


def test_output_length_compiles_nothing():
    """The finiteness flag rides through the greedy pick: a new output
    length runs the programs the first request compiled, and no other."""
    compiles = _compiles()
    eng = RealEngine.create("olmo-1b", max_len=64, pool_blocks=16, kernel_mode="jnp")
    prompt = list(range(40))
    eng.generate(prompt, max_new=3)
    eng.generate(prompt, max_new=3)  # the hit path's programs
    n = len(compiles)
    for max_new in (2, 5, 7):
        _, info = eng.generate(prompt, max_new=max_new)
        assert info["hit_tokens"] == 32 and info["logits_finite"]
    assert compiles[n:] == []


def test_tail_length_compiles_nothing():
    """Every tail runs the one ``extend_fn`` shape the first hit compiled:
    after it, hits with other tail lengths compile nothing."""
    compiles = _compiles()
    eng = RealEngine.create("olmo-1b", max_len=64, pool_blocks=16, kernel_mode="jnp")
    prefix = list(range(32))
    eng.generate(prefix, max_new=2)
    eng.generate(prefix + [7] * 16, max_new=2)  # the first hit, a 16-token tail
    assert "jit(extend_fn)" in compiles
    n = len(compiles)
    for n_tail in (0, 1, 5, 17, 29):
        _, info = eng.generate(prefix + [9] * n_tail, max_new=2)
        assert info["hit_tokens"] == 32 and info["tail_tokens"] == max(n_tail, 1)
    assert compiles[n:] == []
