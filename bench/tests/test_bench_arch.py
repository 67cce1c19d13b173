"""The harness takes everything shape-dependent from the architecture module.

A toy latent-KV module (``toy_latent.py``: one part of width 576 a layer
in each pool block, no key/value pair) goes through ``engine.build``'s
layout checks against a stub engine, and through the per-layer readers'
counts, with no edit to the harness (CPU, no program run).
"""

from __future__ import annotations

import math
import os
import types

import jax
import pytest

from bench import engine, flops, run
from bench.tests import toy_latent as toy

S = toy.sizes_of(toy.TINY)
PROGRAM = {"arch": "toy-latent", "layers": S["layers"]}
DENSE = run.Cell.load(run.load_json(run.ROOT, "BENCHMARK.json")["workloads"][0]["name"]).arch
# dense-GQA sizes with the toy's depth, width, heads and vocabulary
DENSE_S = dict(layers=S["layers"], d=S["d"], heads=S["heads"], kv_heads=1, head_dim=32,
               ff=S["ff"], vocab=S["vocab"], theta=10000.0, eps=1e-5)


def _ns(**kw):
    return types.SimpleNamespace(**kw)


@pytest.fixture
def stub(monkeypatch):
    """``RealEngine.create`` returns a stub engine with the given ``cfg``
    fields and pool block bytes, and the toy's parameter tree."""
    from repro.serving.real_runner import RealEngine

    def make(fields: dict, block: int):
        eng = _ns(cfg=_ns(**fields), pool=_ns(layout=_ns(block_bytes=block)),
                  params=toy.make_weights(S, 0))
        monkeypatch.setattr(RealEngine, "create", lambda *a, **k: eng)
        return eng

    return make


def _build(arch, sizes):
    return engine.build(PROGRAM, sizes, arch, pool_blocks=8, max_len=64, seed=3)


def test_toy_block_is_one_576_wide_part_a_layer():
    assert S["kv_rank"] + S["rope_dim"] == 576
    assert toy.block_bytes(S) == S["layers"] * 16 * 576 * 2
    shapes = jax.tree.leaves(toy.weight_shapes(S), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(x) for x in shapes) == toy.params(S)


def test_toy_counts_at_deepseek_v3_widths():
    """The toy's latent attention at DeepSeek-V3's published widths, five
    layers: 187,105,280 attention weights a layer, a 92,160 B block."""
    s = toy.sizes_of(dict(toy.TINY, hidden_size=7168, num_attention_heads=128, q_lora_rank=1536,
                          qk_nope_head_dim=128, v_head_dim=128, num_hidden_layers=5))
    attn = toy.weight_shapes(s)["stack"]["attn"]
    assert sum(math.prod(x[1:]) for k, x in attn.items() if "norm" not in k) == 187_105_280
    assert toy.block_bytes(s) == 92_160


def test_build_accepts_a_matching_latent_engine(stub):
    eng = stub(toy.program_fields(S), toy.block_bytes(S))
    built, weights = _build(toy, S)
    assert built is eng and eng.params is weights


@pytest.mark.parametrize("field", sorted(toy.program_fields(S)))
def test_build_exits_on_a_field_off(stub, field):
    fields = toy.program_fields(S)
    fields[field] += 1
    stub(fields, toy.block_bytes(S))
    with pytest.raises(SystemExit, match=f"program config differs.*{field}"):
        _build(toy, S)


def test_build_exits_on_block_bytes_off(stub):
    stub(toy.program_fields(S), toy.block_bytes(S) + 2)
    with pytest.raises(SystemExit, match="pool block layout differs"):
        _build(toy, S)


def test_dense_gqa_refuses_the_latent_engine(stub):
    """Every dense field the stub could hold matches, and still its pool
    block, one latent row a layer, is not a key/value pair per kv head."""
    stub(dict(toy.program_fields(S), **DENSE.program_fields(DENSE_S)), toy.block_bytes(S))
    assert DENSE.block_bytes(DENSE_S) != toy.block_bytes(S)
    with pytest.raises(SystemExit, match="pool block layout differs"):
        _build(DENSE, DENSE_S)


# two requests, one per serve span, with one traced call of each copy program
RECS = [run.Record(0.0, 0.0, 0.25, 40, 3, 0, ok=True, hit_tokens=32, n_out=3),
        run.Record(0.0, 1.0, 1.5, 20, 2, -1, ok=True, hit_tokens=0, n_out=2)]
CALLS = {"starts": [0.1, 1.1], "call_seconds": [2e-6, 3e-6]}
SUMMARY = _ns(serve=[(0.0, 0.5), (1.0, 1.5)],
              programs={"kv_scatter_read": CALLS, "kv_gather_write": CALLS})
PEAK = flops.peak("TPU v5 lite")


def _read(metric: str):
    ctx = run.MetricContext(RECS, SUMMARY, S, PEAK, arch=toy)
    return run.load_module(os.path.join(run.BENCH, "metrics", metric + ".py")).read(ctx)


@pytest.mark.parametrize("metric, blocks", [
    ("kv_scatter_read_roofline", 32 // 16 + 0),  # the hit blocks
    ("kv_gather_write_roofline", 40 // 16 + 20 // 16),  # the prompt's whole blocks
])
def test_roofline_reads_the_toy_block(metric, blocks):
    block = 2 * 16 * 576 * 2  # two layers of one 576-wide bf16 row a token
    want = 100.0 * (2 * blocks * block / 819e9) / 5e-6
    assert _read(metric) == pytest.approx(want, rel=1e-12)


def test_step_mfu_reads_the_toy_flops():
    d, h, ff, v = 64, 2, 64, 128
    mat = d * 32 + 32 * h * (16 + 64) + d * 576 + 512 * h * (16 + 16) + h * 16 * d + 3 * d * ff

    def token(c):  # two layers: matrices, QK^T over 16 + 64, PV over 16
        return 2 * (2 * mat + 2 * c * h * 80 + 2 * c * h * 16)

    # hit: positions 32..41 (contexts 33..42), miss: positions 0..20; logits n_out times
    work = sum(token(c) for c in range(33, 43)) + sum(token(c) for c in range(1, 22))
    work += (3 + 2) * 2 * d * v
    assert _read("step_mfu") == pytest.approx(100.0 * work / 0.75 / 197e12, rel=1e-12)
