"""DeepSeek-V3's block as served (latent attention, a held share of the
experts, a leading dense layer) against the benchmark's plain reference
(``bench/configs/mla_moe.py``), on the CPU at the reduced widths.

The program serves as the benchmark runs it: bf16 weights and latent
cache, float32 activations, so that it routes as the reference does; the
reference is float32 at ``Precision.HIGHEST`` over the latent rows as the
cache holds them.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from repro.configs.base import RuntimeConfig
from repro.configs.registry import cut_depth, get_config, reduced_config
from repro.core.index import GlobalIndex
from repro.core.pool import BelugaPool, PoolLayout
from repro.models import Model
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.serving import real_runner
from repro.serving.real_runner import RealEngine

CONFIG = run.load_json(run.BENCH, "configs", "deepseek-v3-ep32-5L.json")
ARCH = run.load_module(os.path.join(run.BENCH, "configs", CONFIG["architecture"] + ".py"))
SIZES = ARCH.sizes_of(CONFIG)
TINY = ARCH.sizes_of(dict(CONFIG, **ARCH.TINY))
CFG = reduced_config("deepseek-v3-ep32")
CFG32 = dataclasses.replace(CFG, dtype="float32")
MAX_LEN = 320  # > 48 + TAIL_CHUNK: the hit's tail window starts at the prefix, not before it
# float32 activations against the float32 reference, both over the same
# bf16 latent rows: they differ in the order of summation, and where that
# puts a row on the other side of a bf16 rounding step (about 1e-3 on
# logits of order 3 here; a held expert that leaves the choice moves them
# by 0.6, a pool scaled by 1.01 by 2e-2)
ATOL = 5e-3


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def weights():
    return ARCH.make_weights(TINY, seed=2**31 + 17)


def _engine(weights) -> RealEngine:
    pool = BelugaPool(PoolLayout.for_model(CFG), n_blocks=32, n_shards=8, backing="jax")
    return RealEngine(
        cfg=CFG, model=Model(CFG, RuntimeConfig(remat="none", decode_kv="replicated")),
        pool=pool, index=GlobalIndex(pool), params=weights, max_len=MAX_LEN,
        kernel_mode="interpret",
    )


@pytest.fixture
def logits_seen(monkeypatch):
    """Every logits row the greedy pick is given, in order."""
    seen = []
    greedy = real_runner._greedy

    def spy(logits, ok):
        seen.append(np.asarray(logits, np.float32).reshape(-1))
        return greedy(logits, ok)

    monkeypatch.setattr(real_runner, "_greedy", spy)
    return seen


def _reference(weights, prompt, out):
    seq = prompt + out[:-1]
    read = list(range(len(prompt) - 1, len(seq)))
    return np.asarray(ARCH.logits_at(weights, TINY, seq, read, MAX_LEN, len(read)))


def test_reduced_program_is_the_tiny_cell():
    """The reduced program has the widths the reference module's ``TINY`` names."""
    cfg = reduced_config("deepseek-v3-ep32")
    wrong = {f: v for f, v in ARCH.program_fields(TINY).items() if getattr(cfg, f) != v}
    assert not wrong
    assert PoolLayout.for_model(cfg).block_bytes == ARCH.block_bytes(TINY)


def test_published_widths_match_the_configuration():
    """At the configuration's widths (5 layers): 1 dense layer, then 4 MoE layers;
    the fields, pool block and parameter tree ``bench/engine.build``
    checks, read from shapes alone."""
    cfg = cut_depth(get_config("deepseek-v3-ep32"), 5)
    assert (cfg.n_layers, cfg.first_k_dense) == (5, 1)
    s = SIZES
    assert not {f for f, v in ARCH.program_fields(s).items() if getattr(cfg, f) != v}
    assert PoolLayout.for_model(cfg).block_bytes == ARCH.block_bytes(s) == 92_160
    shapes = jax.eval_shape(Model(cfg).init, jax.random.key(0))
    tree = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), shapes)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        jax.eval_shape(lambda: ARCH.make_weights(s, 0)))
    assert tree == want
    assert tree["lead"]["pos_0"]["mlp"]["wi_gate"][0] == (1, 7168, 18432)
    assert tree["stack"]["pos_0"]["moe"]["wi_gate"][0] == (4, 8, 7168, 2048)
    assert tree["stack"]["pos_0"]["moe"]["router"] == ((4, 7168, 256), "float32")
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert n == ARCH.params(s)


def test_miss_prefill_then_decode_matches_reference(weights, logits_seen):
    eng = _engine(weights)
    prompt = np.random.default_rng(5).integers(0, CFG.vocab_size, 37).tolist()
    out, info = eng.generate(prompt, max_new=6)
    assert info["hit_tokens"] == 0 and info["logits_finite"]
    want = _reference(weights, prompt, out)
    np.testing.assert_allclose(np.stack(logits_seen), want, rtol=0, atol=ATOL)


def test_hit_fetch_extend_decode_matches_reference(weights, logits_seen):
    """Publish a prefix, then serve it plus a tail: lookup, fetch through the
    one-part kernels, ``extend_fn`` over the tail, decode."""
    eng = _engine(weights)
    rng = np.random.default_rng(6)
    prefix = rng.integers(0, CFG.vocab_size, 48).tolist()
    eng.generate(prefix, max_new=1)
    prompt = prefix + rng.integers(0, CFG.vocab_size, 11).tolist()
    n = len(logits_seen)
    out, info = eng.generate(prompt, max_new=6)
    assert info["hit_tokens"] == 48 and info["tail_tokens"] == 11 and info["logits_finite"]
    want = _reference(weights, prompt, out)
    np.testing.assert_allclose(np.stack(logits_seen[n:]), want, rtol=0, atol=ATOL)


@pytest.mark.parametrize("arch, precisions", [
    ("deepseek-v3-ep32", {"HIGH", "HIGHEST"}),  # float32 activations
    ("qwen3-32b", {"DEFAULT"}),  # bf16 activations: the programs as they were
])
def test_served_programs_multiply_at_their_activations_precision(arch, precisions):
    """Every matrix product of the served programs states the precision its
    activations need: a TPU's default would round float32 operands to bf16
    and the router's choices with them."""
    cfg = reduced_config(arch)
    model = Model(cfg, RuntimeConfig(remat="none", decode_kv="replicated"))
    p = jax.eval_shape(model.init, jax.random.key(0))
    c = jax.eval_shape(lambda: model.init_cache(1, 256))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    for lowered in (
        jax.jit(model.decode_fn).lower(p, c, i32(1), i32(1)),
        jax.jit(model.extend_fn).lower(p, c, i32(1, 64), i32(), i32()),
        jax.jit(lambda p, b: model.prefill_fn(p, b, max_len=256)).lower(p, {"tokens": i32(1, 64)}),
    ):
        dots = [ln for ln in lowered.as_text().splitlines() if "dot_general" in ln]
        found = set()
        for ln in dots:
            m = re.search(r"precision = \[(\w+)", ln)
            found.add(m.group(1) if m else "DEFAULT")
        assert dots and found == precisions


def test_expert_shares_add_up_to_the_uncut_layer(weights):
    """Four chips' shares of a layer (experts 0-3, 4-7, 8-11, 12-15 of 16),
    each the program's layer told which experts it holds, add up to the
    reference's layer holding all 16, with the shared expert counted once."""
    moe = CFG32.moe
    uncut = dict(TINY, held=moe.n_experts, first_held=0)
    m = _f32(jax.tree.map(lambda w: w[0], ARCH.make_weights(uncut, 3)["stack"]["pos_0"]["moe"]))
    x = jnp.asarray(np.random.default_rng(7).normal(size=(1, 40, CFG32.d_model)), jnp.float32)
    want = ARCH.moe_block(x[0], m, uncut)
    shared = ARCH._swiglu(x[0], m["shared"], False)
    total, shares = 0.0, moe.n_experts // moe.n_held
    for i in range(shares):
        cut = slice(i * moe.n_held, (i + 1) * moe.n_held)
        cfg = dataclasses.replace(CFG32, moe=dataclasses.replace(moe, first_held=cut.start))
        p = dict(m, wi_gate=m["wi_gate"][cut], wi_up=m["wi_up"][cut], wo=m["wo"][cut])
        out, _ = moe_lib.moe_apply(p, x, cfg, RuntimeConfig(), None)
        total = total + out[0]
    total = total - (shares - 1) * shared
    np.testing.assert_allclose(total, want, rtol=0, atol=1e-4)
    assert float(jnp.abs(want - shared).max()) > 0.1  # the routed experts count


def test_absorbed_attention_equals_expanded(weights):
    """Attention over the cached latent rows, with W_UK folded into the
    query and W_UV applied after, equals attention with k_nope and v
    rebuilt from the latent."""
    p = _f32(jax.tree.map(lambda w: w[0], weights["lead"]["pos_0"]["attn"]))
    S = 64  # two query blocks of the absorbed form
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, S, CFG32.d_model)), jnp.float32)
    pos = jnp.arange(S)[None]
    q_nope, q_rope, rows = mla_lib.project(p, x, pos, CFG32)
    runtime = RuntimeConfig(attn_chunk_q=16, attn_chunk_kv=16)
    expanded = mla_lib.attend_expanded(p, q_nope, q_rope, rows, CFG32, runtime)
    qf = mla_lib.absorb(p, q_nope, q_rope)
    absorbed = mla_lib.attend_absorbed(p, qf, rows, pos, jnp.asarray([S]), CFG32)
    np.testing.assert_allclose(absorbed, expanded, rtol=0, atol=1e-5)


def test_routing_matches_reference_with_a_bias():
    """Sigmoid, group-limited top-k with a selection bias: the program's
    weights equal the reference's, and the bias moves the choice but not
    the weights of what is chosen."""
    rng = np.random.default_rng(9)
    logits = jnp.asarray(rng.normal(size=(64, CFG32.moe.n_experts)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(CFG32.moe.n_experts,)) * 0.5, jnp.float32)
    for b in (jnp.zeros_like(bias), bias):
        w = moe_lib.route_grouped(logits, b, CFG32.moe)
        np.testing.assert_allclose(w, ARCH._route(logits, b, TINY), rtol=0, atol=1e-6)
        assert ((w > 0).sum(-1) == CFG32.moe.top_k).all()
        np.testing.assert_allclose(w.sum(-1), CFG32.moe.routed_scaling, rtol=1e-6)
    plain = moe_lib.route_grouped(logits, jnp.zeros_like(bias), CFG32.moe)
    assert not np.array_equal(np.asarray(w > 0), np.asarray(plain > 0))


def test_held_share_needs_sigmoid_routing():
    """Only the sigmoid-routed layer computes a held share of the experts:
    a softmax layer told it holds some is refused where it is built."""
    moe = CFG32.moe
    with pytest.raises(ValueError, match="sigmoid"):
        dataclasses.replace(moe, scoring="softmax")
    assert dataclasses.replace(moe, scoring="softmax", n_held=0).held == moe.n_experts
