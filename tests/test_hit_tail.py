"""A prefix hit's uncached tail, prefilled in ``extend_fn`` chunks against
the fetched cache, serves what a cold prefill of the whole prompt serves.

CPU, reduced widths: publish a prefix, then serve the prefix plus a tail on
that engine, and the same prompt on an engine that never saw it.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.serving import real_runner
from repro.serving.real_runner import TAIL_CHUNK, RealEngine

MAX_LEN = 640
N_OUT = 6
LOGIT_ATOL = 2e-2  # bf16 logits of order 1; both paths read 0.0 apart on the CPU

# (published prefix, tail): a full hit re-feeds the last token; TAIL_CHUNK + 5
# takes two chunks; 400 + 100 starts its window at 400, past MAX_LEN - TAIL_CHUNK,
# so it is clipped back to recompute cached positions
CASES = [(32, 0), (32, 1), (32, 15), (32, 16), (32, TAIL_CHUNK + 5), (400, 100)]


@pytest.fixture(scope="module")
def engines():
    def make():
        return RealEngine.create("olmo-1b", max_len=MAX_LEN, pool_blocks=64, kernel_mode="jnp")

    return make(), make()


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


@pytest.mark.parametrize("n_prefix,n_tail", CASES, ids=[f"{p}+{t}" for p, t in CASES])
def test_hit_tail_matches_cold_prefill(engines, logits_seen, n_prefix, n_tail):
    hot, cold = engines
    rng = np.random.default_rng(1000 * n_prefix + n_tail)
    prefix = rng.integers(0, hot.cfg.vocab_size, n_prefix).tolist()
    prompt = prefix + rng.integers(0, hot.cfg.vocab_size, n_tail).tolist()
    hot.generate(prefix, max_new=1)  # publishes the prefix

    def serve(eng):
        n = len(logits_seen)
        out, info = eng.generate(prompt, max_new=N_OUT)
        return out, info, logits_seen[n]  # the first token's logits

    out_hot, info_hot, logits_hot = serve(hot)
    out_cold, info_cold, logits_cold = serve(cold)

    assert info_cold["hit_tokens"] == 0
    assert info_hot["hit_tokens"] == n_prefix
    assert info_hot["tail_tokens"] == max(n_tail, 1)
    assert info_hot["logits_finite"] and info_cold["logits_finite"]
    assert out_hot == out_cold
    np.testing.assert_allclose(logits_hot, logits_cold, rtol=0, atol=LOGIT_ATOL)
