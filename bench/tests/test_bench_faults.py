"""The correctness check catches a broken timed path (CPU, reduced widths).

Each test drives a whole run past the harness's look for a chip, with a
fault planted under the timed path, and sees ``correct`` come out false
under the cell's committed limit. The cells run at batch 1 on one chip,
so of the faults a cell can have these two apply: a decode step that
returns its state unchanged, and a token altered where it is produced.
The fp8 control is the reference in the program's place one precision
below the configuration's bfloat16.
"""

from __future__ import annotations

import pytest

from bench import calibrate
from bench.tests import tiny

# more served tokens than the adapter test, so that a fault has positions to show at
LONGER = {"n_requests": 6, "output": {"dist": "log_uniform", "min": 8, "max": 16},
          "check_tokens": 64}


def _stale_decode(eng):
    step = eng._decode

    def decode(params, cache, tokens, pos):
        logits, _ = step(params, cache, tokens, pos)
        return logits, cache  # the KV of this token is never written

    eng._decode = decode


def _altered_token(eng):
    generate = eng.generate

    def gen(prompt, max_new):
        out, info = generate(prompt, max_new)
        k = len(out) // 2
        out[k] = (out[k] + 1) % eng.cfg.vocab_size
        return out, info

    eng.generate = gen


@pytest.mark.parametrize("name", tiny.CELLS)
@pytest.mark.parametrize("fault", [_stale_decode, _altered_token], ids=["stale_decode", "altered_token"])
def test_fault_is_not_correct(name, fault):
    res, _ = tiny.run_tiny(name, engine_hook=fault, cell_kw=LONGER)
    assert res["failed"] == 0
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", tiny.CELLS)
def test_fp8_control_is_not_correct(name):
    """The reference one precision below bf16, in the program's place, reads
    a widest gap over the cell's limit on the same prompts and tokens."""
    built = {}
    res, recs = tiny.run_tiny(name, cell_kw=LONGER, engine_hook=lambda eng: built.update(
        weights=eng.params, max_len=eng.max_len))
    assert res["correct"]  # the program itself passes
    cell = tiny.tiny_cell(name, **LONGER)
    served, ctrl = calibrate.control_gaps(cell, built["weights"], recs, 7, built["max_len"])
    limit = res["compared"]["max_logit_gap"]["limit"]
    assert max(served) == res["compared"]["max_logit_gap"]["value"]
    assert max(ctrl) > limit, ctrl
