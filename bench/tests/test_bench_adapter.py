"""The benchmark's adapter over RealEngine, on the CPU at reduced widths.

Guards the interface the benchmark relies on (``RealEngine.create``,
``generate`` and its ``info``), so that a change that breaks it fails
here and not on the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import run
from bench.tests import tiny


@pytest.mark.parametrize("name", tiny.CELLS)
def test_loop_over_real_engine(name):
    res, recs = tiny.run_tiny(name)
    assert res["correct"] and res["attempted"] == len(recs) == 3 and res["failed"] == 0
    assert res["compared"]["max_logit_gap"]["value"] <= res["compared"]["max_logit_gap"]["limit"]
    for r in recs:
        assert r.ok and r.n_out == r.max_new and r.ttft_s > 0 and r.end >= r.start
        if tiny.MIXES[run.Cell.load(name).traffic]["prefixes"]:
            assert r.hit_tokens == 32  # the whole published prefix
        else:
            assert r.hit_tokens == 0
    assert {m for m in res["metrics"]} == {m["name"] for m in run.Cell.load(name).end_to_end}
    assert list(res)[-1] == "compared"


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", tiny.CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert "platform cpu" in p.stdout
