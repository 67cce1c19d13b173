"""The latent-attention cell's per-layer readers, on a stub trace (CPU, no
program run): each reads the same quantity as the dense cells' reader it
delegates to, through ``mla_moe``'s counts; ``mla_extend_call_ms`` reads
``extend_fn``'s device time per call, and nothing where it did not run."""

from __future__ import annotations

import os
import types

import pytest

from bench import flops, run

CELL = run.Cell.load("deepseek-v3-ep32-5L.shared-sysprompt")
RECS = [run.Record(0.0, 0.0, 0.5, 8300, 20, 0, ok=True, hit_tokens=8192, n_out=20),
        run.Record(0.0, 1.0, 1.4, 8210, 9, 1, ok=True, hit_tokens=8192, n_out=9)]


def _calls(*secs):
    return {"starts": [0.1, 1.1][: len(secs)], "call_seconds": list(secs),
            "seconds": sum(secs), "calls": len(secs)}


SUMMARY = types.SimpleNamespace(
    serve=[(0.0, 0.5), (1.0, 1.4)],
    programs={"kv_scatter_read": _calls(2.0e-4, 3.0e-4), "decode_fn": _calls(0.2, 0.1),
              "extend_fn": _calls(0.04, 0.05)})


def _read(metric: str, summary=SUMMARY):
    ctx = run.MetricContext(RECS, summary, CELL.sizes, flops.peak("TPU v5 lite"), CELL.arch)
    return run.load_module(os.path.join(run.BENCH, "metrics", metric + ".py")).read(ctx)


@pytest.mark.parametrize("metric, twin", [
    ("mla_decode_step_ms", "decode_step_ms"),
    ("latent_scatter_read_roofline", "kv_scatter_read_roofline"),
    ("mla_step_mfu", "step_mfu"),
])
def test_reads_as_its_dense_twin(metric, twin):
    assert _read(metric) == _read(twin) is not None


def test_latent_roofline_counts_92160_byte_blocks():
    need = 2 * 2 * 512 * 92_160 / 819e9  # two hits of 512 blocks, read and written
    assert _read("latent_scatter_read_roofline") == pytest.approx(100 * need / 5e-4, rel=1e-12)


def test_extend_call_ms():
    assert _read("mla_extend_call_ms") == pytest.approx(45.0, rel=1e-12)
    assert _read("mla_extend_call_ms", types.SimpleNamespace(serve=[], programs={})) is None
