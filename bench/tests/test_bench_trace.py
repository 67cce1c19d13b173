"""The reduction from a profiler trace to busy time, program time and
idle gaps."""

from __future__ import annotations

import os

import pytest

from bench import trace


def test_union_and_overlap():
    u = trace.union([(3, 4), (0, 1), (0.5, 2), (4, 5)])
    assert u == [(0, 2), (3, 5)]
    assert trace.overlap(u, [(1, 3.5)]) == pytest.approx(1.5)
    assert trace.overlap([], u) == 0


def test_summarize_synthetic():
    Op = trace.Op
    ops = [Op("fusion.1", "decode_fn", 1.0, 1.5), Op("fusion.2", "decode_fn", 1.5, 2.0),
           Op("copy", "kv_scatter_read", 3.0, 3.2)]
    calls = [Op("jit_decode_fn(7)", "decode_fn", 1.0, 2.0),
             Op("jit_kv_scatter_read(9)", "kv_scatter_read", 3.0, 3.2)]
    spans = [("serve", 0.9, 2.2), ("wait", 2.2, 2.95), ("serve", 2.95, 3.4)]
    sm = trace.summarize(trace.Trace(ops, calls, spans, 1))
    assert sm.window == (0.9, 3.4) and sm.busy_s == pytest.approx(1.2)
    assert sm.programs["decode_fn"]["calls"] == 1
    assert sm.programs["decode_fn"]["call_seconds"] == [pytest.approx(1.0)]
    assert sm.ops["fusion.1"] == pytest.approx(0.5)
    # idle: 0.9-1.0 (serve), 2.0-3.0 (mostly wait), 3.2-3.4 (serve)
    assert [round(s, 6) for s, _ in sm.gaps] == [1.0, 0.2, 0.1]
    assert sm.gaps[0][1] == "wait" and trace.idle_by_phase(sm)["serve"] == pytest.approx(0.3)
    bd = trace.breakdown(sm)
    assert set(bd) == {"device_ops", "idle_gaps"} and bd["device_ops"][0][0] in ("fusion.1", "fusion.2")


def test_op_name():
    assert trace.op_name("%fusion.71 = bf16[16,128]{1,0} fusion(bf16[24,2048]{1,0} %p)") == "fusion.71"
    assert trace.op_name("copy.3") == "copy.3"


def test_program_name():
    assert trace.program_name("jit_decode_fn(123)") == "decode_fn"
    assert trace.program_name("jit_kv_scatter_read") == "kv_scatter_read"


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "tiny_shared.xplane.pb")


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5 lite by ``record_fixture.py``: three
    requests of the shared-prefix cell at reduced widths, prompts of 45, 37
    and 40 tokens hitting a 32-token prefix, 2, 3 and 4 output tokens. By
    hand: the hit path decodes the 13, 5 and 8 tail tokens, then 1, 2 and 3
    more, so 32 decode calls; one greedy pick per output token, 9; one
    fetch per request, 3, each inside its own request's serve span."""
    tr = trace.load(FIXTURE)
    sm = trace.summarize(tr)
    assert tr.n_devices == 1 and len(sm.serve) == 3
    assert sm.programs["decode_fn"]["calls"] == 32
    assert sm.programs["_greedy"]["calls"] == 9
    fetch = sm.programs["kv_scatter_read"]
    assert fetch["calls"] == 3
    assert [trace.span_of(sm.serve, t) for t in fetch["starts"]] == [0, 1, 2]
    assert fetch["seconds"] == pytest.approx(2.9358e-05, rel=1e-3)
    assert sm.programs["decode_fn"]["seconds"] == pytest.approx(1.394768e-03, rel=1e-3)
    assert 0 < sm.busy_s < sm.window_s
    assert sum(s for s, _ in sm.gaps) == pytest.approx(sm.window_s - sm.busy_s, rel=1e-6)
    assert trace.idle_by_phase(sm)["wait"] > 0.2


def test_metrics_on_recorded_trace():
    """The per-layer readers find their programs in the recorded trace and
    match each call to its request."""
    from bench import flops, run
    from bench.tests import tiny

    sm = trace.summarize(trace.load(FIXTURE))
    cell = tiny.tiny_cell("qwen3-32b-noqknorm-4L.shared-sysprompt")
    recs = [run.Record(0, 0, 1, n, m, 0, ok=True, hit_tokens=32, n_out=m)
            for n, m in ((45, 2), (37, 3), (40, 4))]
    ctx = run.MetricContext(recs, sm, cell.sizes, flops.peak("TPU v5 lite"), cell.arch)
    assert [r.prompt_len for r, _ in ctx.calls_by_request("kv_scatter_read")] == [45, 37, 40]
    for m in cell.per_layer:
        v = run.load_module(os.path.join(run.BENCH, "metrics", m["name"] + ".py")).read(ctx)
        assert v is not None and 0 < v < 100, m["name"]
