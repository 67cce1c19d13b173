"""The program's spans in a trace: nesting, the naming of idle gaps, and
the four quantities read from them, against values worked out by hand."""

from __future__ import annotations

import os

import pytest

from bench import spans, trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
FIXTURE = os.path.join(FIXTURES, "tiny_shared.xplane.pb")  # PR 12's, with no program spans
SPANS_FIXTURE = os.path.join(FIXTURES, "tiny_shared_spans.xplane.pb")

# One miss with three decode steps, on one thread (seconds).
ENGINE = [
    ("engine.generate", 1.00, 1.96, 5),
    ("engine.lookup", 1.00, 1.02, None),
    ("engine.prefill", 1.02, 1.04, None),
    ("engine.writeback", 1.04, 1.10, None),
    ("engine.allocate", 1.05, 1.06, None),
    ("engine.publish", 1.07, 1.10, None),
    ("engine.first_token", 1.10, 1.30, None),
    ("engine.decode", 1.30, 1.95, None),
    ("engine.step", 1.30, 1.32, None), ("engine.sync", 1.32, 1.60, None),
    ("engine.step", 1.60, 1.62, None), ("engine.sync", 1.62, 1.90, None),
    ("engine.step", 1.90, 1.92, None), ("engine.sync", 1.92, 1.95, None),
]
DEVICE = [("prefill_fn", 1.03, 1.20), ("_greedy", 1.20, 1.25),
          ("decode_fn", 1.34, 1.55), ("_greedy", 1.55, 1.58),
          ("decode_fn", 1.64, 1.80), ("_greedy", 1.80, 1.82),
          ("decode_fn", 1.93, 1.94), ("_greedy", 1.94, 1.945)]


def synthetic():
    ops = [trace.Op(p + "/op", p, a, b) for p, a, b in DEVICE]
    tr = trace.Trace(ops, list(ops), [("wait", 0.0, 1.0), ("serve", 1.0, 2.0)], 1)
    return tr, trace.summarize(tr), spans.nest(ENGINE)


def test_nest():
    _, _, sp = synthetic()
    assert [s.depth for s in sp] == [0, 1, 1, 1, 2, 2, 1, 1, 2, 2, 2, 2, 2, 2]
    assert {s.req for s in sp} == {5}
    # a second request on the same thread takes its own id
    more = spans.nest(ENGINE[:2] + [("engine.generate", 2.0, 2.5, 6),
                                    ("engine.lookup", 2.0, 2.1, None)])
    assert [(s.depth, s.req) for s in more] == [(0, 5), (1, 5), (0, 6), (1, 6)]


def test_gaps_named_by_innermost_span():
    """Gaps: [0, 1.03] mostly in `wait`, outside every span; [1.25, 1.34] 0.05
    of 0.09 in first_token; [1.58, 1.64] split 0.02/0.02 between a sync and a
    step, so their parent decode; [1.82, 1.93] 0.08 of 0.11 in a sync;
    [1.945, 2.0] mostly after generate ends, so the harness's `serve`."""
    tr, sm, sp = synthetic()
    named = spans.name_gaps(tr, sm, sp)
    assert [(round(a, 6), round(b, 6), n) for a, b, n in named] == [
        (0.0, 1.03, "wait"), (1.25, 1.34, "engine.first_token"), (1.58, 1.64, "engine.decode"),
        (1.82, 1.93, "engine.sync"), (1.945, 2.0, "serve")]
    by = spans.idle_by_name(named)
    assert by == pytest.approx({"wait": 1.03, "engine.first_token": 0.09, "engine.decode": 0.06,
                                "engine.sync": 0.11, "serve": 0.055})
    in_serve = spans.idle_by_name(named, sm.serve)
    assert in_serve == pytest.approx(dict(by, wait=0.03))
    # with no program spans, the names are the ones `trace.summarize` gives
    assert sorted(n for *_, n in spans.name_gaps(tr, sm, [])) == sorted(p for _, p in sm.gaps)


def test_readers_by_hand():
    """decode [1.30, 1.95]: 0.65 s, busy 0.24 + 0.18 + 0.015 inside, 3 steps:
    (0.65 - 0.435) / 3 = 71.667 ms. First token [1.00, 1.30], busy 0.22 of
    0.30: 26.667% idle. One lookup of 20 ms, one writeback of 60 ms."""
    _, sm, sp = synthetic()
    assert spans.decode_gap_ms(sm, sp) == pytest.approx(215.0 / 3)
    assert spans.ttft_idle_share(sm, sp) == pytest.approx(100 * (1 - 0.22 / 0.30))
    assert spans.lookup_ms(sp) == pytest.approx(20.0)
    assert spans.writeback_host_ms(sp) == pytest.approx(60.0)


def test_readers_find_nothing_without_spans():
    """PR 12's recorded trace has no program spans: every reader gives None,
    and the gaps keep the names `trace.summarize` gives them."""
    tr = trace.load(FIXTURE)
    sm = trace.summarize(tr)
    sp = spans.load(FIXTURE)
    assert sp == []
    assert spans.decode_gap_ms(sm, sp) is None and spans.ttft_idle_share(sm, sp) is None
    assert spans.lookup_ms(sp) is None and spans.writeback_host_ms(sp) is None
    named = spans.name_gaps(tr, sm, sp)
    assert sorted((round(b - a, 12), n) for a, b, n in named) == sorted(
        (round(s, 12), n) for s, n in sm.gaps)


# Traces recorded on a TPU v5 lite by ``record_fixture.py`` (reduced widths,
# jnp copy kernels): per cell, (prompt tokens, hit tokens, output tokens) of
# its three requests, the spans a request writes, and device program calls.
RECORDED = {
    "shared": ("qwen3-32b-noqknorm-4L.shared-sysprompt", "tiny_shared_spans.xplane.pb",
               [(45, 32, 2), (37, 32, 3), (40, 32, 4)],
               {"engine.generate", "engine.lookup", "engine.fetch", "engine.tail",
                "engine.first_token", "engine.decode", "engine.step", "engine.sync"},
               {"decode_fn": 32, "_greedy": 9, "pool_gather": 3, "kv_scatter_read": 3}),
    "unshared": ("qwen3-32b-noqknorm-4L.unshared", "tiny_unshared_spans.xplane.pb",
                 [(32, 0, 2), (16, 0, 3), (16, 0, 4)],
                 {"engine.generate", "engine.lookup", "engine.prefill", "engine.writeback",
                  "engine.allocate", "engine.publish", "engine.first_token", "engine.decode",
                  "engine.step", "engine.sync"},
                 {"prefill_fn": 3, "kv_gather_write": 3, "pool_write": 3, "decode_fn": 6,
                  "_greedy": 9}),
}


@pytest.mark.parametrize("which", sorted(RECORDED))
def test_recorded_spans(which):
    """By hand: one ``engine.step`` per output token after the first (1 + 2
    + 3); every span in one of three requests with consecutive ids; the
    programs named as the program names them, prefill as ``prefill_fn``."""
    _, fname, reqs, names, programs = RECORDED[which]
    path = os.path.join(FIXTURES, fname)
    tr = trace.load(path)
    sm = trace.summarize(tr)
    sp = spans.load(path)
    assert {s.name for s in sp} == names
    ids = [s.req for s in sp if s.name == "engine.generate"]
    assert len(ids) == 3 and ids == list(range(ids[0], ids[0] + 3))
    assert {s.req for s in sp} == set(ids)
    assert sum(s.name == "engine.step" for s in sp) == sum(n - 1 for *_, n in reqs)
    assert {k: sm.programs[k]["calls"] for k in programs} == programs
    assert "_unknown" not in sm.programs
    # the new readers find their spans; writeback only where requests miss
    assert spans.decode_gap_ms(sm, sp) > 0 and spans.lookup_ms(sp) > 0
    assert 0 < spans.ttft_idle_share(sm, sp) <= 100
    assert (spans.writeback_host_ms(sp) is not None) == (which == "unshared")
    # under 10% of the idle time in `serve` is left unnamed
    in_serve = spans.idle_by_name(spans.name_gaps(tr, sm, sp), sm.serve)
    unnamed = in_serve.get("serve", 0.0) + in_serve.get("other", 0.0)
    assert unnamed < 0.1 * sum(in_serve.values())


@pytest.mark.parametrize("which", sorted(RECORDED))
def test_accepted_readers_on_recorded_spans(which):
    """The benchmark's per-layer readers read a trace with program spans as
    they read one without."""
    from bench import flops, run
    from bench.tests import tiny

    name, fname, reqs, *_ = RECORDED[which]
    sm = trace.summarize(trace.load(os.path.join(FIXTURES, fname)))
    cell = tiny.tiny_cell(name)
    recs = [run.Record(0, 0, 1, n, m, 0, ok=True, hit_tokens=h, n_out=m) for n, h, m in reqs]
    ctx = run.MetricContext(recs, sm, cell.sizes, flops.peak("TPU v5 lite"), cell.arch)
    for m in cell.per_layer:
        v = run.load_module(os.path.join(run.BENCH, "metrics", m["name"] + ".py")).read(ctx)
        assert v is not None and 0 < v < 100, m["name"]
