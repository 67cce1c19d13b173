"""The program's own spans in a profiler trace, and the device's idle time
put down to what the host was doing.

``RealEngine.generate`` writes ``engine.*`` spans with
``jax.profiler.TraceAnnotation`` (``repro.serving.real_runner.SPANS``):
host events on the device trace's clock. ``engine.generate`` carries the
request id (``req``); the others nest inside it by time on one thread.
This module reads them beside ``bench/trace.py``'s reduction, which it
leaves as it is, and gives:

- each idle gap of the device put down to the innermost ``engine.*`` span
  that covers more than half of it, else to the harness phase that
  ``bench/trace.py`` names (``serve``, ``wait`` or ``other``);
- four per-layer quantities: ``decode_gap_ms``, ``ttft_idle_share``,
  ``lookup_ms`` and ``writeback_host_ms``.

    python3 -m bench.spans <trace dir or .xplane.pb>

prints them as one JSON line, for a trace kept by ``bench/run.py --trace 1
--keep-trace <dir>``. ``bench/run.py`` itself does not read the spans.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import os
import sys

from bench import trace

PREFIX = "engine."


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    req: int | None  # the id its outermost span carries
    depth: int  # 0 for the outermost
    thread: int = 0  # the host line it was written on


def nest(events, thread: int = 0) -> list[Span]:
    """Spans of one host thread, ``[(name, start, end, req or None)]``,
    nested by time: each gets its depth and the ``req`` of the outermost
    span around it."""
    out: list[Span] = []
    stack: list[Span] = []
    for name, s, e, req in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1].end:
            stack.pop()
        sp = Span(name, s, e, stack[0].req if stack else req, len(stack), thread)
        stack.append(sp)
        out.append(sp)
    return out


def load(path: str) -> list[Span]:
    """The ``engine.*`` spans of every host thread, by start."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: list[Span] = []
    thread = 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = []
            for e in line.events:
                if e.name.startswith(PREFIX):
                    req = dict(e.stats).get("req")
                    events.append((e.name, *trace._span(e), None if req is None else int(req)))
            out += nest(events, thread)
            thread += 1
    out.sort(key=lambda s: (s.start, s.depth))
    return out


def idle_gaps(sm: trace.Summary) -> list[tuple[float, float]]:
    """The device's idle intervals in the window, as ``trace.summarize``
    finds them, in time order."""
    lo, hi = sm.window
    edges = [(lo, lo)] + sm.busy + [(hi, hi)]
    return [(a, b) for (_, a), (b, _) in zip(edges, edges[1:]) if b > a]


def name_gaps(tr: trace.Trace, sm: trace.Summary, spans: list[Span]):
    """``[(start, end, name)]`` of each idle gap: the innermost span that
    covers more than half of it, else the harness phase ``trace`` gives it.

    A span that covers more than half of a gap holds its midpoint, and the
    spans of one thread at one depth are disjoint, so at each depth only the
    last span to start before the midpoint can."""
    levels: dict = {}
    for s in spans:
        levels.setdefault((s.thread, s.depth), []).append(s)
    starts = {k: [s.start for s in v] for k, v in levels.items()}
    out = []
    for a, b in idle_gaps(sm):
        mid, best = (a + b) / 2, None
        for k, level in levels.items():
            i = bisect.bisect_right(starts[k], mid) - 1
            if i < 0:
                continue
            s = level[i]
            covers = 2 * (min(b, s.end) - max(a, s.start)) > b - a
            if covers and (best is None or s.depth > best.depth):
                best = s
        out.append((a, b, best.name if best else trace._phase_at(tr.spans, a, b)))
    return out


def idle_by_name(named, within=None) -> dict:
    """Idle seconds by the name of their gap; with ``within`` (sorted,
    disjoint intervals), only the part of each gap inside them."""
    out: dict = {}
    for a, b, name in named:
        secs = b - a if within is None else trace.overlap([(a, b)], within)
        if secs > 0:
            out[name] = out.get(name, 0.0) + secs
    return out


def _mean_ms(spans: list[Span], name: str) -> float | None:
    secs = [s.end - s.start for s in spans if s.name == name]
    return 1e3 * sum(secs) / len(secs) if secs else None


def decode_gap_ms(sm: trace.Summary, spans: list[Span]) -> float | None:
    """Device-idle ms inside ``engine.decode`` spans per ``engine.step`` in
    them: the time a token waits on the host."""
    decode = trace.union((s.start, s.end) for s in spans if s.name == "engine.decode")
    steps = sum(s.name == "engine.step" and trace.span_of(decode, s.start) is not None
                for s in spans)
    if not steps:
        return None
    return 1e3 * (sum(b - a for a, b in decode) - trace.overlap(sm.busy, decode)) / steps


def ttft_idle_share(sm: trace.Summary, spans: list[Span]) -> float | None:
    """Device-idle share (%) of each request's time to its first token, from
    the start of ``engine.generate`` to the end of ``engine.first_token``,
    summed over requests."""
    start: dict = {}
    windows = []
    for s in spans:
        if s.name == "engine.generate":
            start[s.thread] = s.start
        elif s.name == "engine.first_token" and s.thread in start:
            windows.append((start.pop(s.thread), s.end))
    windows = trace.union(windows)
    total = sum(b - a for a, b in windows)
    if not total:
        return None
    return 100.0 * (1.0 - trace.overlap(sm.busy, windows) / total)


def lookup_ms(spans: list[Span]) -> float | None:
    """Mean host ms of ``engine.lookup`` (``index.match_prefix``)."""
    return _mean_ms(spans, "engine.lookup")


def writeback_host_ms(spans: list[Span]) -> float | None:
    """Mean host ms of ``engine.writeback``, one per miss that writes blocks."""
    return _mean_ms(spans, "engine.writeback")


def report(path: str, top: int = 10) -> dict:
    """Everything above for one trace file."""
    tr = trace.load(path)
    sm = trace.summarize(tr)
    spans = load(path)
    named = name_gaps(tr, sm, spans)
    in_serve = idle_by_name(named, trace.union(sm.serve))
    serve_idle = sum(in_serve.values())
    decode = sm.programs.get("decode_fn")
    step, sync = _mean_ms(spans, "engine.step"), _mean_ms(spans, "engine.sync")
    counts: dict = {}
    for s in spans:
        counts[s.name] = counts.get(s.name, 0) + 1
    return {
        "decode_gap_ms": decode_gap_ms(sm, spans),
        "ttft_idle_share": ttft_idle_share(sm, spans),
        "lookup_ms": lookup_ms(spans),
        "writeback_host_ms": writeback_host_ms(spans),
        "decode_step_ms": 1e3 * decode["seconds"] / decode["calls"] if decode else None,
        "step_plus_sync_ms": step + sync if step is not None and sync is not None else None,
        "span_mean_ms": {n: [c, _mean_ms(spans, n)] for n, c in counts.items()},
        "idle_by_span_s": idle_by_name(named),
        "idle_in_serve_by_span_s": in_serve,
        "idle_in_serve_named_share": (100.0 * sum(v for k, v in in_serve.items()
                                                  if k.startswith(PREFIX)) / serve_idle
                                      if serve_idle else None),
        "idle_gaps": [[f"idle in {n}", b - a]
                      for a, b, n in sorted(named, key=lambda g: g[0] - g[1])[:top]],
    }


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[0]
    print(json.dumps(report(trace.find_xplane(path) if os.path.isdir(path) else path)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
