"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

From the device planes (``/device:TPU:<n>``): every operation's interval
and the program (``hlo_module``) it belongs to. From the host plane: the
harness's own annotations, ``serve`` around each ``generate`` call and
``wait`` while the loop sleeps until the next request is due. All times
are seconds on the trace's clock, which the profiler shares between host
and device planes.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

PHASES = ("serve", "wait")
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    name: str
    program: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: list[Op]  # device operations of the first device, by start
    calls: list[Op]  # executions of compiled programs on that device, by start
    spans: list[tuple[str, float, float]]  # host annotations (phase, start, end)
    n_devices: int


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def program_name(module: str) -> str:
    """``jit_decode_fn(123)`` -> ``decode_fn``: the jitted function's name."""
    m = re.sub(r"\(\d+\)$", "", module)
    return m[4:] if m.startswith("jit_") else m


def op_name(hlo: str) -> str:
    """``%fusion.71 = bf16[16,128]{...} fusion(...)`` -> ``fusion.71``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _span(e) -> tuple[float, float]:
    return e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = sorted((p for p in data.planes if _DEVICE.match(p.name)), key=lambda p: p.name)
    ops: list[Op] = []
    calls: list[Op] = []
    if devices:
        for line in devices[0].lines:
            if line.name == _OPS_LINE:
                for e in line.events:
                    program = program_name(str(dict(e.stats).get("hlo_module", "")))
                    ops.append(Op(f"{program}/{op_name(e.name)}", program, *_span(e)))
            elif line.name == _MODULES_LINE:
                for e in line.events:
                    calls.append(Op(e.name, program_name(e.name), *_span(e)))
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in PHASES:
                    spans.append((e.name, *_span(e)))
    ops.sort(key=lambda o: o.start)
    calls.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s[1])
    return Trace(ops, calls, spans, len(devices))


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(xs, ys) -> float:
    """Total length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


@dataclasses.dataclass
class Summary:
    window: tuple[float, float]
    busy: list[tuple[float, float]]  # device-busy intervals, merged
    serve: list[tuple[float, float]]  # host `serve` spans, one per request, in order
    programs: dict  # program -> {"seconds", "calls", "starts", "call_seconds"}
    ops: dict  # op name -> seconds
    gaps: list  # (seconds, phase) of the idle gaps, longest first

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _phase_at(spans, a: float, b: float) -> str:
    """The host phase that covers most of [a, b]."""
    best, name = 0.0, "other"
    for ph, s, e in spans:
        if e <= a:
            continue
        if s >= b:
            break
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, name = ov, ph
    return name


def summarize(tr: Trace) -> Summary:
    """Window: from the first to the last harness annotation."""
    lo = min([s for _, s, _ in tr.spans] + [o.start for o in tr.ops])
    hi = max([e for _, _, e in tr.spans] + [o.end for o in tr.ops])
    busy = union((o.start, o.end) for o in tr.ops)
    serve = [(s, e) for ph, s, e in tr.spans if ph == "serve"]
    ops: dict = {}
    for o in tr.ops:
        ops[o.name] = ops.get(o.name, 0.0) + (o.end - o.start)
    programs: dict = {}
    for c in tr.calls:
        p = programs.setdefault(
            c.program, {"seconds": 0.0, "calls": 0, "starts": [], "call_seconds": []})
        p["seconds"] += c.end - c.start
        p["calls"] += 1
        p["starts"].append(c.start)
        p["call_seconds"].append(c.end - c.start)
    edges = [(lo, lo)] + busy + [(hi, hi)]
    gaps = []
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            gaps.append((b - a, _phase_at(tr.spans, a, b)))
    gaps.sort(reverse=True)
    return Summary((lo, hi), busy, serve, programs, ops, gaps)


def breakdown(sm: Summary, top: int = 10) -> dict:
    ops = sorted(sm.ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = [[f"idle in {ph}", secs] for secs, ph in sm.gaps[:top]]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": gaps}


def span_of(spans: list[tuple[float, float]], t: float) -> int | None:
    """Index of the span (sorted, disjoint) that holds time ``t``."""
    i = bisect.bisect_right([a for a, _ in spans], t) - 1
    return i if i >= 0 and t <= spans[i][1] else None


def idle_by_phase(sm: Summary) -> dict:
    out: dict = {}
    for secs, ph in sm.gaps:
        out[ph] = out.get(ph, 0.0) + secs
    return out
