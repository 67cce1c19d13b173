"""Share of in-service time in which no operation runs on the device (%):
1 - (device-busy time inside the harness's ``serve`` spans) / (time in
those spans), over the traced slice."""

from bench import trace


def read(ctx):
    sm = ctx.trace
    if sm is None:
        return None
    serve = trace.union(sm.serve)
    total = sum(b - a for a, b in serve)
    if not total:
        return None
    return 100.0 * (1.0 - trace.overlap(sm.busy, serve) / total)
