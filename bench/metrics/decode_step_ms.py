"""Device time of one call of the compiled decode step (``decode_fn``),
averaged over the calls in the traced slice (ms)."""


def read(ctx):
    p = ctx.program("decode_fn")
    if not p or not p["calls"]:
        return None
    return 1e3 * p["seconds"] / p["calls"]
