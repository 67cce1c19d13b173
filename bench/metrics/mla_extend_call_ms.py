"""Device time of one call of the compiled tail prefill (``extend_fn``):
``TAIL_CHUNK`` tail tokens of a hit through absorbed MLA against the
fetched latent cache, averaged over the calls in the traced slice (ms)."""


def read(ctx):
    p = ctx.program("extend_fn")
    if not p or not p["calls"]:
        return None
    return 1e3 * p["seconds"] / p["calls"]
