"""Device time of the compiled prefill per 1,000 prompt tokens prefilled,
over the prefill calls in the traced slice (ms). ``RealEngine`` jits a
``functools.partial`` of ``prefill_fn``, which JAX names ``_unknown``; a
program that names it ``prefill_fn`` is read the same."""


def read(ctx):
    calls = ctx.calls_by_request("prefill_fn", "_unknown")
    tokens = sum(r.prompt_len for r, _ in calls)
    if not tokens:
        return None
    return 1e3 * sum(secs for _, secs in calls) / (tokens / 1e3)
