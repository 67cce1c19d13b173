"""Share of the window's prompt tokens served from the pool (%): the
program's own count, ``info["hit_tokens"]``, over the prompt tokens."""


def read(ctx):
    recs = [r for r in ctx.records if r.ok]
    prompt = sum(r.prompt_len for r in recs)
    if not prompt or not any(r.hit_tokens for r in recs):
        return None
    return 100.0 * sum(r.hit_tokens for r in recs) / prompt
