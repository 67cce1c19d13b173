"""Whole-step model FLOP utilization (%): the operations the window's
requests need (``flops.request_flops``: prompt positions not served from
the pool, decode steps, needed logits), over the seconds the engine spent
serving them (host clock, ``generate`` start to return), over the chip's
bf16 peak. Taken over in-service time, not window time: in an open loop
below the knee the window's length measures the offered rate."""

from bench import flops


def read(ctx):
    recs = [r for r in ctx.records if r.ok]
    busy = sum(r.end - r.start for r in recs)
    if not busy:
        return None
    work = sum(flops.request_flops(ctx.arch, ctx.sizes, r.prompt_len, r.hit_tokens, r.n_out)
               for r in recs)
    return 100.0 * work / busy / ctx.peak["bf16_flops_per_s"]
