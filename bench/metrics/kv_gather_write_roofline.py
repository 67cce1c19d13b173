"""Roofline share of the compiled cache-to-pool-block copy
(``kv_gather_write``) on the miss path (%): 2 x the bytes of the blocks
written at the peak HBM rate, over the program's device time, summed over
the calls in the traced slice."""

from bench import flops


def read(ctx):
    calls = ctx.calls_by_request("kv_gather_write")
    if not calls:
        return None
    need = sum(flops.copy_bytes(ctx.arch, ctx.sizes, r.prompt_len // flops.BLOCK_TOKENS)
               for r, _ in calls) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * need / sum(secs for _, secs in calls)
