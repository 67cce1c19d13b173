"""Roofline share of the compiled pool-to-cache copy (``kv_scatter_read``)
on the hit path (%): the least time the chip needs to read the hit blocks
and write them into the cache (2 x their bytes at the peak HBM rate; the
zero fill of unused cache slots is not needed work), over the program's
device time, summed over the calls in the traced slice."""

from bench import flops


def read(ctx):
    calls = ctx.calls_by_request("kv_scatter_read")
    if not calls:
        return None
    need = sum(flops.copy_bytes(ctx.arch, ctx.sizes, r.hit_tokens // flops.BLOCK_TOKENS)
               for r, _ in calls) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * need / sum(secs for _, secs in calls)
