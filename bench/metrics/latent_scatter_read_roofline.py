"""Roofline share of the compiled pool-to-cache copy (``kv_scatter_read``)
moving one latent part a layer on the hit path (%): 2 x the hit blocks'
bytes (the architecture module's ``block_bytes``) at the peak HBM rate,
over the program's device time. The same reading as
``kv_scatter_read_roofline``, on the latent layout's cells."""

from bench.metrics import kv_scatter_read_roofline


def read(ctx):
    return kv_scatter_read_roofline.read(ctx)
