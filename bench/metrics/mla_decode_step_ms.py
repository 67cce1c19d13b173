"""Device time of one call of the compiled decode step (``decode_fn``) on
a latent-attention configuration: absorbed MLA over the latent cache and
the MoE layers' share, averaged over the calls in the traced slice (ms).
The same reading as ``decode_step_ms``, kept apart so that the dense
cells' metric and this one are judged each on its own cells."""

from bench.metrics import decode_step_ms


def read(ctx):
    return decode_step_ms.read(ctx)
