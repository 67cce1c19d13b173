"""Whole-step model FLOP utilization (%) of a latent-attention MoE
configuration, through its architecture module's counts (expanded
attention, routed experts at their expectation on the chip): the same
reading as ``step_mfu``, on that configuration's cells."""

from bench.metrics import step_mfu


def read(ctx):
    return step_mfu.read(ctx)
