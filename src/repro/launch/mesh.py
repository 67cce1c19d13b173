"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init
while smoke tests/benches see 1 device.
"""

from __future__ import annotations

import jax

AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


def make_debug_mesh(*, multi_pod: bool = False, model: int = 4):
    """Small mesh with the same axis names (CI / 8-device tests)."""
    n = len(jax.devices())
    if multi_pod:
        shape = (2, max(1, n // (2 * model)), model)
        axes = ("pod", "data", "model")
    else:
        shape = (max(1, n // model), model)
        axes = ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


# TPU v5e hardware constants (roofline targets; the container runs CPU-only)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (~per-chip effective, conservative)
HBM_PER_CHIP = 16 * 2**30  # 16 GiB
