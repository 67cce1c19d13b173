"""qwen3-32b - exact assigned config.

paper's own eval model: 64L d_model=5120 64H x 128 (GQA kv=8) d_ff=25600 vocab=151936 [arXiv:2505.09388]

Single source of truth lives in ``repro.configs.registry.QWEN3_32B``;
this module exposes it as ``CONFIG`` (and a reduced smoke config) for the
``--arch qwen3-32b`` selector.
"""

from repro.configs.registry import QWEN3_32B as CONFIG  # noqa: F401
from repro.configs.registry import reduced_config

SMOKE_CONFIG = reduced_config("qwen3-32b")
