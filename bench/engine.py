"""The benchmark's adapter over the program's served path.

The interface it relies on, and nothing else of the program:

- ``RealEngine.create(arch, max_len=, pool_blocks=, seed=, kernel_mode=,
  layers=)``, and its public fields ``cfg`` (every attribute the
  architecture module's ``program_fields`` names must hold the value it
  gives), ``params`` (replaced by the benchmark's weights, in the tree
  ``weight_shapes`` names) and ``pool`` (its ``layout.block_bytes`` must
  equal the module's ``block_bytes``);
- ``RealEngine.generate(prompt, max_new) -> (tokens, info)`` with
  ``info["hit_tokens"]``, ``info["ttft_s"]`` (to the first token on the
  host), ``info["total_s"]`` and ``info["logits_finite"]``;
- ``repro.launch.compile_cache.enable_compile_cache()``;
- in a kept trace, read by ``bench/spans.py`` alone: the program's spans,
  named in ``repro.serving.real_runner.SPANS`` (``engine.*``), and the
  ``req`` stat on ``engine.generate``.

From the architecture module (``bench/configs/<architecture>.py``) it takes
``program_fields``, ``block_bytes`` and ``make_weights``: the layout checks
live there, so a new form of the model or of its cache is new files only.
"""

from __future__ import annotations

import gc

import jax

_MISSING = "<missing>"


def _shapes(tree) -> dict:
    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def build(program: dict, sizes: dict, arch, pool_blocks: int, max_len: int,
          seed: int, kernel_mode: str = "pallas"):
    """A RealEngine serving the benchmark's own weights, made from ``seed``."""
    from repro.serving.real_runner import RealEngine

    eng = RealEngine.create(
        program["arch"], max_len=max_len, pool_blocks=pool_blocks, seed=0,
        kernel_mode=kernel_mode, layers=program["layers"],
    )
    wrong = {f: (getattr(eng.cfg, f, _MISSING), v) for f, v in arch.program_fields(sizes).items()
             if getattr(eng.cfg, f, _MISSING) != v}
    if wrong:
        raise SystemExit(f"program config differs from the configuration file: {wrong}")
    if eng.pool.layout.block_bytes != arch.block_bytes(sizes):
        raise SystemExit("pool block layout differs from the configuration's")
    expected = _shapes(eng.params)
    eng.params = None  # free the program's own weights before making ours
    gc.collect()
    weights = arch.make_weights(sizes, seed)
    if _shapes(weights) != expected:
        raise SystemExit("weight layout differs from the program's parameter tree")
    eng.params = weights
    jax.block_until_ready(weights)
    return eng, weights
