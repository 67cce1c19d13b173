"""The benchmark's adapter over the program's served path.

The interface it relies on, and nothing else of the program:

- ``RealEngine.create(arch, max_len=, pool_blocks=, seed=, kernel_mode=,
  layers=)``, and its public fields ``cfg`` (sizes checked against the
  configuration file), ``params`` (replaced by the benchmark's weights, in
  the tree ``weight_shapes`` names) and ``pool`` (its ``layout.block_bytes``);
- ``RealEngine.generate(prompt, max_new) -> (tokens, info)`` with
  ``info["hit_tokens"]``, ``info["ttft_s"]`` (to the first token on the
  host), ``info["total_s"]`` and ``info["logits_finite"]``;
- ``repro.launch.compile_cache.enable_compile_cache()``.
"""

from __future__ import annotations

import gc

import jax

from bench import flops

# configuration key -> RealEngine.cfg field
_CFG_FIELDS = {
    "layers": "n_layers", "d": "d_model", "heads": "n_heads",
    "kv_heads": "n_kv_heads", "head_dim": "head_dim", "ff": "d_ff",
    "vocab": "vocab_size", "theta": "rope_theta", "eps": "norm_eps",
}


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
    wrong = {k: (getattr(eng.cfg, f), sizes[k]) for k, f in _CFG_FIELDS.items()
             if getattr(eng.cfg, f) != sizes[k]}
    if wrong or eng.cfg.qkv_bias or eng.cfg.tie_embeddings or eng.cfg.act != "silu":
        raise SystemExit(f"program config differs from the configuration file: {wrong}")
    if eng.pool.layout.block_bytes != flops.block_bytes(sizes):
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
