"""Serving launcher: real tokens through the full Beluga KVCache stack.

    # on a TPU: qwen3-32b at published widths, depth cut to 4 layers
    python -m repro.launch.serve --arch qwen3-32b --layers 4 \
        --prompt-len 512 --max-len 1024 --pool-blocks 512
    # on the CPU: reduced widths, Pallas kernels run by the interpreter
    JAX_PLATFORMS=cpu python -m repro.launch.serve --kernel-mode interpret

Runs the model end to end: prompts -> prefix-index lookup -> pool fetch
(kv_scatter_read) or prefill -> pool writeback (kv_gather_write) -> greedy
decode. Demonstrates real cross-request KV reuse through the shared pool:
the prompts share their first half, so all but the first fetch it, and
two exact repeats of the first prompt skip prefill entirely.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def make_prompts(
    vocab: int,
    n_prompts: int,
    prompt_len: int,
    shared_len: int,
    repeats: int,
    seed: int,
) -> list[list[int]]:
    """``n_prompts`` prompts sharing a ``shared_len`` prefix, then
    ``repeats`` exact copies of the first (the only one written back:
    a prefix hit does not write back)."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, size=shared_len).tolist()
    prompts = [
        prefix + rng.integers(0, vocab, size=prompt_len - shared_len).tolist()
        for _ in range(n_prompts)
    ]
    return prompts + [prompts[0]] * repeats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--layers", type=int, default=None,
                    help="published widths at this depth (default: reduced config)")
    ap.add_argument("--kernel-mode", default="pallas",
                    choices=("pallas", "interpret", "jnp"))
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--pool-blocks", type=int, default=256)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.real_runner import RealEngine

    enable_compile_cache()
    eng = RealEngine.create(
        args.arch, max_len=args.max_len, pool_blocks=args.pool_blocks,
        kernel_mode=args.kernel_mode, layers=args.layers,
    )
    prompts = make_prompts(
        eng.cfg.vocab_size, args.requests, args.prompt_len,
        shared_len=args.prompt_len // 2, repeats=2, seed=0,
    )
    t0 = time.time()
    for i, p in enumerate(prompts):
        out, info = eng.generate(p, max_new=args.gen)
        print(
            f"req {i}: hit {info['hit_tokens']}/{len(p)} prompt tokens, "
            f"ttft {info['ttft_s']*1e3:.1f} ms, total {info['total_s']*1e3:.1f} ms, "
            f"{len(out)} tokens -> {out[:8]}..."
        )
    print(f"total {time.time()-t0:.1f}s; index: {eng.index.stats()}")


if __name__ == "__main__":
    main()
