"""Per-kernel allclose sweeps (Pallas interpret=True vs pure-jnp oracles)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


RNG = np.random.default_rng(0)


def _randn(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# flash attention: shapes x dtypes sweep
# ---------------------------------------------------------------------------

FLASH_SHAPES = [
    # (b, sq, skv, hq, hkv, d)
    (1, 64, 64, 4, 4, 64),      # MHA
    (2, 128, 128, 8, 2, 64),    # GQA 4:1
    (1, 96, 96, 4, 1, 128),     # MQA, ragged seq
    (2, 128, 128, 16, 16, 128), # olmo-like head ratio
]


@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(shape, dtype):
    b, sq, skv, hq, hkv, d = shape
    q = _randn((b, sq, hq, d), dtype)
    k = _randn((b, skv, hkv, d), dtype)
    v = _randn((b, skv, hkv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=True, mode="interpret",
                              block_q=32, block_kv=32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_flash_attention_noncausal():
    q = _randn((1, 64, 4, 64), jnp.float32)
    k = _randn((1, 64, 4, 64), jnp.float32)
    v = _randn((1, 64, 4, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, mode="interpret",
                              block_q=32, block_kv=32)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

PAGED_SHAPES = [
    # (b, hq, hkv, d, bt, max_blocks, n_blocks)
    (3, 8, 2, 64, 16, 6, 32),
    (2, 4, 4, 128, 16, 4, 16),
    (1, 16, 8, 64, 32, 3, 8),
]


@pytest.mark.parametrize("shape", PAGED_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_oracle(shape, dtype):
    b, hq, hkv, d, bt, mb, nb = shape
    q = _randn((b, hq, d), dtype)
    pool = _randn((nb, 2, bt, hkv, d), dtype)
    tbl = jnp.asarray(
        np.stack([RNG.choice(nb, size=mb, replace=False) for _ in range(b)]),
        jnp.int32,
    )
    ctx = jnp.asarray(RNG.integers(1, mb * bt, size=(b,)), jnp.int32)
    out = ops.paged_attention(q, pool, tbl, ctx, mode="interpret")
    want = ref.paged_attention_ref(q, pool, tbl, ctx)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


# ---------------------------------------------------------------------------
# gather-write / scatter-read roundtrip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,n_slots,bt,hkv,hd", [(3, 8, 16, 2, 32), (1, 4, 8, 1, 16)])
def test_kv_transfer_roundtrip(dtype, L, n_slots, bt, hkv, hd):
    k = _randn((L, n_slots * bt, hkv, hd), dtype)
    v = _randn((L, n_slots * bt, hkv, hd), dtype)
    slots = jnp.asarray(RNG.choice(n_slots, size=3, replace=False), jnp.int32)
    blocks_p = ops.kv_gather_write(k, v, slots, bt, mode="interpret")
    blocks_r = ref.kv_gather_write_ref(k, v, slots, bt)
    assert jnp.array_equal(blocks_p, blocks_r)
    k2, v2 = ops.kv_scatter_read(blocks_p, slots, n_slots, mode="interpret")
    for s in np.asarray(slots):
        assert jnp.array_equal(k2[:, s * bt : (s + 1) * bt], k[:, s * bt : (s + 1) * bt])
        assert jnp.array_equal(v2[:, s * bt : (s + 1) * bt], v[:, s * bt : (s + 1) * bt])
    # every slot is defined: the unwritten ones are zero, as in the oracle
    k3, v3 = ops.kv_scatter_read(blocks_p, slots, n_slots, mode="jnp")
    assert jnp.array_equal(k2, k3) and jnp.array_equal(v2, v3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,n_slots,bt,width", [(5, 8, 16, 576), (3, 4, 16, 48)])
def test_one_part_transfer_roundtrip(dtype, L, n_slots, bt, width):
    """A latent cache, one part of any width a layer: pool blocks of shape
    (L, bt, width) written and read back bit for bit, the unwritten slots
    zero, the kernels equal to the oracle."""
    from repro.core.pool import BelugaPool, PoolLayout

    layout = PoolLayout.latent(bt, L, width)
    assert layout.n_fragments == L and layout.block_bytes == L * bt * width * 2
    pool = BelugaPool(layout, n_blocks=8, n_shards=8, backing="jax")
    assert pool.data.shape == (8, L, bt, width)
    lat = _randn((L, n_slots * bt, width), dtype)
    slots = jnp.asarray(RNG.choice(n_slots, size=3, replace=False), jnp.int32)
    blocks = ops.kv_gather_write(lat, None, slots, bt, mode="interpret")
    assert blocks.shape == (3, L, bt, width)
    assert jnp.array_equal(blocks, ref.kv_gather_write_ref(lat, None, slots, bt))
    (back,) = ops.kv_scatter_read(blocks, slots, n_slots, n_parts=1, mode="interpret")
    written = np.zeros(n_slots * bt, bool)
    for s in np.asarray(slots):
        written[s * bt : (s + 1) * bt] = True
    assert jnp.array_equal(back[:, written], lat[:, written])
    assert not jnp.any(back[:, ~written])
    (oracle,) = ops.kv_scatter_read(blocks, slots, n_slots, n_parts=1, mode="jnp")
    assert jnp.array_equal(back, oracle)


@pytest.mark.parametrize("kernel", ["kv_gather_write", "kv_scatter_read"])
def test_pallas_mode_raises_off_tpu(kernel):
    """No silent fallback: off a TPU, compiled Pallas is refused, never
    swapped for the interpreter or the oracle."""
    if jax.default_backend() == "tpu":
        pytest.skip("on a TPU, mode='pallas' is the compiled kernel")
    k = jnp.zeros((1, 32, 1, 16), jnp.float32)
    slots = jnp.arange(2, dtype=jnp.int32)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        if kernel == "kv_gather_write":
            ops.kv_gather_write(k, k, slots, 16, mode="pallas")
        else:
            blocks = ops.kv_gather_write(k, k, slots, 16, mode="jnp")
            ops.kv_scatter_read(blocks, slots, 2, mode="pallas")
    with pytest.raises(ValueError, match="unknown kernel mode"):
        ops.kv_gather_write(k, k, slots, 16, mode="auto")


def test_sparse_gather_matches_oracle():
    kv = _randn((64, 2, 32), jnp.float32)
    ids = jnp.asarray(RNG.choice(64, size=17, replace=False), jnp.int32)
    out = ops.sparse_kv_gather(kv, ids, mode="interpret")
    assert jnp.array_equal(out, ref.sparse_kv_gather_ref(kv, ids))


# ---------------------------------------------------------------------------
# hypothesis property tests on kernel invariants
# ---------------------------------------------------------------------------

from hypothesis import given, settings, strategies as st  # noqa: E402


@settings(max_examples=20, deadline=None)
@given(
    n_sel=st.integers(1, 16),
    n_tokens=st.integers(16, 64),
)
def test_sparse_gather_property(n_sel, n_tokens):
    kv = jnp.arange(n_tokens * 2 * 8, dtype=jnp.float32).reshape(n_tokens, 2, 8)
    rng = np.random.default_rng(n_sel * 977 + n_tokens)
    ids = jnp.asarray(rng.integers(0, n_tokens, size=n_sel), jnp.int32)
    out = ops.sparse_kv_gather(kv, ids, mode="interpret")
    assert out.shape == (n_sel, 2, 8)
    for i, t in enumerate(np.asarray(ids)):
        assert jnp.array_equal(out[i], kv[t])


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_gather_scatter_is_permutation_safe(data):
    """gather_write then scatter_read restores slots for ANY slot permutation."""
    n_slots = 6
    L, bt, hkv, hd = 2, 8, 1, 16
    n_blocks = data.draw(st.integers(1, n_slots))
    slots = data.draw(
        st.permutations(list(range(n_slots))).map(lambda p: p[:n_blocks])
    )
    k = jnp.asarray(
        np.random.default_rng(42).normal(size=(L, n_slots * bt, hkv, hd)),
        jnp.float32,
    )
    slots_arr = jnp.asarray(list(slots), jnp.int32)
    blocks = ops.kv_gather_write(k, k, slots_arr, bt, mode="jnp")
    k2, v2 = ops.kv_scatter_read(blocks, slots_arr, n_slots, mode="jnp")
    for s in slots:
        assert jnp.array_equal(k2[:, s * bt : (s + 1) * bt], k[:, s * bt : (s + 1) * bt])


# ---------------------------------------------------------------------------
# ssd_chunk (Mamba-2 intra-chunk SSD)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nb,lc,nh,hp,n,tile", [(2, 32, 8, 16, 8, 4), (1, 16, 4, 8, 16, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_matches_oracle(nb, lc, nh, hp, n, tile, dtype):
    x = _randn((nb, lc, nh, hp), dtype)
    a = jnp.asarray(-np.abs(RNG.normal(size=(nb, lc, nh))) * 0.1, jnp.float32)
    b = _randn((nb, lc, nh, n), dtype)
    c = _randn((nb, lc, nh, n), dtype)
    yp, sp = ops.ssd_chunk(x, a, b, c, nh_tile=tile, mode="interpret")
    yr, sr = ops.ssd_chunk(x, a, b, c, mode="jnp")
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), atol=tol, rtol=tol)


def test_ssd_chunk_matches_model_path():
    """Kernel output equals the model's _ssd_chunked intra-chunk term on a
    single chunk (the chunk state must agree exactly with the scan path)."""
    from repro.models.mamba import _ssd_chunked

    rng = np.random.default_rng(3)
    b, s, nh, hp, n = 1, 32, 4, 8, 8
    x = jnp.asarray(rng.normal(size=(b, s, nh, hp)), jnp.float32)
    a = jnp.asarray(-np.abs(rng.normal(size=(b, s, nh))) * 0.1, jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, 1, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, 1, n)), jnp.float32)
    y_model, state_model = _ssd_chunked(x, a, bm, cm, chunk=s)  # one chunk
    bh = jnp.broadcast_to(bm, (b, s, nh, n))
    ch = jnp.broadcast_to(cm, (b, s, nh, n))
    yk, sk = ops.ssd_chunk(x, a, bh, ch, nh_tile=4, mode="interpret")
    np.testing.assert_allclose(np.asarray(y_model[:, :s].reshape(b, s, nh, hp)),
                               np.asarray(yk), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(state_model), np.asarray(sk[0][None]),
                               atol=1e-4, rtol=1e-4)
