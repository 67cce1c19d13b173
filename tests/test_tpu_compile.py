"""Compile-only rehearsals for a described TPU v5e chip (no chip attached).

The TPU compiler refuses here what it would refuse on the chip: tiling a
kernel cannot use, more fast memory than a kernel may hold, a program that
does not fit in HBM. Shapes are qwen3-32b's published KV and model widths,
and deepseek-v3-ep32's at the 5 layers of ``bench/configs/deepseek-v3-ep32-5L.json``.

The topology is described inside the module fixture, never at import: only
one process may load the TPU library, and every pytest worker imports this
file. Keep every such rehearsal in this one file.
"""

from __future__ import annotations

import dataclasses
import os

import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config

HBM_BYTES = 16 * 10**9  # TPU v5e, 16 GB
QWEN3 = get_config("qwen3-32b")
BT, N_SLOTS, N_BLOCKS = 16, 64, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("n_layers", [4, 64])
@pytest.mark.parametrize("kernel", ["kv_gather_write", "kv_scatter_read"])
def test_kv_transfer_kernel_compiles_for_v5e(one_chip, kernel, n_layers):
    from repro.kernels import kv_transfer

    hkv, hd = QWEN3.n_kv_heads, QWEN3.head_dim
    ids = _sds((N_BLOCKS,), jnp.int32, one_chip)
    if kernel == "kv_gather_write":
        kc = _sds((n_layers, N_SLOTS * BT, hkv, hd), jnp.bfloat16, one_chip)
        lowered = jax.jit(kv_transfer.kv_gather_write, static_argnums=3).lower(
            kc, kc, ids, BT
        )
    else:
        blocks = _sds((N_BLOCKS, 2 * n_layers, BT, hkv, hd), jnp.bfloat16, one_chip)
        lowered = jax.jit(kv_transfer.kv_scatter_read, static_argnums=2).lower(
            blocks, ids, N_SLOTS
        )
    assert "tpu_custom_call" in lowered.as_text()
    assert _device_bytes(lowered.compile()) < HBM_BYTES


@pytest.mark.parametrize("kernel", ["kv_gather_write", "kv_scatter_read"])
def test_one_part_transfer_kernel_compiles_for_v5e(one_chip, kernel):
    """The latent layout's copies: one 576-wide part a layer, 5 layers."""
    from repro.kernels import kv_transfer

    L, width = 5, 576
    ids = _sds((N_BLOCKS,), jnp.int32, one_chip)
    if kernel == "kv_gather_write":
        lat = _sds((L, N_SLOTS * BT, width), jnp.bfloat16, one_chip)
        lowered = jax.jit(kv_transfer.kv_gather_write, static_argnums=3).lower(
            lat, None, ids, BT
        )
    else:
        blocks = _sds((N_BLOCKS, L, BT, width), jnp.bfloat16, one_chip)
        lowered = jax.jit(
            lambda b, i: kv_transfer.kv_scatter_read(b, i, N_SLOTS, n_parts=1)
        ).lower(blocks, ids)
    assert "tpu_custom_call" in lowered.as_text()
    assert _device_bytes(lowered.compile()) < HBM_BYTES


def _deepseek_5l(one_chip):
    """deepseek-v3-ep32 at published widths and 5 layers, with an
    8,512-slot cache: the model, its weights and cache as shapes."""
    from repro.configs.base import RuntimeConfig
    from repro.configs.registry import cut_depth
    from repro.models import Model

    model = Model(cut_depth(get_config("deepseek-v3-ep32"), 5),
                  RuntimeConfig(remat="none", decode_kv="replicated"))
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda x: _sds(x.shape, x.dtype, one_chip), t
    )
    params = on_chip(jax.eval_shape(model.init, jax.random.key(0)))
    return model, params, on_chip(jax.eval_shape(lambda: model.init_cache(1, 8512)))


@pytest.mark.parametrize("program", ["decode_fn", "extend_fn", "prefill_fn"])
def test_deepseek_programs_compile_for_v5e(one_chip, program):
    """The served programs of the latent-attention MoE model: a decode step, a
    256-token tail chunk, and an 8,192-token prefill, weights as arguments."""
    from repro.serving.real_runner import TAIL_CHUNK

    model, params, cache = _deepseek_5l(one_chip)
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    if program == "decode_fn":
        lowered = jax.jit(model.decode_fn).lower(params, cache, i32(1), i32(1))
    elif program == "extend_fn":
        lowered = jax.jit(model.extend_fn).lower(params, cache, i32(1, TAIL_CHUNK), i32(), i32())
    else:
        fn = lambda p, b: model.prefill_fn(p, b, max_len=8512)  # noqa: E731
        lowered = jax.jit(fn).lower(params, {"tokens": i32(1, 8192)})
    _assert_fits_with_weights_as_arguments(lowered.compile(), params)


def _one_layer_qwen3(one_chip):
    """qwen3-32b at published widths, one layer deep; its weights as
    shapes on the described chip (``jax.eval_shape``); a shape putter."""
    from repro.configs.base import RuntimeConfig
    from repro.models import Model

    model = Model(dataclasses.replace(QWEN3, n_layers=1),
                  RuntimeConfig(remat="none", decode_kv="replicated"))
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda x: _sds(x.shape, x.dtype, one_chip), t
    )
    return model, on_chip(jax.eval_shape(model.init, jax.random.key(0))), on_chip


def _assert_fits_with_weights_as_arguments(compiled, params):
    n_weight_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert compiled.memory_analysis().argument_size_in_bytes >= n_weight_bytes
    assert _device_bytes(compiled) < HBM_BYTES


def test_published_width_decode_step_compiles_for_v5e(one_chip):
    """One decode step of qwen3-32b at published widths, one layer deep,
    with the weights as arguments (shapes from ``jax.eval_shape``)."""
    model, params, on_chip = _one_layer_qwen3(one_chip)
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(1, 1024)))
    tok = _sds((1,), jnp.int32, one_chip)
    compiled = jax.jit(model.decode_fn).lower(params, cache, tok, tok).compile()
    _assert_fits_with_weights_as_arguments(compiled, params)


def test_published_width_tail_extend_compiles_for_v5e(one_chip):
    """One ``extend_fn`` call of qwen3-32b at published widths, one layer
    deep: a 256-token chunk of a hit's tail over the shared-prefix cells'
    8,512-slot cache, with the weights as arguments."""
    from repro.serving.real_runner import TAIL_CHUNK

    model, params, on_chip = _one_layer_qwen3(one_chip)
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(1, 8512)))
    tokens = _sds((1, TAIL_CHUNK), jnp.int32, one_chip)
    scalar = _sds((), jnp.int32, one_chip)
    compiled = jax.jit(model.extend_fn).lower(params, cache, tokens, scalar, scalar).compile()
    _assert_fits_with_weights_as_arguments(compiled, params)
