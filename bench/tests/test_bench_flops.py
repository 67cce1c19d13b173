"""Peaks table, parameter, block, FLOP and byte counts (CPU, shapes only)."""

from __future__ import annotations

import dataclasses

import jax
import pytest

from bench import flops, run

CELLS = {c: run.Cell.load(w) for c, w in (
    ("qwen3-32b-noqknorm-4L", "qwen3-32b-noqknorm-4L.shared-sysprompt"),
    ("internlm2-1.8b", "internlm2-1.8b.shared-sysprompt"))}
SIZES = {c: cell.sizes for c, cell in CELLS.items()}
DENSE = CELLS["internlm2-1.8b"].arch  # bench/configs/dense_gqa.py, as Cell.load loads it


@pytest.mark.parametrize("config, n_params, block", [
    # by hand: 2*V*d + L*(2*d*hq*hd + 2*d*hkv*hd + 3*d*ff + 2*d) + d
    ("qwen3-32b-noqknorm-4L", 3_506_222_080, 262_144),  # 8 fragments x 16 x 8 x 128 x 2 B
    ("internlm2-1.8b", 1_889_110_016, 1_572_864),  # 48 fragments
])
def test_params_and_block_bytes(config, n_params, block):
    arch, s = CELLS[config].arch, SIZES[config]
    assert arch.params(s) == n_params
    assert arch.block_bytes(s) == block
    assert arch.kv_bytes_per_token(s) == block // 16


@pytest.mark.parametrize("config, prompt_len, hit, n_out, want", [
    # the counts before they moved into the architecture module, pinned
    ("qwen3-32b-noqknorm-4L", 8208, 8192, 64, 492968083456.0),  # a shared-prefix hit
    ("qwen3-32b-noqknorm-4L", 8448, 8192, 8, 1325275807744.0),  # the longest tail
    ("qwen3-32b-noqknorm-4L", 768, 0, 17, 3124933427200.0),  # a miss
    ("internlm2-1.8b", 8208, 8192, 64, 390691553280.0),
    ("internlm2-1.8b", 8448, 8192, 8, 1227682480128.0),
    ("internlm2-1.8b", 768, 0, 17, 2434544959488.0),
])
def test_request_flops_pinned(config, prompt_len, hit, n_out, want):
    assert flops.request_flops(CELLS[config].arch, SIZES[config], prompt_len, hit, n_out) == want


@pytest.mark.parametrize("config", sorted(SIZES))
def test_weights_match_the_program_tree(config):
    """The benchmark's weight layout is the program's parameter tree, leaf
    for leaf, and holds the module's params() values (shapes only, no arrays)."""
    from repro.configs.base import RuntimeConfig
    from repro.configs.registry import get_config
    from repro.models import Model

    cell = run.Cell.load(config + ".shared-sysprompt")
    prog = cell.config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), n_layers=prog["layers"])
    model = Model(cfg, RuntimeConfig(remat="none", decode_kv="replicated"))
    theirs = jax.eval_shape(model.init, jax.random.key(0))
    ours = jax.eval_shape(lambda: cell.arch.make_weights(cell.sizes, 0))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)  # noqa: E731
    assert shape(ours) == shape(theirs)
    assert sum(a.size for a in jax.tree.leaves(ours)) == cell.arch.params(cell.sizes)


def test_request_flops_by_hand():
    s = {"layers": 1, "d": 4, "heads": 2, "kv_heads": 1, "head_dim": 2, "ff": 8, "vocab": 10}
    # weights a token multiplies: wq + wo (4x4 each), wk + wv (4x2 each), 3 MLP (4x8)
    mat = 2 * 4 * 4 + 2 * 4 * 2 + 3 * 4 * 8
    assert DENSE.token_flops(s, 3) == 2 * mat + 4 * 3 * 2 * 2
    # a miss of 3 prompt tokens and 2 output tokens: positions 0..3, logits twice
    want = sum(2 * mat + 4 * c * 2 * 2 for c in (1, 2, 3, 4)) + 2 * (2 * 4 * 10)
    assert flops.request_flops(DENSE, s, 3, 0, 2) == want
    # a full hit of 2 of the 3 prompt tokens computes positions 2..3
    assert flops.request_flops(DENSE, s, 3, 2, 2) == sum(
        2 * mat + 4 * c * 2 * 2 for c in (3, 4)) + 2 * (2 * 4 * 10)
    assert flops.copy_bytes(DENSE, s, 3) == 2 * 3 * DENSE.block_bytes(s)


def test_peaks_by_device_kind():
    v5e = flops.peak("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peak("cpu")
