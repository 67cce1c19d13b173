"""Sharded-path integration tests (8 fake devices, subprocess-isolated so
the fake device count never leaks into the main test session)."""

import os
import subprocess
import sys
import textwrap

import pytest


def _run(code: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=900,
    )
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-3000:])
    assert "PASS" in out.stdout


HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools, dataclasses
import jax, jax.numpy as jnp
from repro.configs.registry import reduced_config
from repro.configs.base import RuntimeConfig
from repro.models import Model
from repro.distributed.sharding import AxisRules
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = AxisRules.create(mesh)
"""


@pytest.mark.slow  # subprocess model compiles: minutes
def test_sharded_train_and_interleaved_decode():
    _run(HEADER + textwrap.dedent("""
        rt = RuntimeConfig(remat="full", attn_chunk_q=16, attn_chunk_kv=16,
                           decode_kv="pool_interleaved")
        for arch in ["command-r-35b", "jamba-1.5-large-398b", "mamba2-2.7b"]:
            cfg = reduced_config(arch)
            m = Model(cfg, rt, rules)
            params = jax.jit(m.init, out_shardings=m.param_shardings())(jax.random.key(0))
            tokens = jnp.ones((4, 32), jnp.int32)
            with mesh:
                loss, _ = jax.jit(m.loss_fn)(params, {"tokens": tokens, "labels": tokens})
                assert bool(jnp.isfinite(loss)), arch
                cache = jax.jit(lambda: m.init_cache(4, 32),
                                out_shardings=m.cache_shardings(4, 32))()
                dec = jax.jit(functools.partial(
                    m.decode_fn, kv_shard_axes=("model",), kv_batch_axes=("data",)))
                logits, _ = dec(params, cache, tokens[:, 0], jnp.zeros((4,), jnp.int32))
                assert bool(jnp.isfinite(logits).all()), arch
        print("PASS")
        """))


@pytest.mark.slow
def test_interleaved_decode_matches_replicated():
    """The LSE-merge distributed flash-decode must equal the single-chip
    softmax over the full cache (numerical equivalence of Beluga O9)."""
    _run(HEADER + textwrap.dedent("""
        cfg = reduced_config("command-r-35b")
        params = Model(cfg, RuntimeConfig(remat="none")).init(jax.random.key(1))
        outs = {}
        for mode in ["replicated", "pool_interleaved"]:
            rt = RuntimeConfig(remat="none", decode_kv=mode)
            m = Model(cfg, rt, rules)
            with mesh:
                kv_axes = ("batch", "kv_seq") if mode == "pool_interleaved" else ("batch", None)
                sh = m.cache_shardings(4, 32, kv_axes)
                cache = jax.jit(lambda: m.init_cache(4, 32), out_shardings=sh)()
                # prefill a few tokens through decode steps
                dec = jax.jit(functools.partial(
                    m.decode_fn, kv_shard_axes=("model",), kv_batch_axes=("data",)))
                logits = None
                for t in range(6):
                    logits, cache = dec(params, cache,
                                        jnp.full((4,), t % 7, jnp.int32),
                                        jnp.full((4,), t, jnp.int32))
                outs[mode] = logits
        import numpy as np
        a = np.asarray(outs["replicated"], np.float32)
        b = np.asarray(outs["pool_interleaved"], np.float32)
        err = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
        assert err < 2e-2, err
        print("PASS", err)
        """))


@pytest.mark.slow
def test_a2a_moe_matches_einsum_dispatch():
    _run(HEADER + textwrap.dedent("""
        cfg = reduced_config("llama4-maverick-400b-a17b")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        tokens = jax.random.randint(jax.random.key(3), (4, 32), 0, cfg.vocab_size)
        outs = {}
        for mode in ["einsum", "a2a"]:
            rt = RuntimeConfig(remat="none", attn_chunk_q=16, attn_chunk_kv=16,
                               moe_dispatch=mode)
            m = Model(cfg, rt, rules)
            params = jax.jit(m.init, out_shardings=m.param_shardings())(jax.random.key(0))
            with mesh:
                loss, _ = jax.jit(m.loss_fn)(params, {"tokens": tokens, "labels": tokens})
            outs[mode] = float(loss)
        diff = abs(outs["einsum"] - outs["a2a"])
        assert diff < 5e-3, outs
        print("PASS", outs)
        """))


def test_production_mesh_shapes():
    from repro.launch.mesh import make_production_mesh  # noqa: F401

    # shape math only (cannot build 512 fake devices in-session)
    import inspect

    src = inspect.getsource(make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src.replace("'", '"')
