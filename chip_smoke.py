"""Smoke run of the served path on a TPU: proof that the system starts there.

    python chip_smoke.py             # one chip: RealEngine, qwen3-32b widths
    python chip_smoke.py --chips 4   # four chips: TP-sharded decode only

Model: qwen3-32b at its published widths (d_model 5120, 64 query heads,
8 KV heads x 128, d_ff 25600, vocab 151936, bf16) with the depth cut from
64 to 4 layers, the only cut. Weights are random, made from ``SEED``.

One chip: ``RealEngine`` (the engine ``repro.launch.serve`` drives) serves
512-token prompts sharing a 256-token prefix, plus two exact repeats of the
first, with the compiled Pallas transfer kernels; then a fresh engine
serves them again with the jnp copy oracle. Checks: prefix-hit sizes, all
logits finite, identical tokens from both engines, and ``tpu_custom_call``
in both kernels' lowered text.

``--chips 4``: prefill and a few decode steps with the weights sharded over
a (1, 4) ("data", "model") mesh and the KV sequence interleaved over
``model`` (``decode_kv="pool_interleaved"``), against the same weights run
unsharded on one chip, within a bf16 tolerance.

Exits nonzero, without the final ``ok`` line, when JAX finds no TPU or any
check fails. Timings printed are smoke timings, not benchmark numbers.
``--rehearse`` runs the same phases on the CPU at reduced widths with the
kernels in interpret mode; it never prints the ``ok`` line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
ARCH = "qwen3-32b"
LAYERS = 4  # of the published 64
SEED = 0
# max |logit error| / max |logit|. bf16 rounding of the TP partial sums
# compounds over the layers: about 2% at 4 layers of published widths; a
# wrong KV shard offset gives about 40%.
BF16_TOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int | None  # None: the reduced CPU config
    kernel_mode: str
    prompt_len: int
    shared_len: int
    max_len: int
    pool_blocks: int
    n_prompts: int = 6
    repeats: int = 2
    gen: int = 16
    decode_steps: int = 4  # four-chip phase


CHIP = Sizes(LAYERS, "pallas", prompt_len=512, shared_len=256, max_len=1024,
             pool_blocks=512)
REHEARSAL = Sizes(None, "interpret", prompt_len=64, shared_len=32, max_len=128,
                  pool_blocks=64, gen=4)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), and the persistent cache's hits and misses."""

    def __init__(self, jax):
        self.secs = 0.0
        self.counts = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration_secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += duration_secs

    def _on_event(self, event, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in self.counts:
            self.counts[name] += 1


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")
    print(f"check ok: {what}", flush=True)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


# ---------------------------------------------------------------------------
# one chip: the served path
# ---------------------------------------------------------------------------


def serve_once(jax, clock: CompileClock, sizes: Sizes, kernel_mode: str):
    from repro.launch.serve import make_prompts
    from repro.serving.real_runner import RealEngine

    t0 = time.time()
    eng = RealEngine.create(
        ARCH, max_len=sizes.max_len, pool_blocks=sizes.pool_blocks, seed=SEED,
        kernel_mode=kernel_mode, layers=sizes.layers,
    )
    jax.block_until_ready(eng.params)
    cfg = eng.cfg
    print(f"[{kernel_mode}] engine: {cfg.name} {cfg.n_layers}L d={cfg.d_model} "
          f"H={cfg.n_heads}/{cfg.n_kv_heads}kv x {cfg.head_dim} ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} {cfg.dtype}; pool {sizes.pool_blocks} blocks x "
          f"{eng.pool.layout.block_bytes} B; built in {time.time() - t0:.2f} s "
          "(smoke timing)", flush=True)
    prompts = make_prompts(cfg.vocab_size, sizes.n_prompts, sizes.prompt_len,
                           sizes.shared_len, sizes.repeats, SEED)
    results = []
    for i, p in enumerate(prompts):
        c0 = clock.secs
        out, info = eng.generate(p, max_new=sizes.gen)
        print(f"[{kernel_mode}] req {i}: hit {info['hit_tokens']}/{len(p)}; smoke "
              f"timings (not benchmark numbers): ttft {info['ttft_s'] * 1e3:.1f} ms, "
              f"total {info['total_s'] * 1e3:.1f} ms, compile "
              f"{clock.secs - c0:.2f} s; tokens {out[:8]}...", flush=True)
        results.append((out, info))
    print(f"[{kernel_mode}] peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")
    return results


def one_chip(jax, clock: CompileClock, sizes: Sizes, on_tpu: bool) -> None:
    s = sizes
    want_hits = [0] + [s.shared_len] * (s.n_prompts - 1) + [s.prompt_len] * s.repeats

    results = serve_once(jax, clock, s, s.kernel_mode)
    hits = [info["hit_tokens"] for _, info in results]
    check(hits == want_hits, f"hit_tokens {hits} == {want_hits}")
    check(all(info["logits_finite"] for _, info in results),
          "every logit of every generated token is finite")
    gc.collect()  # drop the first engine's weights before the second is made

    oracle = serve_once(jax, clock, s, "jnp")
    check([info["hit_tokens"] for _, info in oracle] == want_hits,
          "oracle engine hit_tokens match")
    check([out for out, _ in results] == [out for out, _ in oracle],
          f"{s.kernel_mode} kernels and the jnp copy give identical tokens")

    if on_tpu:
        check_lowered_kernels(jax)


def check_lowered_kernels(jax) -> None:
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.kernels import ops

    cfg = get_config(ARCH)
    bt, n_slots, nb = 16, 64, 32
    kc = jax.ShapeDtypeStruct((LAYERS, n_slots * bt, cfg.n_kv_heads, cfg.head_dim),
                              jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((nb,), jnp.int32)
    blocks = jax.ShapeDtypeStruct((nb, 2 * LAYERS, bt, cfg.n_kv_heads, cfg.head_dim),
                                  jnp.bfloat16)
    write = ops.kv_gather_write.lower(kc, kc, ids, bt, mode="pallas").as_text()
    read = ops.kv_scatter_read.lower(blocks, ids, n_slots, mode="pallas").as_text()
    check("tpu_custom_call" in write, "kv_gather_write lowers to tpu_custom_call")
    check("tpu_custom_call" in read, "kv_scatter_read lowers to tpu_custom_call")


# ---------------------------------------------------------------------------
# four chips: tensor-parallel weights, KV sequence interleaved over `model`
# ---------------------------------------------------------------------------


def four_chip(jax, sizes: Sizes) -> None:
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.base import RuntimeConfig
    from repro.configs.registry import get_config, reduced_config
    from repro.distributed.sharding import AxisRules
    from repro.models import Model

    s = sizes
    if s.layers is None:
        cfg = reduced_config(ARCH)
    else:
        cfg = dataclasses.replace(get_config(ARCH), n_layers=s.layers)
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = AxisRules.create(mesh)
    tp = Model(cfg, RuntimeConfig(remat="none", decode_kv="pool_interleaved"), rules)
    rng = np.random.default_rng(SEED)
    prompt = jax.numpy.asarray(rng.integers(0, cfg.vocab_size, (1, s.prompt_len)),
                               jax.numpy.int32)
    feed = rng.integers(0, cfg.vocab_size, s.decode_steps).tolist()

    def run(model, params, cache_sh, out_sh, decode_kw):
        prefill = jax.jit(functools.partial(model.prefill_fn, max_len=s.max_len),
                          out_shardings=(out_sh, cache_sh))
        decode = jax.jit(functools.partial(model.decode_fn, **decode_kw),
                         out_shardings=(out_sh, cache_sh), donate_argnums=1)
        logits, cache = prefill(params, {"tokens": prompt})
        outs = [np.asarray(logits[:, -1], np.float32)]
        for i, tok in enumerate(feed):
            pos = jax.numpy.full((1,), s.prompt_len + i, jax.numpy.int32)
            logits, cache = decode(params, cache, jax.numpy.asarray([tok]), pos)
            outs.append(np.asarray(logits, np.float32))
        return outs, cache

    t0 = time.time()
    params = jax.jit(tp.init, out_shardings=tp.param_shardings())(jax.random.key(SEED))
    cache_sh = tp.cache_shardings(1, s.max_len, ("batch", "kv_seq"))
    got, cache = run(tp, params, cache_sh, NamedSharding(mesh, P()),
                     {"kv_shard_axes": ("model",), "kv_batch_axes": ("data",)})
    print(f"[4 chips] TP prefill + {s.decode_steps} decode steps in "
          f"{time.time() - t0:.2f} s (smoke timing, compile included)")
    k_shards = {sh.device.id: sh.data.shape for sh in cache["pos_0"]["k"].addressable_shards}
    print(f"[4 chips] KV cache shard per device: {k_shards}")
    for d in devs:
        stats = d.memory_stats() or {}
        print(f"[4 chips] device {d.id} bytes_in_use: "
              f"{stats.get('bytes_in_use', 'not reported')}")
    del cache
    gc.collect()

    ref = Model(cfg, RuntimeConfig(remat="none", decode_kv="replicated"))
    one = jax.sharding.SingleDeviceSharding(devs[0])
    params_one = jax.device_put(params, one)
    del params
    want, _ = run(ref, params_one, one, one, {})
    for i, (a, b) in enumerate(zip(got, want)):
        err = float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))
        check(np.isfinite(a).all() and err < BF16_TOL,
              f"step {i}: sharded vs one-chip logits, rel err {err:.2e} < {BF16_TOL}")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at reduced widths; never prints the ok line")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"no repro package under {SRC}: run from a checkout", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; devices {devices}")
    print(f"platform {dev.platform}; device_kind {dev.device_kind}; "
          f"count {len(devices)}", flush=True)
    on_tpu = dev.platform == "tpu"
    if on_tpu == args.rehearse:
        print("--rehearse runs on the CPU only" if on_tpu else
              f"no TPU: JAX found {dev.platform!r}; this smoke has no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)
    sizes = REHEARSAL if args.rehearse else CHIP
    if sizes.layers is None:
        print(f"{ARCH}: reduced widths (CPU rehearsal)")
    else:
        print(f"{ARCH}: published widths, depth cut from 64 to {sizes.layers} "
              "layers (the only cut)")
    if args.chips == 4:
        four_chip(jax, sizes)
    else:
        one_chip(jax, clock, sizes, on_tpu)
    print(f"compile: {clock.secs:.2f} s in all; persistent cache {clock.counts}")
    if args.rehearse:
        print("CPU rehearsal passed; this is not a chip run")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
