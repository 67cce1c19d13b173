"""RealEngine: actual token generation through the Beluga KVCache stack.

End-to-end driver, on a TPU at published widths (``layers=`` cuts depth
only) or on the CPU at reduced widths: prompts are served with real
numerics and REAL pool reuse —

  miss: prefill -> per-layer KV packed into pool blocks (kv_gather_write
        kernel) -> blocks published in the GlobalIndex;
  hit : pool blocks fetched (kv_scatter_read kernel) straight into a decode
        cache — prefill for the hit prefix is SKIPPED; the uncached tail is
        prefilled against the fetched cache by ``extend_fn``, in chunks of
        ``TAIL_CHUNK`` tokens (one compiled shape for any tail).

Restricted to stacks of attention layers: dense GQA (olmo, qwen,
command-r, internlm2, ...) with a key/value pair a layer in each pool
block, and latent attention (deepseek-v3-ep32: a leading dense layer, then
MoE layers) with one latent part a layer. Hybrid/ssm archs would need their
recurrent state pooled beside the KV blocks, which the pool does not do
yet; they run only in the simulated cluster.

``generate`` writes a span (``jax.profiler.TraceAnnotation``) at each
layer boundary, named in ``SPANS``: on the profiler's host plane, on the
device trace's clock, when a profiler is collecting, and about a
microsecond each when none is. ``engine.generate`` carries the request id
(``req``); the others nest inside it by time on the calling thread:

  engine.generate
    engine.lookup                  index.match_prefix
    engine.fetch                   hit: pool gather + kv_scatter_read
                                   (``blocks`` fetched, their ``bytes``)
    engine.tail                    hit: extend_fn over the tail, each chunk
                                   (``tokens``: tail length, ``chunks``: calls)
    engine.prefill                 miss: prefill_fn
    engine.writeback               miss: kv_gather_write + pool write, with
      engine.allocate              pool.allocate
      engine.publish               keys_for + write_blocks + publish_many
    engine.first_token             greedy pick, host waits for the token
    engine.decode                  output tokens, each
      engine.step                  inputs, decode_fn and greedy dispatched
      engine.sync                  host waits for the token
"""

from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import RuntimeConfig
from repro.configs.registry import cut_depth, get_config, reduced_config
from repro.core.index import GlobalIndex
from repro.core.pool import BelugaPool, PoolLayout
from repro.kernels import ops
from repro.models import Model

SPANS = (
    "engine.generate", "engine.lookup", "engine.fetch", "engine.tail",
    "engine.prefill", "engine.writeback", "engine.allocate", "engine.publish",
    "engine.first_token", "engine.decode", "engine.step", "engine.sync",
)

# Tokens a hit's tail is prefilled in per ``extend_fn`` call (capped at
# ``max_len``): a multiple of the pool block, one call for tails up to it.
TAIL_CHUNK = 256


@dataclass
class RealEngine:
    cfg: object
    model: Model
    pool: BelugaPool
    index: GlobalIndex
    params: dict
    max_len: int
    kernel_mode: str = "pallas"
    n_requests: int = dataclasses.field(default=0, init=False)  # the next ``req`` span id

    @classmethod
    def create(
        cls,
        arch: str = "olmo-1b",
        max_len: int = 128,
        pool_blocks: int = 256,
        seed: int = 0,
        kernel_mode: str = "pallas",
        layers: int | None = None,
    ) -> "RealEngine":
        """``layers=None``: the reduced CPU config; ``layers=n``: the
        published widths with the depth cut to ``n`` layers (leading dense
        layers count once)."""
        if layers is None:
            cfg = reduced_config(arch)
        else:
            cfg = cut_depth(get_config(arch), layers)
        if cfg.has_ssm_layers or not cfg.n_heads:
            raise ValueError(f"RealEngine needs a stack of attention layers, not {cfg.name}")
        model = Model(cfg, RuntimeConfig(remat="none", decode_kv="replicated"))
        params = jax.jit(model.init)(jax.random.key(seed))
        pool = BelugaPool(PoolLayout.for_model(cfg), n_blocks=pool_blocks, n_shards=8,
                          backing="jax")
        return cls(
            cfg=cfg,
            model=model,
            pool=pool,
            index=GlobalIndex(pool),
            params=params,
            max_len=max_len,
            kernel_mode=kernel_mode,
        )

    # ------------------------------------------------------------------
    @property
    def _parts(self) -> tuple[str, ...]:
        """The cache's parts a layer, in pool-block order."""
        return ("latent",) if self.cfg.is_mla else ("k", "v")

    def _cache_to_layers(self, cache: dict) -> tuple[jax.Array, ...]:
        """Stacked cache -> one array per part, (L, T, *row) over every
        layer in order (the leading dense layers first)."""
        if "lead" not in cache:
            return tuple(cache["pos_0"][p][:, 0] for p in self._parts)
        return tuple(
            jnp.concatenate([cache["lead"]["pos_0"][p][:, 0], cache["pos_0"][p][:, 0]])
            for p in self._parts
        )

    def _layers_to_cache(self, *parts: jax.Array) -> dict:
        k = self.cfg.first_k_dense
        cache = {"pos_0": {p: x[k:, None] if k else x[:, None]
                           for p, x in zip(self._parts, parts)}}
        if k:
            cache["lead"] = {"pos_0": {p: x[:k, None] for p, x in zip(self._parts, parts)}}
        return cache

    # ------------------------------------------------------------------
    def generate(self, prompt: list[int], max_new: int = 16) -> tuple[list[int], dict]:
        req = self.n_requests
        self.n_requests += 1
        with TraceAnnotation("engine.generate", req=req):
            t_start = time.time()
            bt = self.pool.layout.block_tokens
            with TraceAnnotation("engine.lookup"):
                hits = self.index.match_prefix(prompt)
            n_hit = len(hits) * bt
            info = {"hit_tokens": n_hit, "tail_tokens": 0}

            if n_hit:
                # --- pool fetch path: scatter-read hit blocks, skip prefill ---
                with TraceAnnotation("engine.fetch", blocks=len(hits),
                                     bytes=len(hits) * self.pool.layout.block_bytes):
                    cache = self._fetch([b for _, b, _ in hits])
                logits, cache, info["tail_tokens"] = self._prefill_tail(prompt, n_hit, cache)
            else:
                # --- prefill path + pool writeback ---
                with TraceAnnotation("engine.prefill"):
                    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
                    logits, cache = self._prefill(self.params, batch)
                with TraceAnnotation("engine.writeback"):
                    self._writeback(prompt, cache)

            with TraceAnnotation("engine.first_token"):
                tok, ok = _greedy(logits, True)
                out = [int(tok)]  # int() waits for the device
            info["ttft_s"] = time.time() - t_start
            pos = len(prompt)
            with TraceAnnotation("engine.decode"):
                while len(out) < max_new and pos + 1 < self.max_len:
                    with TraceAnnotation("engine.step"):
                        logits, cache = self._decode(
                            self.params, cache, jnp.asarray([out[-1]]), jnp.asarray([pos])
                        )
                        tok, ok = _greedy(logits, ok)
                    with TraceAnnotation("engine.sync"):
                        out.append(int(tok))
                    pos += 1
            info["total_s"] = time.time() - t_start
            info["logits_finite"] = bool(ok)
        return out, info

    def _prefill_tail(self, prompt: list[int], n_hit: int, cache: dict):
        """Prefill ``prompt`` past its ``n_hit`` cached tokens into ``cache``,
        in ``extend_fn`` calls of one chunk shape; if the prompt is fully
        covered, re-feed its last token (same KV, yields logits). A window
        that would run past ``max_len`` starts earlier instead, recomputing
        cached positions with the same KV; the last one is padded past the
        prompt, with KV that output decode overwrites before reading it.
        Returns (last prompt token's logits, cache, tail tokens)."""
        c = min(TAIL_CHUNK, self.max_len)
        first = min(n_hit, len(prompt) - 1)
        n_tail = len(prompt) - first
        with TraceAnnotation("engine.tail", tokens=n_tail, chunks=-(-n_tail // c)):
            for start in range(first, len(prompt), c):
                start = min(start, self.max_len - c)
                window = prompt[start : start + c]
                tokens = np.zeros((1, c), np.int32)
                tokens[0, : len(window)] = window
                logits, cache = self._extend(
                    self.params, cache, tokens, np.int32(start), np.int32(len(window))
                )
        return logits, cache, n_tail

    def _fetch(self, block_ids: list[int]) -> dict:
        """A decode cache of ``max_len`` slots holding the pool blocks
        ``block_ids`` in order."""
        layout = self.pool.layout
        bt = layout.block_tokens
        blocks = pool_gather(self.pool.data, jnp.asarray(block_ids))
        parts = ops.kv_scatter_read(
            blocks, jnp.arange(len(block_ids), dtype=jnp.int32), self.max_len // bt,
            n_parts=layout.n_parts, mode=self.kernel_mode,
        )
        cache = self._layers_to_cache(*(p.astype(jnp.dtype(self.cfg.dtype)) for p in parts))
        # pad cache seq dim up to max_len if needed
        pad = self.max_len - parts[0].shape[1]
        if pad > 0:
            cache = jax.tree.map(
                lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3)),
                cache,
            )
        return cache

    # ------------------------------------------------------------------
    # params are arguments of the compiled programs, never baked-in constants
    @functools.cached_property
    def _prefill(self):
        model, max_len = self.model, self.max_len

        def prefill_fn(params: dict, batch: dict) -> tuple[jax.Array, dict]:
            return model.prefill_fn(params, batch, max_len=max_len)

        return jax.jit(prefill_fn)

    @functools.cached_property
    def _decode(self):
        return jax.jit(self.model.decode_fn)

    @functools.cached_property
    def _extend(self):
        return jax.jit(self.model.extend_fn)

    def _writeback(self, prompt: list[int], cache: dict) -> None:
        bt = self.pool.layout.block_tokens
        n_blocks = len(prompt) // bt
        if not n_blocks:
            return
        parts = self._cache_to_layers(cache)
        blocks = ops.kv_gather_write(
            parts[0], parts[1] if len(parts) > 1 else None,
            jnp.arange(n_blocks, dtype=jnp.int32), bt, mode=self.kernel_mode,
        )
        with TraceAnnotation("engine.allocate"):
            block_ids = self.pool.allocate(n_blocks)
        self.pool.data = pool_write(
            self.pool.data, jnp.asarray(block_ids), blocks.astype(self.pool.data.dtype)
        )
        with TraceAnnotation("engine.publish"):
            keys = self.index.keys_for(prompt)
            # commit AFTER the payload write (§5.1): one batched epoch bump,
            # one batched publish (single lock, one scatter per column)
            epochs = self.pool.write_blocks(block_ids)
            self.index.publish_many(list(keys[: len(block_ids)]), block_ids, epochs, bt)


@jax.jit
def pool_gather(data: jax.Array, ids: jax.Array) -> jax.Array:
    """The device pool's blocks ``ids``, copied out in order."""
    return data[ids]


@jax.jit
def pool_write(data: jax.Array, ids: jax.Array, blocks: jax.Array) -> jax.Array:
    """The device pool with ``blocks`` written at ``ids``: a new array, the
    whole pool copied (``data`` is not donated)."""
    return data.at[ids].set(blocks)


@jax.jit
def _greedy(logits: jax.Array, ok: jax.Array | bool) -> tuple[jax.Array, jax.Array]:
    """(1, V) logits and whether every earlier logit was finite -> (greedy
    next token, whether every logit so far is finite)."""
    return jnp.argmax(logits[0]), ok & jnp.isfinite(logits).all()
