"""Public model API: init / loss / prefill / decode / extend / input_specs.

``Model`` binds a ModelConfig + RuntimeConfig + (optional) mesh AxisRules and
exposes pure functions suitable for jit/lower: ``loss_fn``, ``prefill_fn``,
``decode_fn``, ``extend_fn``. Inputs are produced by ``input_specs``
(ShapeDtypeStructs — the same objects the multi-pod dry-run lowers against).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RuntimeConfig, ShapeConfig
from repro.distributed import sharding as shlib
from repro.distributed.sharding import AxisRules
from repro.models import transformer as stack_lib
from repro.models.layers import embed_apply, norm_apply, norm_params, unembed_apply
from repro.models.layers import embed_params


def _act_precision(fn):
    """Runs a model function at the matrix-product precision its
    activations' dtype needs: float32 activations (``ModelConfig.act_dtype``)
    at ``Precision.HIGH``, three bf16 passes, where a TPU's default would
    round them to bf16."""

    @functools.wraps(fn)
    def wrapped(self, *args, **kwargs):
        if self.cfg.act_dtype != "float32":
            return fn(self, *args, **kwargs)
        with jax.default_matmul_precision("high"):
            return fn(self, *args, **kwargs)

    return wrapped


@dataclass
class Model:
    cfg: ModelConfig
    runtime: RuntimeConfig = RuntimeConfig()
    rules: AxisRules | None = None  # None => single-device (tests/examples)

    # ------------------------------------------------------------------
    @property
    def tp(self) -> int:
        return self.rules.tp if self.rules is not None else 1

    @property
    def mesh(self):
        return self.rules.mesh if self.rules is not None else None

    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        cfg = self.cfg
        p = {
            "embed": embed_params(cfg, self.tp),
            "stack": stack_lib.stack_params(cfg, self.tp),
            "final_ln": norm_params(cfg),
        }
        if cfg.first_k_dense:
            p["lead"] = stack_lib.stack_params(cfg, self.tp, lead=True)
        return p

    def init(self, key: jax.Array) -> dict:
        return shlib.init_tree(self.param_specs(), key)

    def param_shardings(self):
        assert self.rules is not None
        return shlib.tree_shardings(self.param_specs(), self.rules)

    def param_shape_dtypes(self):
        return shlib.tree_shape_dtype(self.param_specs())

    # ------------------------------------------------------------------
    # Embedding of batch inputs (handles stub frontends)
    # ------------------------------------------------------------------
    def embed(self, params: dict, batch: dict) -> tuple[jax.Array, jax.Array]:
        """Returns (x, positions)."""
        cfg = self.cfg
        if cfg.frontend == "audio_stub":
            x = batch["frame_embeds"].astype(cfg.dtype)
        elif cfg.frontend == "vision_stub":
            tok_x = self._embed_tokens(params, batch["tokens"])
            patch = batch["patch_embeds"].astype(cfg.dtype)
            x = jnp.concatenate([patch, tok_x], axis=1)
        else:
            x = self._embed_tokens(params, batch["tokens"])
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        return x, positions

    def _embed_tokens(self, params: dict, tokens: jax.Array) -> jax.Array:
        x = embed_apply(params["embed"], tokens, self.rules)
        return x.astype(self.cfg.act_dtype) if self.cfg.act_dtype else x

    # ------------------------------------------------------------------
    # Training loss
    # ------------------------------------------------------------------
    @_act_precision
    def loss_fn(self, params: dict, batch: dict) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x, positions = self.embed(params, batch)
        h, aux_lb, _ = stack_lib.forward_full(
            params, x, positions, cfg, self.runtime, self.rules
        )
        h = norm_apply(params["final_ln"], h, cfg)
        logits = unembed_apply(params["embed"], h, self.rules)  # (b, s, V) f32

        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if cfg.frontend == "vision_stub":
            # only the text segment (after the patch prefix) predicts tokens
            npatch = cfg.n_frontend_tokens
            logits = logits[:, npatch:]
        # next-token shift
        logits = logits[:, :-1]
        targets = labels[:, 1:]
        if mask is not None:
            mask = mask[:, 1:]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, targets[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        nll = lse - picked
        if mask is not None:
            denom = jnp.maximum(mask.sum(), 1.0)
            loss = jnp.sum(nll * mask) / denom
        else:
            loss = jnp.mean(nll)
        aux = {"lm_loss": loss, "load_balance_loss": aux_lb}
        if self.cfg.moe.enabled:
            loss = loss + 0.01 * aux_lb
        return loss, aux

    # ------------------------------------------------------------------
    # Prefill: returns last-position logits + populated cache
    # ------------------------------------------------------------------
    @_act_precision
    def prefill_fn(
        self, params: dict, batch: dict, max_len: int | None = None
    ) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x, positions = self.embed(params, batch)
        max_len = max_len if max_len is not None else x.shape[1]
        h, _, cache = stack_lib.forward_full(
            params, x, positions, cfg, self.runtime, self.rules,
            collect_cache=True, max_len=max_len,
        )
        h = norm_apply(params["final_ln"], h, cfg)
        logits = unembed_apply(params["embed"], h[:, -1:, :], self.rules)
        return logits, cache

    # ------------------------------------------------------------------
    # Decode: one token for every sequence in the batch
    # ------------------------------------------------------------------
    @_act_precision
    def decode_fn(
        self,
        params: dict,
        cache: dict,
        tokens: jax.Array,  # (b,) int32 previous tokens
        pos: jax.Array,  # (b,) int32 write positions (= context length so far)
        kv_shard_axes: tuple[str, ...] = ("model",),
        kv_batch_axes: tuple[str, ...] = ("data",),
    ) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        if cfg.frontend == "audio_stub":
            x = embed_apply(params["embed"], tokens[:, None], self.rules)
        else:
            x = self._embed_tokens(params, tokens[:, None])
        h, new_cache = stack_lib.decode_step_stack(
            params, cache, x, pos, cfg, self.runtime, self.rules,
            mesh=self.mesh,
            kv_shard_axes=kv_shard_axes,
            kv_batch_axes=kv_batch_axes,
        )
        h = norm_apply(params["final_ln"], h, cfg)
        logits = unembed_apply(params["embed"], h, self.rules)  # (b, 1, V)
        return logits[:, 0], new_cache

    # ------------------------------------------------------------------
    # Extend: prefill a chunk of tokens against a filled cache
    # ------------------------------------------------------------------
    @_act_precision
    def extend_fn(
        self,
        params: dict,
        cache: dict,
        tokens: jax.Array,  # (b, c) int32 chunk; rows from n_valid on are padding
        start: jax.Array,  # scalar int32: position of tokens[:, 0]
        n_valid: jax.Array,  # scalar int32: real tokens in the chunk
    ) -> tuple[jax.Array, dict]:
        """Prefill of the chunk at positions ``start … start+c-1`` against a
        ``max_len`` cache that holds the positions before ``start``, as the
        uncached tail of a prefix hit. The padding rows' KV is written but
        no real token attends to it. Returns the (b, V) logits of row
        ``n_valid - 1`` and the updated cache."""
        cfg = self.cfg
        x = self._embed_tokens(params, tokens)
        h, new_cache = stack_lib.extend_stack(
            params, cache, x, start, n_valid, cfg, self.runtime, self.rules
        )
        h = jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
        h = norm_apply(params["final_ln"], h, cfg)
        logits = unembed_apply(params["embed"], h, self.rules)  # (b, 1, V)
        return logits[:, 0], new_cache

    # ------------------------------------------------------------------
    # Cache construction
    # ------------------------------------------------------------------
    def cache_specs(self, batch: int, max_len: int, kv_axes=("batch", "kv_seq")):
        kv_dtype = "float8_e4m3fn" if self.runtime.use_fp8_kv else None
        return stack_lib.cache_specs(
            self.cfg, batch, max_len, self.tp, kv_axes, kv_dtype
        )

    def init_cache(self, batch: int, max_len: int):
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.cache_specs(batch, max_len),
            is_leaf=shlib.is_param_spec,
        )

    def cache_shardings(self, batch: int, max_len: int, kv_axes=("batch", "kv_seq")):
        assert self.rules is not None
        return shlib.tree_shardings(
            self.cache_specs(batch, max_len, kv_axes), self.rules
        )

    # ------------------------------------------------------------------
    # Dry-run input specs (ShapeDtypeStruct stand-ins; no allocation)
    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> dict[str, Any]:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32

        def sds(shp, dt):
            return jax.ShapeDtypeStruct(shp, dt)

        if shape.kind in ("train", "prefill"):
            if cfg.frontend == "audio_stub":
                batch = {
                    "frame_embeds": sds((b, s, cfg.d_model), jnp.bfloat16),
                    "labels": sds((b, s), i32),
                }
            elif cfg.frontend == "vision_stub":
                npatch = cfg.n_frontend_tokens
                batch = {
                    "tokens": sds((b, s - npatch), i32),
                    "patch_embeds": sds((b, npatch, cfg.d_model), jnp.bfloat16),
                    "labels": sds((b, s - npatch), i32),
                }
            else:
                batch = {
                    "tokens": sds((b, s), i32),
                    "labels": sds((b, s), i32),
                }
            if shape.kind == "prefill":
                batch.pop("labels")
            return batch
        else:  # decode
            return {
                "tokens": sds((b,), i32),
                "pos": sds((b,), i32),
            }

    def input_shardings(self, shape: ShapeConfig) -> dict[str, Any]:
        assert self.rules is not None
        r = self.rules
        specs = self.input_specs(shape)
        out = {}
        for k, v in specs.items():
            if v.ndim >= 2:
                out[k] = r.sharding(("batch",) + (None,) * (v.ndim - 1))
            elif shape.global_batch >= r.dp or shape.kind != "decode":
                out[k] = r.sharding(("batch",))
            else:
                out[k] = r.sharding((None,))
            if shape.kind == "decode" and shape.global_batch < r.dp:
                # tiny decode batch (long_500k b=1): replicate batch dims
                out[k] = r.sharding((None,) * v.ndim)
        return out
