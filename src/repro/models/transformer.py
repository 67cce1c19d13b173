"""Decoder stack: homogeneous scan over layer *periods*.

A "period" is the smallest repeating pattern of layers:
  dense/moe/audio/vlm : period 1  (n_periods = n_layers)
  mamba2              : period 1  (ssm mixer, no MLP)
  jamba               : period 8  (pos 7 = attention, others mamba;
                        odd positions = MoE FFN, even = dense FFN)

Params for each position-in-period are stacked with a leading (n_periods,)
dim and consumed as scan xs — one compiled layer body regardless of depth
(the roofline analyzer multiplies while-loop bodies by their trip count).

Leading dense layers (DeepSeek-V3's ``first_k_dense``) come before the
periods as a scan of their own: params and cache under ``"lead"``, laid out
as one period of kind (mixer, "mlp").
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.distributed.sharding import AxisRules, ParamSpec, constrain, is_param_spec
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models.layers import mlp_apply, mlp_params, norm_apply, norm_params


@dataclass(frozen=True)
class LayerKind:
    mixer: str  # "attn" | "mla" | "ssm"
    ffn: str  # "mlp" | "moe" | "none"


def period_length(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        p = cfg.attn_period
        if cfg.moe.enabled:
            import math

            p = p * cfg.moe.layer_period // math.gcd(p, cfg.moe.layer_period)
        return p
    return 1


def layer_kinds(cfg: ModelConfig) -> list[LayerKind]:
    """Kind of each position within one period."""
    p = period_length(cfg)
    attn_ids = set(cfg.attn_layer_ids())
    moe_ids = set(cfg.moe_layer_ids())
    kinds = []
    for pos in range(p):
        mixer = "attn" if pos in attn_ids or (p == 1 and cfg.family != "ssm") else "ssm"
        if p == 1:
            mixer = "ssm" if cfg.family == "ssm" else "attn"
        if cfg.family == "ssm":
            ffn = "none"
        elif cfg.moe.enabled and (p == 1 or pos in moe_ids):
            ffn = "moe" if (p > 1 and pos in moe_ids) or (p == 1) else "mlp"
        else:
            ffn = "mlp"
        if mixer == "attn" and cfg.is_mla:
            mixer = "mla"
        kinds.append(LayerKind(mixer=mixer, ffn=ffn))
    return kinds


def lead_kinds(cfg: ModelConfig) -> list[LayerKind]:
    """The one position of the leading dense layers' scan (none if none)."""
    if not cfg.first_k_dense:
        return []
    return [LayerKind(mixer=layer_kinds(cfg)[0].mixer, ffn="mlp")]


def n_periods(cfg: ModelConfig) -> int:
    p = period_length(cfg)
    n = cfg.n_layers - cfg.first_k_dense
    assert n % p == 0, (n, p)
    return n // p


def _segments(cfg: ModelConfig, params: dict, cache: dict | None):
    """Each scanned run of layers in order: (name, kinds, its params, its
    cache). The leading dense layers' params and cache sit under "lead";
    the periods' params under "stack", their cache at the top level."""
    rest = cache
    out = []
    if cfg.first_k_dense:
        rest = None if cache is None else {k: v for k, v in cache.items() if k != "lead"}
        out.append(("lead", lead_kinds(cfg), params["lead"],
                    None if cache is None else cache["lead"]))
    out.append(("stack", layer_kinds(cfg), params["stack"], rest))
    return out


def _join(caches: dict) -> dict:
    """Per-segment caches -> the cache tree (see ``_segments``)."""
    out = dict(caches["stack"])
    if "lead" in caches:
        out["lead"] = caches["lead"]
    return out


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def _stack_spec(spec: ParamSpec, n: int) -> ParamSpec:
    return ParamSpec(
        (n, *spec.shape), spec.dtype, ("layers", *spec.logical_axes),
        init=spec.init, scale=spec.scale,
    )


def _position_params(cfg: ModelConfig, kind: LayerKind, tp: int) -> dict:
    p: dict = {"ln1": norm_params(cfg)}
    if kind.mixer == "attn":
        p["attn"] = attn_lib.attn_params(cfg, tp)
    elif kind.mixer == "mla":
        p["attn"] = mla_lib.mla_params(cfg)
    else:
        p["ssm"] = mamba_lib.mamba_params(cfg, tp)
    if kind.ffn != "none":
        p["ln2"] = norm_params(cfg)
        if kind.ffn == "moe":
            p["moe"] = moe_lib.moe_params(cfg, tp)
        else:
            p["mlp"] = mlp_params(cfg, cfg.d_ff)
    return p


def stack_params(cfg: ModelConfig, tp: int, lead: bool = False) -> dict:
    """The periods' stacked params; ``lead=True``: the leading dense layers'."""
    np_ = cfg.first_k_dense if lead else n_periods(cfg)
    kinds = lead_kinds(cfg) if lead else layer_kinds(cfg)
    out = {}
    for pos, kind in enumerate(kinds):
        sub = _position_params(cfg, kind, tp)
        out[f"pos_{pos}"] = jax.tree.map(
            lambda s: _stack_spec(s, np_), sub, is_leaf=is_param_spec
        )
    return out


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------


def cache_specs(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    tp: int,
    kv_axes: tuple,
    kv_dtype: str | None = None,
) -> dict:
    """ShapeDtypeStruct-compatible ParamSpec tree for the decode cache.

    kv_axes: logical axes for the (batch, seq) dims of the kv cache, e.g.
    ("batch", "kv_seq") for decode_32k or (None, "kv_seq_long") for long_500k.
    """
    out = _cache_specs(cfg, layer_kinds(cfg), n_periods(cfg), batch, max_len, kv_axes, kv_dtype)
    if cfg.first_k_dense:
        out["lead"] = _cache_specs(
            cfg, lead_kinds(cfg), cfg.first_k_dense, batch, max_len, kv_axes, kv_dtype
        )
    return out


def _cache_specs(cfg, kinds, np_, batch, max_len, kv_axes, kv_dtype) -> dict:
    di, nh, conv_dim = (0, 0, 0)
    if cfg.has_ssm_layers:
        di, nh, conv_dim = mamba_lib.ssm_dims(cfg)
    out = {}
    b_ax, s_ax = kv_axes
    for pos, kind in enumerate(kinds):
        if kind.mixer == "mla":
            out[f"pos_{pos}"] = {"latent": ParamSpec(
                (np_, batch, max_len, cfg.latent_width),
                kv_dtype or cfg.dtype,
                ("layers", b_ax, s_ax, None),
                init="zeros",
            )}
        elif kind.mixer == "attn":
            kv = ParamSpec(
                (np_, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                kv_dtype or cfg.dtype,
                ("layers", b_ax, s_ax, None, None),
                init="zeros",
            )
            out[f"pos_{pos}"] = {"k": kv, "v": kv}
        else:
            out[f"pos_{pos}"] = {
                "state": ParamSpec(
                    (np_, batch, nh, cfg.ssm.d_state, cfg.ssm.head_dim),
                    "float32",
                    ("layers", b_ax, "ssm_inner", None, None),
                    init="zeros",
                ),
                "conv": ParamSpec(
                    (np_, batch, cfg.ssm.d_conv - 1, conv_dim),
                    cfg.dtype,
                    ("layers", b_ax, None, None),
                    init="zeros",
                ),
            }
    return out


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _mixer_full(pos_params, kind, x, positions, cfg, runtime, rules,
                collect_cache: bool, max_len: int | None):
    """Full-sequence mixer (train / prefill). Returns (out, cache_entry)."""
    h = norm_apply(pos_params["ln1"], x, cfg)
    if kind.mixer == "mla":
        return mla_lib.mla_full(
            pos_params["attn"], h, positions, cfg, runtime, collect_cache, max_len
        )
    if kind.mixer == "attn":
        q, k, v = attn_lib.qkv_proj(pos_params["attn"], h, cfg, positions, rules)
        o = attn_lib.flash_attention(
            q, k, v, causal=True,
            chunk_q=runtime.attn_chunk_q, chunk_kv=runtime.attn_chunk_kv,
        )
        out = attn_lib.out_proj(pos_params["attn"], o, rules)
        cache = None
        if collect_cache:
            b, s = x.shape[0], x.shape[1]
            pad = max_len - s
            kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            if runtime.use_fp8_kv:
                kc = kc.astype(jnp.float8_e4m3fn)
                vc = vc.astype(jnp.float8_e4m3fn)
            cache = {"k": kc, "v": vc}
        return out, cache
    else:
        if collect_cache:
            out, state, conv = mamba_lib.mamba_apply(
                pos_params["ssm"], h, cfg, rules, return_state=True
            )
            return out, {"state": state, "conv": conv}
        out = mamba_lib.mamba_apply(pos_params["ssm"], h, cfg, rules)
        return out, None


def _ffn(pos_params, kind, x, cfg, runtime, rules):
    if kind.ffn == "none":
        return x, 0.0
    h = norm_apply(pos_params["ln2"], x, cfg)
    if kind.ffn == "moe":
        out, aux = moe_lib.moe_apply(pos_params["moe"], h, cfg, runtime, rules)
        return x + out, aux["load_balance_loss"]
    return x + mlp_apply(pos_params["mlp"], h, cfg, rules), 0.0


def forward_full(
    params: dict,
    x: jax.Array,  # (b, s, d) embedded inputs
    positions: jax.Array,  # (b, s)
    cfg: ModelConfig,
    runtime: RuntimeConfig,
    rules: AxisRules | None,
    collect_cache: bool = False,
    max_len: int | None = None,
):
    """Run the full stack; returns (hidden, aux_loss, cache|None)."""

    def period_fn(kinds, carry, xs_params):
        h, aux = carry
        caches = {}
        for pos, kind in enumerate(kinds):
            pp = xs_params[f"pos_{pos}"]
            mix_out, cache = _mixer_full(
                pp, kind, h, positions, cfg, runtime, rules,
                collect_cache, max_len,
            )
            h = h + mix_out
            h, lb = _ffn(pp, kind, h, cfg, runtime, rules)
            if rules is not None:
                h = constrain(h, rules, ("batch", "seq", "act_embed"))
            if collect_cache:
                caches[f"pos_{pos}"] = cache
            aux = aux + lb
        return (h, aux), caches if collect_cache else None

    carry, caches = (x, 0.0), {}
    for name, kinds, seg_params, _ in _segments(cfg, params, None):
        body = functools.partial(period_fn, kinds)
        if runtime.remat != "none":
            policy = {
                "full": jax.checkpoint_policies.nothing_saveable,
                "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            }[runtime.remat]
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        carry, caches[name] = jax.lax.scan(body, carry, seg_params)
    h, aux = carry
    return h, aux, _join(caches) if collect_cache else None


def decode_step_stack(
    params: dict,
    cache: dict,
    x: jax.Array,  # (b, 1, d)
    pos: jax.Array,  # (b,) current positions (write index)
    cfg: ModelConfig,
    runtime: RuntimeConfig,
    rules: AxisRules | None,
    mesh=None,
    kv_shard_axes: tuple[str, ...] = (),
    kv_batch_axes: tuple[str, ...] = (),
):
    """One decode token through the stack; returns (hidden, new_cache)."""
    cache_len = pos + 1

    def period_fn(kinds, carry, xs):
        h = carry
        pp, pc = xs
        new_caches = {}
        for p_i, kind in enumerate(kinds):
            layer_p = pp[f"pos_{p_i}"]
            layer_c = pc[f"pos_{p_i}"]
            hn = norm_apply(layer_p["ln1"], h, cfg)
            if kind.mixer == "mla":
                mix_out, new_caches[f"pos_{p_i}"] = mla_lib.mla_decode(
                    layer_p["attn"], hn, layer_c, pos, cfg
                )
            elif kind.mixer == "attn":
                q, k_new, v_new = attn_lib.qkv_proj(
                    layer_p["attn"], hn, cfg, pos[:, None], rules
                )
                kc, vc = attn_lib.update_kv_cache(
                    layer_c["k"], layer_c["v"], k_new, v_new, pos
                )
                if runtime.decode_kv == "pool_interleaved" and mesh is not None:
                    o = attn_lib.decode_attention_interleaved(
                        q, kc, vc, cache_len, mesh,
                        axes=kv_shard_axes, batch_axes=kv_batch_axes,
                    )
                else:
                    o = attn_lib.decode_attention_replicated(q, kc, vc, cache_len)
                mix_out = attn_lib.out_proj(layer_p["attn"], o, rules)
                new_caches[f"pos_{p_i}"] = {"k": kc, "v": vc}
            else:
                mix_out, state, conv = mamba_lib.mamba_decode(
                    layer_p["ssm"], hn, layer_c["state"], layer_c["conv"],
                    cfg, rules,
                )
                new_caches[f"pos_{p_i}"] = {"state": state, "conv": conv}
            h = h + mix_out
            h, _ = _ffn(layer_p, kind, h, cfg, runtime, rules)
            if rules is not None:
                h = constrain(h, rules, ("batch", "seq", "act_embed"))
        return h, new_caches

    h, new = x, {}
    for name, kinds, seg_params, seg_cache in _segments(cfg, params, cache):
        body = functools.partial(period_fn, kinds)
        h, new[name] = jax.lax.scan(body, h, (seg_params, seg_cache))
    return h, _join(new)


def extend_stack(
    params: dict,
    cache: dict,
    x: jax.Array,  # (b, c, d) embedded chunk
    start: jax.Array,  # scalar: position of x[:, 0]
    n_valid: jax.Array,  # scalar: rows of x that are real tokens
    cfg: ModelConfig,
    runtime: RuntimeConfig,
    rules: AxisRules | None,
):
    """A chunk of tokens at positions ``start … start+c-1`` through an
    attention stack whose cache already holds the positions before
    ``start``: each layer writes the chunk's K/V (or latent rows) into its
    cache at ``start``, then attends causally over the cache, masked beyond
    ``start + n_valid``. Returns (hidden, new_cache)."""
    assert all(k.mixer in ("attn", "mla") for k in layer_kinds(cfg)), (
        "extend needs an attention stack"
    )
    b, c = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(start + jnp.arange(c, dtype=jnp.int32), (b, c))

    def period_fn(kinds, carry, xs):
        h = carry
        pp, pc = xs
        new_caches = {}
        for p_i, kind in enumerate(kinds):
            layer_p = pp[f"pos_{p_i}"]
            layer_c = pc[f"pos_{p_i}"]
            hn = norm_apply(layer_p["ln1"], h, cfg)
            if kind.mixer == "mla":
                mix_out, new_caches[f"pos_{p_i}"] = mla_lib.mla_extend(
                    layer_p["attn"], hn, layer_c, start, n_valid, positions, cfg
                )
            else:
                q, k_new, v_new = attn_lib.qkv_proj(
                    layer_p["attn"], hn, cfg, positions, rules
                )
                kc = jax.lax.dynamic_update_slice_in_dim(
                    layer_c["k"], k_new.astype(layer_c["k"].dtype), start, axis=1
                )
                vc = jax.lax.dynamic_update_slice_in_dim(
                    layer_c["v"], v_new.astype(layer_c["v"].dtype), start, axis=1
                )
                o = attn_lib.flash_attention(
                    q, attn_lib._dequant(kc), attn_lib._dequant(vc), causal=True,
                    q_offset=start, kv_len=start + n_valid,
                    chunk_q=runtime.attn_chunk_q, chunk_kv=runtime.attn_chunk_kv,
                )
                mix_out = attn_lib.out_proj(layer_p["attn"], o, rules)
                new_caches[f"pos_{p_i}"] = {"k": kc, "v": vc}
            h = h + mix_out
            h, _ = _ffn(layer_p, kind, h, cfg, runtime, rules)
            if rules is not None:
                h = constrain(h, rules, ("batch", "seq", "act_embed"))
        return h, new_caches

    h, new = x, {}
    for name, kinds, seg_params, seg_cache in _segments(cfg, params, cache):
        body = functools.partial(period_fn, kinds)
        h, new[name] = jax.lax.scan(body, h, (seg_params, seg_cache))
    return h, _join(new)
