"""Mixture-of-Experts with expert parallelism over the `model` mesh axis.

Baseline dispatch is the dense one-hot einsum path (MaxText / GShard style,
capacity-factor token dropping) — robust under GSPMD for the dry-run.  The
`ragged` dispatch (sort-based, no capacity waste) is the hillclimb variant.

Supports:
  * top-k routing (llama4-maverick top-1, arctic & jamba top-2)
  * Arctic's dense-residual MLP in parallel with the experts
  * DeepSeek-V3: sigmoid scores, group-limited top-k, shared experts, and
    a layer that holds a share of the experts (``_held_share``)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.distributed.sharding import AxisRules, ParamSpec, constrain
from repro.models.layers import act_fn, mlp_apply, mlp_params


def moe_params(cfg: ModelConfig, tp: int) -> dict:
    moe = cfg.moe
    d, f, e = cfg.d_model, moe.expert_ff or cfg.d_ff, moe.n_experts
    dt = cfg.dtype
    p = {
        "router": ParamSpec((d, e), "float32", ("embed", "experts")),
        "wi_gate": ParamSpec((moe.held, d, f), dt, ("experts", "embed", "expert_mlp")),
        "wi_up": ParamSpec((moe.held, d, f), dt, ("experts", "embed", "expert_mlp")),
        "wo": ParamSpec((moe.held, f, d), dt, ("experts", "expert_mlp", "embed")),
    }
    if moe.scoring == "sigmoid":  # selection bias (e_score_correction_bias)
        p["router_bias"] = ParamSpec((e,), "float32", ("experts",), init="zeros")
    if moe.n_shared:
        p["shared"] = mlp_params(cfg, moe.n_shared * f)
    if moe.dense_residual:
        p["dense"] = mlp_params(cfg, moe.dense_residual_ff)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    moe = cfg.moe
    cap = int(moe.capacity_factor * moe.top_k * n_tokens / moe.n_experts)
    return max(4, -(-cap // 4) * 4)


def moe_apply(
    p: dict,
    x: jax.Array,  # (b, s, d)
    cfg: ModelConfig,
    runtime: RuntimeConfig,
    rules: AxisRules | None,
) -> tuple[jax.Array, dict]:
    b, s, d = x.shape
    moe = cfg.moe
    if moe.scoring == "sigmoid":
        return _held_share(p, x, cfg, rules), {"load_balance_loss": 0.0}

    # a2a pays off when there are enough tokens per shard to fill the
    # all-to-all buffers; decode-sized batches fall back to einsum dispatch
    # (measured: a2a decode_32k inflated flops ~6x on arctic/llama4).
    if (
        runtime.moe_dispatch == "a2a"
        and rules is not None
        and s % rules.tp == 0
        and (b // max(rules.dp, 1) if b >= rules.dp else b) * (s // rules.tp) >= 16
    ):
        out, lb = _a2a_dispatch(p, x, cfg, rules)
        if moe.dense_residual:
            out = out + mlp_apply(p["dense"], x, cfg, rules)
        return out, {"load_balance_loss": lb}

    t = b * s
    xt = x.reshape(t, d)

    gates = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (t, e)
    probs = jax.nn.softmax(gates, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, moe.top_k)  # (t, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    if runtime.moe_dispatch == "einsum":
        out = _einsum_dispatch(p, xt, top_w, top_e, cfg, rules)
    else:
        out = _ragged_dispatch(p, xt, top_w, top_e, cfg, rules)
    out = out.reshape(b, s, d).astype(x.dtype)

    if moe.dense_residual:
        out = out + mlp_apply(p["dense"], x, cfg, rules)

    # aux stats for load-balance loss / monitoring
    me = probs.mean(axis=0)  # (e,)
    ce = jnp.zeros_like(me).at[top_e.reshape(-1)].add(
        jnp.ones((t * moe.top_k,), jnp.float32)
    ) / (t * moe.top_k)
    aux = {"load_balance_loss": moe.n_experts * jnp.sum(me * ce)}
    return out, aux


def _einsum_dispatch(p, xt, top_w, top_e, cfg, rules):
    """GShard-style dense dispatch with capacity-factor token dropping."""
    t, d = xt.shape
    e = cfg.moe.n_experts
    cap = _capacity(t, cfg)
    act = act_fn(cfg.act)

    # position of each (token, k) within its expert's capacity
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)  # (t, k, e)
    pos_in_e = (jnp.cumsum(onehot.reshape(t * cfg.moe.top_k, e), axis=0) - 1)
    pos_in_e = pos_in_e.reshape(t, cfg.moe.top_k, e)
    pos = jnp.sum(pos_in_e * onehot, axis=-1)  # (t, k)
    keep = pos < cap
    w = jnp.where(keep, top_w, 0.0)

    # dispatch (t, e, cap) — combine weights and boolean dispatch mask
    disp = jnp.einsum(
        "tke,tkc->tec",
        jax.nn.one_hot(top_e, e, dtype=jnp.float32) * keep[..., None],
        jax.nn.one_hot(pos, cap, dtype=jnp.float32),
    )
    comb = jnp.einsum(
        "tke,tkc->tec",
        jax.nn.one_hot(top_e, e, dtype=jnp.float32) * w[..., None],
        jax.nn.one_hot(pos, cap, dtype=jnp.float32),
    )
    if rules is not None:
        disp = constrain(disp, rules, ("batch", "experts", None))
        comb = constrain(comb, rules, ("batch", "experts", None))

    xin = jnp.einsum("tec,td->ecd", disp.astype(xt.dtype), xt)  # (e, cap, d)
    if rules is not None:
        xin = constrain(xin, rules, ("experts", None, None))
    g = jnp.einsum("ecd,edf->ecf", xin, p["wi_gate"])
    u = jnp.einsum("ecd,edf->ecf", xin, p["wi_up"])
    h = act(g) * u
    eo = jnp.einsum("ecf,efd->ecd", h, p["wo"])  # (e, cap, d)
    if rules is not None:
        eo = constrain(eo, rules, ("experts", None, None))
    out = jnp.einsum("tec,ecd->td", comb.astype(eo.dtype), eo)
    if rules is not None:
        out = constrain(out, rules, ("batch", "act_embed"))
    return out


def _round4(x: int) -> int:
    return max(4, -(-x // 4) * 4)


def _a2a_dispatch(p, x, cfg, rules):
    """Expert parallelism with explicit all-to-all inside shard_map.

    The production path (beyond-paper distributed optimization): tokens stay
    on their data shard; only the routed rows cross the `model` axis in two
    all-to-alls (forward + return). Dispatch is local scatter/gather —
    O(t·k·d) data movement, ZERO dispatch matmul FLOPs — versus the GShard
    one-hot einsum path whose dispatch costs O(t·e·cap·d) and dominated the
    MoE cells' compute term ~10x in the baseline roofline.

    Two capacity stages, both local: per-destination-shard capacity for the
    a2a buffer, then per-local-expert capacity for the batched matmuls.
    """
    moe = cfg.moe
    mesh = rules.mesh
    tp = rules.tp
    e = moe.n_experts
    e_loc = e // tp
    k = moe.top_k
    d = cfg.d_model
    f = cfg.d_ff
    act = act_fn(cfg.act)
    batch_ax = rules.rules.get("batch")
    if isinstance(batch_ax, str):
        batch_ax = (batch_ax,)

    b, s, _ = x.shape
    dp = rules.dp
    # tokens are sequence-sharded over `model` INSIDE the shard_map: without
    # this, all tp model-peers hold identical tokens and each would route +
    # send + compute the same rows — a measured 16x duplication of expert
    # FLOPs in the first a2a iteration (EXPERIMENTS.md §Perf iter 3b).
    t_shard = (b // dp if b >= dp else b) * (s // tp)
    cap_pair = _round4(int(moe.capacity_factor * k * max(t_shard, 1) / tp))
    # per-local-expert matmul capacity: with e_loc == 1 every valid row goes
    # to the single local expert, so NO extra slack is needed (a 1.5x slack
    # here inflated jamba's expert FLOPs 1.5x — measured); with e_loc > 1
    # keep slack for imbalance among local experts.
    rows = tp * cap_pair
    cap_e = rows if e_loc == 1 else _round4(int(1.25 * rows / e_loc))

    def local_fn(x_loc, router_w, wi_g, wi_u, wo):
        bl, sl, _ = x_loc.shape
        tl = bl * sl
        xt = x_loc.reshape(tl, d)
        gates = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(gates, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)  # (tl, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        flat_e = top_e.reshape(-1)  # (tl*k,)
        flat_w = top_w.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(tl), k)
        dest = flat_e // e_loc  # destination model-shard
        leid = flat_e % e_loc  # local expert id at the destination

        # position within the destination shard's send capacity
        onehot_d = jax.nn.one_hot(dest, tp, dtype=jnp.int32)  # (tl*k, tp)
        pos = jnp.sum((jnp.cumsum(onehot_d, axis=0) - 1) * onehot_d, -1)
        keep = pos < cap_pair
        pos = jnp.where(keep, pos, cap_pair - 1)
        w = jnp.where(keep, flat_w, 0.0)

        send_x = jnp.zeros((tp, cap_pair, d), x_loc.dtype)
        send_x = send_x.at[dest, pos].add(
            xt[flat_tok] * keep[:, None].astype(xt.dtype), mode="drop"
        )
        send_eid = jnp.full((tp, cap_pair), e_loc, jnp.int32)  # e_loc = empty
        send_eid = send_eid.at[dest, pos].set(
            jnp.where(keep, leid, e_loc), mode="drop"
        )

        # ---- forward all-to-all over the model axis ----
        recv_x = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=True)
        recv_eid = jax.lax.all_to_all(send_eid, "model", 0, 0, tiled=True)

        rows_x = recv_x.reshape(tp * cap_pair, d)
        rows_e = recv_eid.reshape(tp * cap_pair)
        valid = rows_e < e_loc

        # pack rows by local expert (second local scatter)
        onehot_e = jax.nn.one_hot(
            jnp.where(valid, rows_e, e_loc), e_loc + 1, dtype=jnp.int32
        )[:, :e_loc]
        pos_e = jnp.sum((jnp.cumsum(onehot_e, axis=0) - 1) * onehot_e, -1)
        keep_e = jnp.logical_and(valid, pos_e < cap_e)
        pos_e = jnp.where(keep_e, pos_e, cap_e - 1)
        eidx = jnp.where(valid, rows_e, 0)

        xin = jnp.zeros((e_loc, cap_e, d), rows_x.dtype)
        xin = xin.at[eidx, pos_e].add(
            rows_x * keep_e[:, None].astype(rows_x.dtype), mode="drop"
        )

        g = jnp.einsum("ecd,edf->ecf", xin, wi_g)
        u = jnp.einsum("ecd,edf->ecf", xin, wi_u)
        h = act(g) * u
        eo = jnp.einsum("ecf,efd->ecd", h, wo)  # (e_loc, cap_e, d)

        y_rows = eo[eidx, pos_e] * keep_e[:, None].astype(eo.dtype)
        y_send = y_rows.reshape(tp, cap_pair, d)

        # ---- return all-to-all ----
        y_recv = jax.lax.all_to_all(y_send, "model", 0, 0, tiled=True)

        out = jnp.zeros((tl, d), y_recv.dtype)
        out = out.at[flat_tok].add(
            y_recv[dest, pos] * w[:, None].astype(y_recv.dtype), mode="drop"
        )

        # load-balance stats (replicated via pmean so out_spec can be P())
        me = probs.mean(axis=0)
        ce = (
            jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0, mode="drop")
            / max(tl * k, 1)
        )
        lb = e * jnp.sum(me * ce)
        axes = tuple(batch_ax or ()) + ("model",)
        lb = jax.lax.pmean(lb, axes)
        return out.reshape(bl, sl, d).astype(x_loc.dtype), lb

    in_specs = (
        P(batch_ax, "model", None),  # x: batch over data, SEQ over model
        P(None, None),  # router (replicated)
        P("model", None, None),  # wi_gate
        P("model", None, None),  # wi_up
        P("model", None, None),  # wo
    )
    out_specs = (P(batch_ax, "model", None), P())
    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])


def _ragged_dispatch(p, xt, top_w, top_e, cfg, rules):
    """Scatter-based dispatch (hillclimb variant).

    Replaces the O(t·e·cap) one-hot dispatch/combine einsums with
    scatter-add into the (e, cap, d) expert buffer and gather back out —
    O(t·k·d) data movement. The per-expert matmuls are unchanged.
    """
    t, d = xt.shape
    e = cfg.moe.n_experts
    k = cfg.moe.top_k
    cap = _capacity(t, cfg)
    act = act_fn(cfg.act)

    flat_e = top_e.reshape(-1)  # (t*k,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)

    # position of each (token, k) within its expert's capacity
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (t*k, e)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)  # (t*k,)
    keep = pos < cap
    pos = jnp.where(keep, pos, cap - 1)
    w = jnp.where(keep, flat_w, 0.0)

    xin = jnp.zeros((e, cap, d), xt.dtype)
    src = xt[flat_tok] * keep[:, None].astype(xt.dtype)
    xin = xin.at[flat_e, pos].add(src, mode="drop")
    if rules is not None:
        xin = constrain(xin, rules, ("experts", None, None))

    g = jnp.einsum("ecd,edf->ecf", xin, p["wi_gate"])
    u = jnp.einsum("ecd,edf->ecf", xin, p["wi_up"])
    h = act(g) * u
    eo = jnp.einsum("ecf,efd->ecd", h, p["wo"])  # (e, cap, d)
    if rules is not None:
        eo = constrain(eo, rules, ("experts", None, None))

    picked = eo[flat_e, pos] * w[:, None].astype(eo.dtype)  # (t*k, d)
    out = jnp.zeros((t, d), eo.dtype).at[flat_tok].add(picked)
    if rules is not None:
        out = constrain(out, rules, ("batch", "act_embed"))
    return out


def route_grouped(logits: jax.Array, bias: jax.Array, moe) -> jax.Array:
    """DeepSeek-V3's ``noaux_tc`` routing, float32. logits: (t, E) ->
    (t, E) weights, zero but for each token's top-k experts: sigmoid
    scores; ``bias`` shifts them for selection only; each group is scored
    by the sum of its two best biased scores and the top ``topk_groups``
    groups are kept; top-k experts inside them; weights are the unbiased
    scores of those, normalized to sum 1, times ``routed_scaling``."""
    t, e = logits.shape
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = scores + bias
    groups = choice.reshape(t, moe.n_groups, e // moe.n_groups)
    group_score = jax.lax.top_k(groups, 2)[0].sum(-1)  # (t, n_groups)
    _, keep = jax.lax.top_k(group_score, moe.topk_groups)
    rows = jnp.arange(t)[:, None]
    kept = jnp.zeros((t, moe.n_groups), jnp.bool_).at[rows, keep].set(True)
    choice = jnp.where(jnp.repeat(kept, e // moe.n_groups, axis=1), choice, -jnp.inf)
    _, top = jax.lax.top_k(choice, moe.top_k)
    w = jnp.take_along_axis(scores, top, axis=1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * moe.routed_scaling
    return jnp.zeros((t, e), jnp.float32).at[rows, top].set(w)


def _held_share(p, x, cfg: ModelConfig, rules) -> jax.Array:
    """One chip's share of an expert-parallel layer: route over all
    ``n_experts``, add what the held experts give for the tokens routed to
    them (every held expert runs on every token, at weight 0 where not
    routed), and the shared experts. What absent experts would add is left
    out. x: (b, s, d)."""
    moe = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    act = act_fn(cfg.act)
    with jax.named_scope("moe.route"):
        # float32 logits: at default precision a TPU would round the router's
        # operands to bfloat16 and flip near-tied experts
        logits = jnp.dot(xt.astype(jnp.float32), p["router"], precision=jax.lax.Precision.HIGHEST)
        w = route_grouped(logits, p["router_bias"], moe)
        w = w[:, moe.first_held : moe.first_held + moe.held]  # (t, held)
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("td,edf->tef", xt, p["wi_gate"])
        u = jnp.einsum("td,edf->tef", xt, p["wi_up"])
        h = act(g) * u * w[..., None].astype(xt.dtype)
        out = jnp.einsum("tef,efd->td", h, p["wo"], preferred_element_type=jnp.float32)
    if moe.n_shared:
        with jax.named_scope("moe.shared"):
            out = out + mlp_apply(p["shared"], xt, cfg, None)
    return out.reshape(b, s, d).astype(x.dtype)
