"""Plain reference of a decoder with latent attention (MLA) and a share of
a mixture of experts: DeepSeek-V3's block, as one chip's share of an
expert-parallel layer.

Written from the published description (DeepSeek-V3 technical report and
the published ``config.json`` / ``modeling_deepseek.py``) and nothing of the
program. With RMSNorm n(.), h heads, and positions p:

    x   = embed[tokens]
    per layer:
        a     = n1(x)
        c_q   = n_q(a W_DQ);   [q_n | q_r] = c_q W_UQ (per head, nope | rope)
        [c | k_r] = a W_DKV;   c = n_kv(c);   k_r = rope(k_r, p)  (one for all heads)
        c, k_r rounded to bf16, as the cache holds them
        [k_n | v] = c W_UKV (per head, nope | v)               (expanded form)
        s     = (q_n . k_n + rope(q_r, p) . k_r) * scale,  causal softmax
        x    += (softmax(s) v) W_O
        b     = n2(x)
        leading dense layers:  x += W_down (silu(W_gate b) * (W_up b))
        MoE layers:            x += shared(b) + sum_{e held} g_e(b) E_e(b)
    logits = head . n_f(x)

- ``scale = (nope + rope)^-0.5 * mscale^2``, ``mscale = 0.1 * mscale_all_dim
  * ln(factor) + 1`` (YaRN).
- Rope: YaRN frequencies as ``DeepseekV3YarnRotaryEmbedding`` gives them:
  theta^(-2i/rope) extrapolated above the correction range of (beta_fast,
  beta_slow) over ``original_max_position_embeddings``, divided by
  ``factor`` below it, a linear ramp between; cos and sin times
  mscale(mscale) / mscale(mscale_all_dim).
- Routing (``noaux_tc``): scores = sigmoid(b W_router) over all routed
  experts, in float32; the correction bias is added for selection only;
  each of ``n_group`` groups is scored by the sum of its two best biased
  scores, the best ``topk_group`` groups are kept, and the top
  ``num_experts_per_tok`` experts inside them chosen; g_e is the unbiased
  score of a chosen expert, normalized over the chosen to sum 1, times
  ``routed_scaling_factor``; 0 for the others.
- Experts and the shared expert are SwiGLU MLPs ``moe_intermediate_size``
  wide (the shared one ``n_shared_experts`` times that).

Everything is float32 with products at ``Precision.HIGHEST``, in blocks of
``Q_CHUNK`` query rows so that 8.5k positions fit on one chip, but for the
cached latent rows ``[c | k_r]``: the configuration holds them in bf16
(``KV_BYTES``), and every query attends to them as they are held. A served
model routes discontinuously, so a float32 latent here would move a
near-tied expert in and out of a token's choice against any deployment
that holds its cache as configured.

Departures from the published model, in the program and the reference
alike:

- One chip's share: only experts ``first_held_expert`` ..
  ``first_held_expert + n_routed_experts - 1`` of ``experts_routed`` are
  held, and the layer adds what they give; the router keeps all its
  outputs. What absent experts would add is left out.
- Rope rotates (first half, second half) pairs, not the published
  interleaved pairs: a fixed permutation of the rope columns of W_UQ and
  W_DKV, so nothing the weights can express is lost.
- Multi-token prediction (``num_nextn_predict_layers``) is left out: the
  served path does not speculate.
- Depth is cut (``num_hidden_layers``), the leading dense layers to one
  (``first_k_dense_replace``).

The counts (``params``, ``block_bytes``, ``token_flops``, ``logits_flops``)
are what this architecture needs, whatever implements it: matrices counted
once a token, attention in its expanded form over the context (QK^T over
nope + rope, PV over v, per head), and routed experts at their expectation
on this chip, held x experts-per-token / routed (8 x 8 / 256 = 0.25) expert
evaluations a token and MoE layer.

``make_weights`` draws N(0, 1/fan_in) weights from the seed in one jitted
call, bf16 but the router, which the program keeps in float32; norm gains
are 1 and the correction bias 0. ``logits_at(..., fp8=True)`` is the
control: every product with a weight takes both operands rounded to
float8 e4m3 (per-row scales for activations, per-output-column scales for
weights), the step below the bfloat16 that the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import BLOCK_TOKENS

KV_BYTES = 2  # bf16 latent rows
HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_CHUNK = 128  # query rows per block: bounds the scores and activations in memory
HEAD_BLOCKS = 8  # the head's columns in this many blocks: no whole f32 copy of it


def sizes_of(cfg: dict) -> dict:
    """The sizes this architecture needs, from a config file's keys."""
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("tie_word_embeddings"):
        raise ValueError("mla_moe: silu MLPs and an untied head only")
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("mla_moe: sigmoid, group-limited (noaux_tc) routing only")
    y = cfg["rope_scaling"]
    return {
        "layers": cfg["num_hidden_layers"], "dense_layers": cfg["first_k_dense_replace"],
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
        "expert_ff": cfg["moe_intermediate_size"], "experts": cfg["experts_routed"],
        "held": cfg["n_routed_experts"], "first_held": cfg["first_held_expert"],
        "shared": cfg["n_shared_experts"], "top_k": cfg["num_experts_per_tok"],
        "groups": cfg["n_group"], "topk_groups": cfg["topk_group"],
        "scaling": float(cfg["routed_scaling_factor"]), "vocab": cfg["vocab_size"],
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "yarn_factor": float(y["factor"]), "yarn_len": y["original_max_position_embeddings"],
        "beta_fast": float(y["beta_fast"]), "beta_slow": float(y["beta_slow"]),
        "mscale": float(y["mscale"]), "mscale_all_dim": float(y["mscale_all_dim"]),
    }


# sizes of a CPU test cell: the program's reduced widths
TINY = {"hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
        "num_hidden_layers": 3, "first_k_dense_replace": 1, "experts_routed": 16,
        "n_routed_experts": 4, "first_held_expert": 0, "num_experts_per_tok": 4,
        "n_group": 4, "topk_group": 2, "vocab_size": 256}

_MISSING = object()


class Has(dict):
    """Equal to any object whose attributes hold these values: a nested
    part of the program's config, compared field by field."""

    def __eq__(self, other):
        return all(getattr(other, k, _MISSING) == v for k, v in self.items())

    def __ne__(self, other):
        return not self == other

    __hash__ = None


def program_fields(s: dict) -> dict:
    """``RealEngine.cfg`` attribute -> the value it must hold for these sizes."""
    return {
        "n_layers": s["layers"], "first_k_dense": s["dense_layers"], "d_model": s["d"],
        "n_heads": s["heads"], "q_lora_rank": s["q_rank"], "kv_lora_rank": s["kv_rank"],
        "qk_nope_head_dim": s["nope"], "qk_rope_head_dim": s["rope"],
        "v_head_dim": s["v_dim"], "d_ff": s["ff"], "vocab_size": s["vocab"],
        "rope_theta": s["theta"], "norm_eps": s["eps"], "tie_embeddings": False,
        "act": "silu",
        "yarn": Has(factor=s["yarn_factor"], original_len=s["yarn_len"],
                    beta_fast=s["beta_fast"], beta_slow=s["beta_slow"],
                    mscale=s["mscale"], mscale_all_dim=s["mscale_all_dim"]),
        "moe": Has(n_experts=s["experts"], n_held=s["held"], first_held=s["first_held"],
                   top_k=s["top_k"], scoring="sigmoid", n_groups=s["groups"],
                   topk_groups=s["topk_groups"], routed_scaling=s["scaling"],
                   n_shared=s["shared"], expert_ff=s["expert_ff"]),
    }


# ---------------------------------------------------------------------------
# counts: parameters, pool bytes, operations
# ---------------------------------------------------------------------------


def _attn_matrices(s: dict) -> int:
    d, h, qr, kr = s["d"], s["heads"], s["q_rank"], s["kv_rank"]
    return (d * qr + qr * h * (s["nope"] + s["rope"]) + d * (kr + s["rope"])
            + kr * h * (s["nope"] + s["v_dim"]) + h * s["v_dim"] * d)


def _norms(s: dict) -> int:
    return 2 * s["d"] + s["q_rank"] + s["kv_rank"]


def _moe_layers(s: dict) -> int:
    return s["layers"] - s["dense_layers"]


def params(s: dict) -> int:
    """All weights: embedding table, layers, final norm and the head."""
    d, ef = s["d"], s["expert_ff"]
    dense = _attn_matrices(s) + _norms(s) + 3 * d * s["ff"]
    moe = (_attn_matrices(s) + _norms(s) + d * s["experts"] + s["experts"]
           + 3 * d * ef * (s["held"] + s["shared"]))
    return 2 * s["vocab"] * d + d + s["dense_layers"] * dense + _moe_layers(s) * moe


def block_bytes(s: dict) -> int:
    """One pool block: one latent row ([c | k_r]) of BLOCK_TOKENS tokens in
    every layer."""
    return s["layers"] * BLOCK_TOKENS * (s["kv_rank"] + s["rope"]) * KV_BYTES


def token_flops(s: dict, context: int) -> float:
    """One token through every layer with ``context`` keys to attend to
    (itself included): 2 per multiply-add of the matrices, routed experts at
    their expectation here, and attention in its expanded form. The head is
    counted apart, by ``logits_flops``."""
    d, ef = s["d"], s["expert_ff"]
    routed = s["held"] * s["top_k"] / s["experts"]  # expert evaluations a token here
    moe = d * s["experts"] + 3 * d * ef * (s["shared"] + routed)
    mats = s["layers"] * _attn_matrices(s) + s["dense_layers"] * 3 * d * s["ff"]
    mats += _moe_layers(s) * moe
    attn = 2 * context * s["heads"] * (s["nope"] + s["rope"] + s["v_dim"]) * s["layers"]
    return 2.0 * mats + attn


def logits_flops(s: dict) -> float:
    return 2.0 * s["d"] * s["vocab"]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def weight_shapes(s: dict) -> dict:
    """Leaf shapes in the program's layout (``Model.init``'s tree)."""
    d, h, qr, kr, v = s["d"], s["heads"], s["q_rank"], s["kv_rank"], s["vocab"]

    def attn(n):
        return {
            "wq_a": (n, d, qr), "q_norm": (n, qr), "wq_b": (n, qr, h, s["nope"] + s["rope"]),
            "wkv_a": (n, d, kr + s["rope"]), "kv_norm": (n, kr),
            "wkv_b": (n, kr, h, s["nope"] + s["v_dim"]), "wo": (n, h, s["v_dim"], d),
        }

    def mlp(n, f):
        return {"wi_gate": (n, d, f), "wi_up": (n, d, f), "wo": (n, f, d)}

    k, n, e, ef = s["dense_layers"], _moe_layers(s), s["held"], s["expert_ff"]
    return {
        "embed": {"table": (v, d), "head": (d, v)},
        "final_ln": {"w": (d,)},
        "lead": {"pos_0": {"ln1": {"w": (k, d)}, "ln2": {"w": (k, d)}, "attn": attn(k),
                           "mlp": mlp(k, s["ff"])}},
        "stack": {"pos_0": {
            "ln1": {"w": (n, d)}, "ln2": {"w": (n, d)}, "attn": attn(n),
            "moe": {"router": (n, d, s["experts"]), "router_bias": (n, s["experts"]),
                    "wi_gate": (n, e, d, ef), "wi_up": (n, e, d, ef), "wo": (n, e, ef, d),
                    "shared": mlp(n, s["shared"] * ef)},
        }},
    }


def _fan_in(names: tuple, shape: tuple) -> int:
    """Inputs summed by each output of the leaf (weights ~ N(0, 1/fan_in))."""
    leaf = names[-1]
    if leaf == "table":
        return 1  # unit-variance embeddings
    if leaf == "head":
        return shape[0]
    if names[-2] == "attn" and leaf == "wo":
        return shape[1] * shape[2]  # (layers, heads, v, d)
    if names[-2] == "moe" and leaf != "router":
        return shape[2]  # (layers, experts, in, out)
    return shape[1]  # (layers, in, ...) stacked matrices


@functools.partial(jax.jit, static_argnames=("sizes_key",))
def _make(key, sizes_key):
    shapes = weight_shapes(dict(sizes_key))
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, int) for i in x)
    )
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        names = tuple(p.key for p in path)
        if names[-1] in ("w", "q_norm", "kv_norm"):  # norm gains
            out.append(jnp.ones(shape, jnp.bfloat16))
        elif names[-1] == "router_bias":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            w = jax.random.normal(k, shape, jnp.float32) * _fan_in(names, shape) ** -0.5
            out.append(w if names[-1] == "router" else w.astype(jnp.bfloat16))
    return jax.tree.unflatten(tree, out)


def make_weights(sizes: dict, seed: int) -> dict:
    """Weights from any non-negative seed, made on the default device."""
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return _make(key, tuple(sorted(sizes.items())))


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------


def _f8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """(rows, k) @ (k, n) in float32; fp8 rounds both operands first."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _f8(x, axis=-1), _f8(w, axis=0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(
        jnp.float32
    )


def _held(x: jax.Array) -> jax.Array:
    """A latent row as the cache holds it: rounded to bf16 (``KV_BYTES``)."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_table(s: dict, pos: jax.Array):
    """cos, sin (len(pos), rope/2) of the YaRN rope at positions ``pos``."""
    dim, base, factor = s["rope"], s["theta"], s["yarn_factor"]
    i = np.arange(0, dim, 2) / dim
    extra, inter = base ** -i, base ** -i / factor

    def corr_dim(rotations):
        return dim * math.log(s["yarn_len"] / (rotations * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(corr_dim(s["beta_fast"])), 0)
    hi = min(math.ceil(corr_dim(s["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / ((hi - lo) or 0.001), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)
    m = _yarn_mscale(factor, s["mscale"]) / _yarn_mscale(factor, s["mscale_all_dim"])
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rope(x: jax.Array, cos, sin) -> jax.Array:
    """x: (S, ..., dim); rotate the (first half, second half) pairs."""
    half = x.shape[-1] // 2
    extra = (slice(None),) + (None,) * (x.ndim - 2)
    c, s_ = cos[extra], sin[extra]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s_, b * c + a * s_], axis=-1)


def _route(logits: jax.Array, bias: jax.Array, s: dict) -> jax.Array:
    """(rows, experts) router logits -> each expert's gate: the normalized,
    scaled score of the chosen top-k, 0 elsewhere (see the module doc)."""
    rows, e = logits.shape
    scores = 1.0 / (1.0 + jnp.exp(-logits))
    biased = scores + bias
    per_group = e // s["groups"]
    best_two = -jnp.sort(-biased.reshape(rows, s["groups"], per_group), axis=-1)[..., :2]
    group_rank = jnp.argsort(-best_two.sum(-1), axis=-1, stable=True)
    kept = jnp.zeros((rows, s["groups"]), bool).at[
        jnp.arange(rows)[:, None], group_rank[:, : s["topk_groups"]]].set(True)
    biased = jnp.where(jnp.repeat(kept, per_group, axis=1), biased, -jnp.inf)
    chosen = jnp.argsort(-biased, axis=-1, stable=True)[:, : s["top_k"]]
    gate = jnp.zeros_like(scores).at[jnp.arange(rows)[:, None], chosen].set(1.0) * scores
    return gate / gate.sum(-1, keepdims=True) * s["scaling"]


def _swiglu(b, m, fp8):
    g = _mm(b, m["wi_gate"], fp8)
    return _mm(jax.nn.silu(g) * _mm(b, m["wi_up"], fp8), m["wo"], fp8)


def moe_block(b: jax.Array, m: dict, s: dict, fp8: bool = False) -> jax.Array:
    """The MoE layer's share on normed rows b (rows, d): the held experts'
    gated outputs and the shared experts."""
    logits = _mm(b, m["router"], fp8)
    gate = _route(logits, m["router_bias"].astype(jnp.float32), s)
    gate = gate[:, s["first_held"] : s["first_held"] + s["held"]]

    def expert(e_w):
        return _swiglu(b, {"wi_gate": e_w[0], "wi_up": e_w[1], "wo": e_w[2]}, fp8)

    outs = jax.lax.map(expert, (m["wi_gate"], m["wi_up"], m["wo"]))  # (held, rows, d)
    return jnp.einsum("erd,re->rd", outs, gate, precision=HIGHEST) + _swiglu(b, m["shared"], fp8)


def _layer(x, p, s, cos, sin, fp8, ffn):
    """One layer over all S positions, its queries and MLP in blocks."""
    S = x.shape[0]
    a, h, eps = p["attn"], s["heads"], s["eps"]
    nope, rope, vd, kr = s["nope"], s["rope"], s["v_dim"], s["kv_rank"]
    scale = (nope + rope) ** -0.5 * _yarn_mscale(s["yarn_factor"], s["mscale_all_dim"]) ** 2
    n1 = _rms(x, p["ln1"]["w"], eps)
    kv = _mm(n1, a["wkv_a"], fp8)
    c = _held(_rms(kv[:, :kr], a["kv_norm"], eps))
    k_r = _held(_rope(kv[:, kr:], cos, sin))  # (S, rope)
    kvx = _mm(c, a["wkv_b"].reshape(kr, h * (nope + vd)), fp8).reshape(S, h, nope + vd)
    k_n, v = kvx[..., :nope], kvx[..., nope:]
    kpos = jnp.arange(S)

    def block(args):
        i, xb, nb = args  # block index, residual rows, normed rows
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        c_q = _rms(_mm(nb, a["wq_a"], fp8), a["q_norm"], eps)
        q = _mm(c_q, a["wq_b"].reshape(s["q_rank"], -1), fp8).reshape(Q_CHUNK, h, nope + rope)
        q_r = _rope(q[..., nope:], cos[qpos], sin[qpos])
        sc = (jnp.einsum("qhn,khn->hqk", q[..., :nope], k_n, precision=HIGHEST)
              + jnp.einsum("qhr,kr->hqk", q_r, k_r, precision=HIGHEST)) * scale
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khv->qhv", jax.nn.softmax(sc, axis=-1), v, precision=HIGHEST)
        xb = xb + _mm(o.reshape(Q_CHUNK, h * vd), a["wo"].reshape(h * vd, -1), fp8)
        return xb + ffn(_rms(xb, p["ln2"]["w"], eps), p, fp8)

    n = S // Q_CHUNK
    out = jax.lax.map(block, (jnp.arange(n), x.reshape(n, Q_CHUNK, -1),
                              n1.reshape(n, Q_CHUNK, -1)))
    return out.reshape(S, -1)


@functools.partial(jax.jit, static_argnames=("sizes_key", "fp8"))
def _logits_at(w, tokens, read, sizes_key, fp8):
    s = dict(sizes_key)
    x = w["embed"]["table"][tokens].astype(jnp.float32)
    if fp8:
        x = _f8(x, axis=-1)  # the table's rows, each with its own scale
    cos, sin = _rope_table(s, jnp.arange(tokens.shape[0]))

    def dense(b, p, fp8):
        return _swiglu(b, p["mlp"], fp8)

    def moe(b, p, fp8):
        return moe_block(b, p["moe"], s, fp8)

    for part, ffn in (("lead", dense), ("stack", moe)):
        x, _ = jax.lax.scan(lambda x, p: (_layer(x, p, s, cos, sin, fp8, ffn), None),
                            x, w[part]["pos_0"])
    hf = _rms(x[read], w["final_ln"]["w"], s["eps"])
    d, v = w["embed"]["head"].shape
    nb = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    head = w["embed"]["head"].reshape(d, nb, v // nb).transpose(1, 0, 2)
    out = jax.lax.map(lambda hb: _mm(hf, hb, fp8), head)  # (nb, rows, v / nb)
    return out.transpose(1, 0, 2).reshape(hf.shape[0], v)


def logits_at(weights: dict, sizes: dict, tokens: list[int], read: list[int],
              pad_to: int, n_read: int, fp8: bool = False) -> jax.Array:
    """Reference logits (len(read), vocab) after ``tokens[: p + 1]`` for
    each p in ``read``. Causal, so padding ``tokens`` up to ``pad_to`` and
    ``read`` up to ``n_read`` (one compiled shape for a whole cell) leaves
    them unchanged."""
    S = -(-max(pad_to, len(tokens)) // Q_CHUNK) * Q_CHUNK
    t = jnp.zeros((S,), jnp.int32).at[: len(tokens)].set(jnp.asarray(tokens, jnp.int32))
    r = jnp.zeros((max(n_read, len(read)),), jnp.int32).at[: len(read)].set(
        jnp.asarray(read, jnp.int32))
    out = _logits_at(weights, t, r, tuple(sorted(sizes.items())), fp8)
    return out[: len(read)]
