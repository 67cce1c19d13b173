"""Plain reference of a dense decoder with grouped-query attention.

The architecture of both configurations in this directory, written from
the published description and nothing of the program:

    x   = embed[tokens]
    per layer:  x += Wo . attn(rope(Wq . n1(x)), rope(Wk . n1(x)), Wv . n1(x))
                x += W_down . (silu(W_gate . n2(x)) * (W_up . n2(x)))
    logits = head . n_f(x)

with RMS norms, causal softmax attention scaled by 1/sqrt(head_dim), query
head h reading key/value head h // (heads / kv_heads), and rotary
embeddings on the two halves of each head (cos/sin over head_dim/2
frequencies theta^(-2i/head_dim)). Everything is float32 with matrix
products at ``Precision.HIGHEST``.

The counts (``params``, ``block_bytes``, ``token_flops``, ``logits_flops``)
are what this architecture needs, whatever implements it; ``program_fields``
is what the program's configuration has to read for these sizes.

``make_weights`` draws the weights from the seed in one jitted call, in
the type they are served in and in the layout the program takes them in
(the benchmark hands them to the engine). ``logits_at`` is the reference;
with ``fp8=True`` it is the control: every product with a weight matrix
takes both operands rounded to float8 e4m3 (per-row scales for
activations, per-output-column scales for weights), the step below the
bfloat16 that the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.flops import BLOCK_TOKENS

KV_BYTES = 2  # bf16 keys and values
HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_CHUNK = 256  # query rows per attention block: bounds the scores in memory


def sizes_of(cfg: dict) -> dict:
    """The sizes this architecture needs, from a config file's keys."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("tie_word_embeddings"):
        raise ValueError("dense_gqa: silu MLP and an untied head only")
    return {
        "layers": cfg["num_hidden_layers"],
        "d": d,
        "heads": h,
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", d // h),
        "ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"],
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


# sizes of a CPU test cell: the program's reduced widths
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
        "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-05}


def program_fields(s: dict) -> dict:
    """``RealEngine.cfg`` attribute -> the value it must hold for these sizes."""
    return {
        "n_layers": s["layers"], "d_model": s["d"], "n_heads": s["heads"],
        "n_kv_heads": s["kv_heads"], "head_dim": s["head_dim"], "d_ff": s["ff"],
        "vocab_size": s["vocab"], "rope_theta": s["theta"], "norm_eps": s["eps"],
        "qkv_bias": False, "tie_embeddings": False, "act": "silu",
    }


# ---------------------------------------------------------------------------
# counts: parameters, pool bytes, operations
# ---------------------------------------------------------------------------


def layer_params(s: dict) -> int:
    d, hq, hkv, hd = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    return 2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * s["ff"] + 2 * d


def params(s: dict) -> int:
    """All weights: embedding table, layers, final norm and the head."""
    return 2 * s["vocab"] * s["d"] + s["layers"] * layer_params(s) + s["d"]


def block_bytes(s: dict) -> int:
    """One pool block: keys and values of BLOCK_TOKENS tokens in every layer."""
    return 2 * s["layers"] * BLOCK_TOKENS * s["kv_heads"] * s["head_dim"] * KV_BYTES


def kv_bytes_per_token(s: dict) -> int:
    return block_bytes(s) // BLOCK_TOKENS


def token_flops(s: dict, context: int) -> float:
    """One token through every layer with ``context`` keys to attend to
    (itself included): 2 per multiply-add of the matrices, and of QK^T and
    PV. The head is counted apart, by ``logits_flops``."""
    attn = 4 * context * s["heads"] * s["head_dim"] * s["layers"]
    return 2.0 * (s["layers"] * (layer_params(s) - 2 * s["d"])) + attn


def logits_flops(s: dict) -> float:
    return 2.0 * s["d"] * s["vocab"]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def weight_shapes(s: dict) -> dict:
    """Leaf shapes in the program's layout (``Model.init``'s tree)."""
    L, d, hq, hkv, hd, ff, v = (
        s["layers"], s["d"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"],
        s["vocab"],
    )
    return {
        "embed": {"table": (v, d), "head": (d, v)},
        "final_ln": {"w": (d,)},
        "stack": {"pos_0": {
            "ln1": {"w": (L, d)},
            "ln2": {"w": (L, d)},
            "attn": {
                "wq": (L, d, hq, hd), "wk": (L, d, hkv * hd),
                "wv": (L, d, hkv * hd), "wo": (L, hq, hd, d),
            },
            "mlp": {"wi_gate": (L, d, ff), "wi_up": (L, d, ff), "wo": (L, ff, d)},
        }},
    }


def _fan_in(name: str, shape: tuple) -> int:
    """Inputs summed by each output of the leaf (weights ~ N(0, 1/fan_in))."""
    if name == "table":
        return 1  # unit-variance embeddings
    if name == "head":
        return shape[0]
    if len(shape) == 4 and name == "wo":
        return shape[1] * shape[2]  # attention output: heads * head_dim
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
        if names[-1] == "w":  # norm gains
            out.append(jnp.ones(shape, jnp.bfloat16))
        else:
            std = _fan_in(names[-1], shape) ** -0.5
            out.append((jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16))
    return jax.tree.unflatten(tree, out)


def make_weights(sizes: dict, seed: int) -> dict:
    """bf16 weights from any non-negative seed, made on the default device."""
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


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (S, heads, hd); rotate the (first half, second half) pairs."""
    S, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v):
    """Causal softmax attention. q: (S, hq, hd); k, v: (S, hkv, hd)."""
    S, hq, hd = q.shape
    hkv = k.shape[1]
    qg = q.reshape(S // Q_CHUNK, Q_CHUNK, hkv, hq // hkv, hd) / jnp.sqrt(jnp.float32(hd))
    kpos = jnp.arange(S)

    def block(args):
        i, qb = args  # qb: (C, hkv, rep, hd)
        s = jnp.einsum("cgrd,kgd->grck", qb, k, precision=HIGHEST)
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grck,kgd->cgrd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(S // Q_CHUNK), qg))
    return o.reshape(S, hq, hd)


@functools.partial(jax.jit, static_argnames=("sizes_key", "fp8"))
def _logits_at(w, tokens, read, sizes_key, fp8):
    s = dict(sizes_key)
    S = tokens.shape[0]
    hq, hkv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    x = w["embed"]["table"][tokens].astype(jnp.float32)
    if fp8:
        x = _f8(x, axis=-1)  # the table's rows, each with its own scale

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = _rms(x, p["ln1"]["w"], s["eps"])
        q = _mm(h, a["wq"].reshape(s["d"], hq * hd), fp8).reshape(S, hq, hd)
        k = _mm(h, a["wk"], fp8).reshape(S, hkv, hd)
        v = _mm(h, a["wv"], fp8).reshape(S, hkv, hd)
        o = _attention(_rope(q, s["theta"]), _rope(k, s["theta"]), v)
        x = x + _mm(o.reshape(S, hq * hd), a["wo"].reshape(hq * hd, s["d"]), fp8)
        h = _rms(x, p["ln2"]["w"], s["eps"])
        g = jax.nn.silu(_mm(h, m["wi_gate"], fp8)) * _mm(h, m["wi_up"], fp8)
        return x + _mm(g, m["wo"], fp8), None

    x, _ = jax.lax.scan(layer, x, w["stack"]["pos_0"])
    h = _rms(x[read], w["final_ln"]["w"], s["eps"])
    return _mm(h, w["embed"]["head"], fp8)


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
