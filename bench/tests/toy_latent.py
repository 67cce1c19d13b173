"""A toy architecture module with a latent KV cache, for the harness's tests.

Shaped like multi-head latent attention (MLA): queries through a low-rank
``q_a``/``q_b`` pair; keys and values come from one cached latent row per
token and layer, ``kv_lora_rank`` wide, beside a shared rotary key part
``qk_rope_head_dim`` wide. So a pool block holds one part per layer, of
width ``kv_lora_rank + qk_rope_head_dim``, and no key/value pair. A dense
SiLU MLP follows. It provides what ``bench/engine.py`` and the metric
readers take from an architecture module; it has no reference
(``logits_at``), and its weights are zeros, as no test runs it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.flops import BLOCK_TOKENS

KV_BYTES = 2  # bf16 latent rows

TINY = {"hidden_size": 64, "num_attention_heads": 2, "q_lora_rank": 32, "kv_lora_rank": 512,
        "qk_rope_head_dim": 64, "qk_nope_head_dim": 16, "v_head_dim": 16,
        "intermediate_size": 64, "num_hidden_layers": 2, "vocab_size": 128}


def sizes_of(cfg: dict) -> dict:
    return {
        "layers": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
        "heads": cfg["num_attention_heads"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"], "rope_dim": cfg["qk_rope_head_dim"],
        "nope_dim": cfg["qk_nope_head_dim"], "v_dim": cfg["v_head_dim"],
        "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
    }


def program_fields(s: dict) -> dict:
    return {
        "n_layers": s["layers"], "d_model": s["d"], "n_heads": s["heads"],
        "q_lora_rank": s["q_rank"], "kv_lora_rank": s["kv_rank"],
        "qk_rope_head_dim": s["rope_dim"], "qk_nope_head_dim": s["nope_dim"],
        "v_head_dim": s["v_dim"], "d_ff": s["ff"], "vocab_size": s["vocab"],
    }


def weight_shapes(s: dict) -> dict:
    L, d, h, ff, v = s["layers"], s["d"], s["heads"], s["ff"], s["vocab"]
    qk = s["nope_dim"] + s["rope_dim"]
    return {
        "embed": {"table": (v, d), "head": (d, v)},
        "final_ln": {"w": (d,)},
        "stack": {
            "ln1": {"w": (L, d)},
            "ln2": {"w": (L, d)},
            "attn": {
                "wq_a": (L, d, s["q_rank"]), "q_norm": (L, s["q_rank"]),
                "wq_b": (L, s["q_rank"], h * qk),
                "wkv_a": (L, d, s["kv_rank"] + s["rope_dim"]), "kv_norm": (L, s["kv_rank"]),
                "wkv_b": (L, s["kv_rank"], h * (s["nope_dim"] + s["v_dim"])),
                "wo": (L, h * s["v_dim"], d),
            },
            "mlp": {"wi_gate": (L, d, ff), "wi_up": (L, d, ff), "wo": (L, ff, d)},
        },
    }


def make_weights(sizes: dict, seed: int) -> dict:
    del seed
    return jax.tree.map(lambda shape: jnp.zeros(shape, jnp.bfloat16), weight_shapes(sizes),
                        is_leaf=lambda x: isinstance(x, tuple))


def _matrices(s: dict) -> int:
    """Weights one token multiplies in one layer (norm gains left out)."""
    d, h, qr, kr = s["d"], s["heads"], s["q_rank"], s["kv_rank"]
    return (d * qr + qr * h * (s["nope_dim"] + s["rope_dim"]) + d * (kr + s["rope_dim"])
            + kr * h * (s["nope_dim"] + s["v_dim"]) + h * s["v_dim"] * d + 3 * d * s["ff"])


def params(s: dict) -> int:
    layer = _matrices(s) + 2 * s["d"] + s["q_rank"] + s["kv_rank"]
    return 2 * s["vocab"] * s["d"] + s["layers"] * layer + s["d"]


def block_bytes(s: dict) -> int:
    """One latent row of each of BLOCK_TOKENS tokens in every layer."""
    return s["layers"] * BLOCK_TOKENS * (s["kv_rank"] + s["rope_dim"]) * KV_BYTES


def token_flops(s: dict, context: int) -> float:
    """Matrices, then attention in its expanded form: QK^T over
    nope + rope dims, PV over v dims, per head."""
    h = s["heads"]
    attn = 2 * context * h * (s["nope_dim"] + s["rope_dim"]) + 2 * context * h * s["v_dim"]
    return 2.0 * s["layers"] * _matrices(s) + s["layers"] * attn


def logits_flops(s: dict) -> float:
    return 2.0 * s["d"] * s["vocab"]
