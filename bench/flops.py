"""Operations and bytes that the served work needs, from the sizes alone.

Counted by what the algorithm needs, whatever implements it: a later
kernel that moves fewer bytes or skips padded work is judged on the same
counts. ``sizes`` is a configuration's ``sizes_of`` dict.
"""

from __future__ import annotations

import json
import os

BLOCK_TOKENS = 16
KV_BYTES = 2  # bf16 keys and values


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


def request_flops(s: dict, prompt_len: int, hit_tokens: int, n_out: int) -> float:
    """The work one served request needs: the prompt positions not served
    from the pool, the n_out - 1 decode steps, and logits for the last
    prompt position and each decode step."""
    if n_out < 1:
        return 0.0
    first = min(hit_tokens, prompt_len - 1)
    last = prompt_len + n_out - 2  # position of the last token fed
    total = sum(token_flops(s, p + 1) for p in range(first, last + 1))
    return total + n_out * logits_flops(s)


def copy_bytes(s: dict, n_blocks: int) -> int:
    """A block copy between pool and cache reads and writes every byte once."""
    return 2 * n_blocks * block_bytes(s)


def peak(device_kind: str) -> dict:
    """The chip's published peaks (``bench/peaks.json``), by ``device_kind``.
    A kind that is not in the table is an error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]
