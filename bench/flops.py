"""Operations and bytes that the served work needs, through the cell's
architecture module.

Counted by what the algorithm needs, whatever implements it: a later
kernel that moves fewer bytes or skips padded work is judged on the same
counts. ``arch`` is a configuration's architecture module (its
``token_flops``, ``logits_flops`` and ``block_bytes``), ``s`` its
``sizes_of`` dict; nothing here depends on the shape of the model.
"""

from __future__ import annotations

import json
import os

BLOCK_TOKENS = 16  # tokens in one pool block


def request_flops(arch, s: dict, prompt_len: int, hit_tokens: int, n_out: int) -> float:
    """The work one served request needs: the prompt positions not served
    from the pool, the n_out - 1 decode steps, and logits for the last
    prompt position and each decode step."""
    if n_out < 1:
        return 0.0
    first = min(hit_tokens, prompt_len - 1)
    last = prompt_len + n_out - 2  # position of the last token fed
    total = sum(arch.token_flops(s, p + 1) for p in range(first, last + 1))
    return total + n_out * arch.logits_flops(s)


def copy_bytes(arch, s: dict, n_blocks: int) -> int:
    """A block copy between pool and cache reads and writes every byte once."""
    return 2 * n_blocks * arch.block_bytes(s)


def peak(device_kind: str) -> dict:
    """The chip's published peaks (``bench/peaks.json``), by ``device_kind``.
    A kind that is not in the table is an error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]
