"""The one traffic generator: a mix file of parameters -> a cell's requests.

A mix (``bench/traffic/<mix>.json``) gives distributions; a cell
(``bench/cells/<cell>.json``) gives the offered rate. The schedule is the
same for every seed: prompt lengths, output lengths, prefix choices and
inter-arrival gaps at the stratified quantiles of each distribution, in
one fixed order. The seed draws the token ids (and the weights, elsewhere).
At batch 1 the order of the work sets the queueing tails: with the order
drawn from the seed, TTFT medians of one cell differed by 30% between
seeds, so a seed changes what is computed, never how much or when.

Keys of a mix:

- ``prefixes``: ``null``, or ``{"count", "tokens", "zipf_s"}``: shared
  prefixes published into the pool during set-up, each request picking
  one by Zipf popularity and appending its own tail;
- ``prompt_tokens``: the tail after the prefix (or the whole prompt):
  ``{"dist": "log_uniform", "min", "max"}`` or
  ``{"dist": "grid", "values", "weights"}``;
- ``output_tokens``: the same forms, for the tokens generated;
- ``arrivals``: ``"poisson"`` (exponential gaps at the cell's rate).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

BLOCK_TOKENS = 16  # the pool's block size (RealEngine.create)


@dataclasses.dataclass
class Request:
    due_s: float  # seconds after the window opens
    prompt: list[int]
    max_new: int
    prefix: int  # index of the shared prefix, -1 for none


@dataclasses.dataclass
class Plan:
    setup_prompts: list[list[int]]  # published before the window
    warm: list[Request]  # one request per shape the window can use
    timed: list[Request]
    max_len: int
    shapes: dict  # {"prefill_tokens", "hit_blocks", "write_blocks"}: sorted lists

    @property
    def n_shapes(self) -> int:
        return sum(len(v) for v in self.shapes.values())

    def pool_writes(self) -> int:
        """Blocks the plan writes into the pool: every prompt that misses."""
        misses = self.setup_prompts + [
            r.prompt for r in self.warm + self.timed if r.prefix < 0
        ]
        return sum(len(p) // BLOCK_TOKENS for p in misses)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent numpy stream per purpose; any non-negative seed."""
    return np.random.default_rng([seed, *stream.encode()])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _counts(weights, n: int) -> np.ndarray:
    """Largest-remainder apportionment of n items to the weights."""
    w = np.asarray(weights, float) / np.sum(weights)
    raw = w * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[: n - out.sum()]:
        out[i] += 1
    return out


def draw_sizes(dist: dict, n: int) -> np.ndarray:
    """n sizes at the stratified quantiles of ``dist``, in ascending order."""
    if dist["dist"] == "log_uniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        return np.rint(np.exp(lo + _quantiles(n) * (hi - lo))).astype(int)
    if dist["dist"] == "grid":
        return np.repeat(dist["values"], _counts(dist["weights"], n)).astype(int)
    raise ValueError(f"unknown size distribution {dist['dist']!r}")


def size_support(dist: dict) -> tuple[int, int]:
    if dist["dist"] == "log_uniform":
        return dist["min"], dist["max"]
    return min(dist["values"]), max(dist["values"])


def _gaps(n: int, rate: float, seconds: float) -> np.ndarray:
    """Exponential inter-arrival gaps at stratified quantiles, scaled so
    that they add up to the window: the last request is due inside it."""
    g = -np.log1p(-_quantiles(n)) / rate
    return g * (seconds / g.sum())


def plan(mix: dict, rate_per_s: float, vocab: int, seed: int, seconds: float) -> Plan:
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    n = max(1, round(rate_per_s * seconds))
    order = rng_for(0, "order")  # the schedule: one for every seed
    toks = rng_for(seed, "tokens")

    def tokens(k: int) -> list[int]:
        return toks.integers(0, vocab, k).tolist()

    tails = order.permutation(draw_sizes(mix["prompt_tokens"], n))
    outs = order.permutation(draw_sizes(mix["output_tokens"], n))
    gaps = order.permutation(_gaps(n, rate_per_s, seconds))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])

    pre = mix.get("prefixes")
    prefixes: list[list[int]] = []
    if pre:
        prefixes = [tokens(pre["tokens"]) for _ in range(pre["count"])]
        zipf = [1.0 / (k + 1) ** pre["zipf_s"] for k in range(pre["count"])]
        which = order.permutation(np.repeat(np.arange(pre["count"]), _counts(zipf, n)))
    else:
        which = np.full(n, -1)

    def request(d: float, p: int, tail: int, m: int) -> Request:
        head = prefixes[p] if p >= 0 else []
        return Request(float(d), head + tokens(int(tail)), int(m), int(p))

    timed = [request(*a) for a in zip(due, which, tails, outs)]

    lo_tail, hi_tail = size_support(mix["prompt_tokens"])
    _, hi_out = size_support(mix["output_tokens"])
    prefix_len = pre["tokens"] if pre else 0
    max_len = -(-(prefix_len + hi_tail + hi_out) // BLOCK_TOKENS) * BLOCK_TOKENS
    if pre:
        # a hit request prefills its tail in fixed chunks against the fetched
        # cache: one compiled shape for any tail, warmed by one short tail
        warm = [request(0.0, 0, lo_tail, 2)]
        shapes = {
            "prefill_tokens": [prefix_len],
            "hit_blocks": [prefix_len // BLOCK_TOKENS],
            "write_blocks": [prefix_len // BLOCK_TOKENS],
        }
    else:
        lengths = sorted(set(mix["prompt_tokens"]["values"]))
        if mix["prompt_tokens"]["dist"] != "grid":
            raise ValueError("an unshared mix draws its prompt lengths from a grid")
        warm = [request(0.0, -1, k, 2) for k in lengths]
        shapes = {
            "prefill_tokens": lengths,
            "hit_blocks": [],
            "write_blocks": sorted({k // BLOCK_TOKENS for k in lengths} - {0}),
        }
    return Plan(prefixes, warm, timed, max_len, shapes)
