"""A cell at the program's reduced CPU widths (its architecture module's
``TINY``), for tests on the CPU."""

from __future__ import annotations

import copy

from bench import run

MIXES = {
    "shared-sysprompt": {"prefixes": {"count": 2, "tokens": 32, "zipf_s": 1.0},
                         "prompt_tokens": {"dist": "log_uniform", "min": 4, "max": 16}},
    "unshared": {"prefixes": None,
                 "prompt_tokens": {"dist": "grid", "values": [16, 32], "weights": [0.5, 0.5]}},
}
OUTPUT = {"dist": "log_uniform", "min": 2, "max": 4}
CELLS = [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]]


def tiny_cell(name: str, n_requests: int = 3, seconds: float = 0.3,
              output=OUTPUT, check_tokens: int = 8) -> run.Cell:
    """The named cell with its sizes, lengths and rate cut to the CPU;
    everything else (metrics, limit, architecture module) as committed."""
    cell = run.Cell.load(name)
    cfg = dict(cell.config, **cell.arch.TINY)
    cell = copy.copy(cell)
    cell.config = cfg
    cell.sizes = cell.arch.sizes_of(cfg)
    cell.mix = dict(MIXES[cell.traffic], output_tokens=output, arrivals="poisson")
    cell.params = dict(cell.params, rate_per_s=n_requests / seconds, check_tokens=check_tokens,
                       pool_blocks=64)
    return cell


def run_tiny(name: str, seed: int = 7, log=lambda s: None, cell_kw=None, **kw):
    cell = tiny_cell(name, **(cell_kw or {}))
    program = dict(cell.config["program"], layers=None)
    return run.run_cell(cell, seed, 0.3, False, kernel_mode="interpret",
                        program=program, log=log, **kw)
