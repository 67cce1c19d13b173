"""Record the small TPU trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_fixture.py <out_dir>

Runs the unshared and shared-prefix cells at the program's reduced widths
(a few requests each, copy kernels as jnp) with the profiler on, and
keeps the ``.xplane.pb`` of the shared-prefix run under ``<out_dir>``.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import run  # noqa: E402
from bench.tests import tiny  # noqa: E402


def main(out_dir: str) -> None:
    for name in ("qwen3-32b-noqknorm-4L.unshared", "qwen3-32b-noqknorm-4L.shared-sysprompt"):
        cell = tiny.tiny_cell(name)
        program = dict(cell.config["program"], layers=None)
        res, recs = run.run_cell(cell, 5, 0.3, True, kernel_mode="jnp", program=program,
                                 keep_trace=os.path.join(out_dir, name), log=print)
        print(res)
        for r in recs:
            print(r.start, r.end, r.prompt_len, r.hit_tokens, r.n_out)


if __name__ == "__main__":
    main(sys.argv[1])
