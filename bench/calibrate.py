"""Readings that set a cell's rate and its correctness limit, many seeds
in one process (set-up compiles once).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10
        the program's widest logit gap and the fp8 control's, per seed, over
        the same sample of served requests (the control lives here only:
        ``run.py`` never runs it);
    python3 bench/calibrate.py --workload <cell> --seeds 1 --seconds 30 \\
        --rates 0.5,0.7,0.9
        a rate sweep without the check: latency tails and whether the queue
        grows (mean wait in the second half of the window over the first).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run, traffic  # noqa: E402


def control_gaps(cell, weights, records, seed: int, max_len: int):
    """Per sampled request, the widest gap of the program's served tokens and
    of the tokens the fp8 control puts first, both under the reference's
    logits: the sample and the reference are ``run.py``'s own."""
    import jax.numpy as jnp

    n_read = traffic.size_support(cell.mix["output_tokens"])[1]
    served, ctrl = [], []
    for rec in run.sample_for_check(records, seed, cell.params["check_tokens"]):
        ref = run.reference_logits(cell, weights, rec, max_len, n_read)
        low = run.reference_logits(cell, weights, rec, max_len, n_read, fp8=True)
        served.append(float(run.gap_below_best(ref, rec.tokens).max()))
        ctrl.append(float(run.gap_below_best(ref, jnp.argmax(low, axis=-1)).max()))
    return served, ctrl


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    args = ap.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell = run.Cell.load(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r]
    for seed in seeds:
        for rate in rates or [cell.params["rate_per_s"]]:
            cell.params = dict(cell.params, rate_per_s=rate)
            t = time.perf_counter()
            built = {}
            res, recs = run.run_cell(
                cell, seed, args.seconds, False, check=False,
                engine_hook=lambda eng: built.update(weights=eng.params, max_len=eng.max_len),
                log=lambda s: print("  " + s, flush=True))
            waits = [r.start - r.due for r in recs]
            half = len(waits) // 2
            line = {"seed": seed, "rate": rate, "wall_s": time.perf_counter() - t,
                    "e2e": {k: v["value"] for k, v in res["metrics"].items()},
                    "wait_first_half_s": sum(waits[:half]) / max(half, 1),
                    "wait_second_half_s": sum(waits[half:]) / max(len(waits) - half, 1),
                    "n": len(recs), "failed": res["failed"],
                    "mem_peak": res["device"]["memory_peak_bytes"]}
            if not rates:
                gaps, ctrl = control_gaps(cell, built["weights"], recs, seed, built["max_len"])
                line.update(gap=max(gaps), control_gap=max(ctrl), gaps=gaps, control_gaps=ctrl)
            print("CAL " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
