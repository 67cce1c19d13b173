"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: ``BENCHMARK.json`` gives its
configuration and traffic mix, ``bench/configs/<config>.json`` the sizes
and the name of its architecture module, ``bench/traffic/<mix>.json`` the
traffic's distributions, ``bench/cells/<cell>.json`` the offered rate, the
pool's size and the limit of the correctness check,
``bench/metrics/<metric>.py`` each per-layer metric, ``bench/peaks.json``
the chip's peaks.

The architecture module, ``bench/configs/<architecture>.py``, is the one
place for whatever depends on the shape of the model; the rest of the
harness holds no formula of any one architecture. It provides:

- ``sizes_of(config) -> sizes``: the sizes it needs from the config file's
  keys, as a flat dict that holds at least ``vocab`` (the traffic draws
  token ids below it);
- ``weight_shapes(sizes)`` and ``make_weights(sizes, seed)``: the weights
  in the program's parameter tree, bf16, made on the device in one call;
- ``logits_at(weights, sizes, tokens, read, pad_to, n_read, fp8=False)``:
  the plain reference's logits, float32, nothing of the program; with
  ``fp8=True`` the control, one precision below the configuration's;
- ``program_fields(sizes)``: each ``RealEngine.cfg`` attribute and the
  value it must hold, and ``block_bytes(sizes)``: the bytes of one pool
  block of ``flops.BLOCK_TOKENS`` tokens; ``bench/engine.py`` refuses a
  program that differs from either;
- ``params(sizes)``, ``token_flops(sizes, context)`` and
  ``logits_flops(sizes)``: parameters, and the operations of one token
  through every layer with ``context`` positions to attend to and of its
  logits. The counts are what the algorithm needs, whatever implements it;
- ``TINY``: config keys that cut it to the program's reduced widths, for
  the CPU tests (``bench/tests/tiny.py``).

A run: set-up (weights made on the chip from the seed, shared prefixes
published into the pool, every shape of the cell warmed), then an open
loop of requests due on a fixed schedule into the synchronous engine for
``--seconds``, drained to the last request; then the correctness check
against the plain reference. The last line of standard output is the
result as JSON. With ``--trace 1`` the window is profiled and the
per-layer metrics are reported in place of the end-to-end ones.

Exits nonzero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import flops, traffic  # noqa: E402


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything one cell needs, read from its files."""

    name: str
    chips: int
    traffic: str
    config: dict
    mix: dict
    params: dict  # bench/cells/<name>.json
    arch: object  # the configuration's architecture module
    sizes: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, name: str, root: str = ROOT) -> "Cell":
        spec = load_json(root, "BENCHMARK.json")
        w = next((w for w in spec["workloads"] if w["name"] == name), None)
        if w is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        config = load_json(BENCH, "configs", w["config"] + ".json")
        arch = load_module(os.path.join(BENCH, "configs", config["architecture"] + ".py"))

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        return cls(
            name, w["chips"], w["traffic"], config, load_json(BENCH, "traffic", w["traffic"] + ".json"),
            load_json(BENCH, "cells", name + ".json"), arch, arch.sizes_of(config),
            [m for m in spec["end_to_end"] if mine(m)],
            [m for m in spec["per_layer"] if mine(m)],
        )


@dataclasses.dataclass
class Record:
    due: float  # absolute host-clock times (perf_counter)
    start: float
    end: float
    prompt_len: int
    max_new: int
    prefix: int
    prompt: list | None = None
    ok: bool = False
    hit_tokens: int = 0
    ttft_s: float = float("nan")  # from the due time
    n_out: int = 0
    tpot_s: float | None = None
    tokens: list | None = None
    error: str = ""


class CompileCounter:
    """Compilations (or persistent-cache fetches) and compile seconds, as
    JAX's monitoring events report them."""

    def __init__(self, jax):
        self.n = 0
        self.secs = 0.0
        self.cache_hits = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.names.append(str(kw.get("fun_name", "?")))
        if event.startswith("/jax/core/compile/"):
            self.secs += duration_secs

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def percentile(xs, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(xs, float), q))


def serve_window(generate, timed, seconds: float, annotate, t0: float):
    """Open loop: requests in due order into the synchronous engine; sleep
    only while the engine is idle. Every request due in the window is
    served to the end. Returns (records, lateness of the idle loop)."""
    records, late = [], []
    for r in timed:
        due = t0 + r.due_s
        now = time.perf_counter()
        if now < due:
            with annotate("wait"):
                time.sleep(due - now)
            late.append(time.perf_counter() - due)
        rec = Record(due, time.perf_counter(), 0.0, len(r.prompt), r.max_new, r.prefix, r.prompt)
        with annotate("serve"):
            try:
                out, info = generate(r.prompt, r.max_new)
                rec.end = time.perf_counter()
                rec.ok = bool(info["logits_finite"])
                if not rec.ok:
                    rec.error = "non-finite logits"
                rec.hit_tokens = int(info["hit_tokens"])
                rec.ttft_s = (rec.start - due) + info["ttft_s"]
                rec.n_out = len(out)
                rec.tokens = list(out)
                if rec.n_out > 1:
                    rec.tpot_s = (info["total_s"] - info["ttft_s"]) / (rec.n_out - 1)
            except Exception as e:  # counted as failed, the loop goes on
                rec.end = time.perf_counter()
                rec.error = f"{type(e).__name__}: {e}"
        records.append(rec)
    return records, late


def end_to_end(records, seconds: float, t0: float) -> tuple[dict, dict]:
    ok = [r for r in records if r.ok]
    ttft = [r.ttft_s * 1e3 for r in ok]
    tpot = [r.tpot_s * 1e3 for r in ok if r.tpot_s is not None]
    close = t0 + seconds
    done = sum(r.n_out for r in ok if r.end <= close)
    values = {
        "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
        "ttft_p90_ms": percentile(ttft, 90) if ttft else None,
        "tpot_p90_ms": percentile(tpot, 90) if tpot else None,
        "output_tok_s": done / seconds,
    }
    counts = {"ttft_p50_ms": len(ttft), "ttft_p90_ms": len(ttft),
              "tpot_p90_ms": len(tpot), "output_tok_s": sum(r.end <= close for r in ok)}
    return values, counts


class MetricContext:
    """What a per-layer metric reader sees."""

    def __init__(self, records, summary, sizes, peak, arch):
        self.records = records
        self.trace = summary
        self.sizes = sizes
        self.peak = peak
        self.arch = arch  # the architecture module: counts for the readers

    def program(self, name: str):
        if self.trace is None:
            return None
        return self.trace.programs.get(name)

    def calls_by_request(self, *names: str):
        """[(record, device seconds)] for each traced call of a program named
        ``names``, matched to its request by the trace's ``serve`` spans
        (the trace covers the whole window: one span per request, in order)."""
        from bench import trace as trace_lib

        spans = self.trace.serve if self.trace is not None else []
        if len(spans) != len(self.records):
            return []
        out = []
        for name in names:
            p = self.program(name) or {"starts": [], "call_seconds": []}
            for start, secs in zip(p["starts"], p["call_seconds"]):
                i = trace_lib.span_of(spans, start)
                if i is not None:
                    out.append((self.records[i], secs))
        return out


def sample_for_check(records, seed: int, min_tokens: int):
    """Requests to compare, drawn from the seed: the one with the most
    output, the one with the longest prompt, then others at random until
    ``min_tokens`` served tokens are in the sample."""
    ok = [r for r in records if r.ok and r.n_out]
    if not ok:
        return []
    picks = {max(range(len(ok)), key=lambda i: (ok[i].n_out, ok[i].prompt_len)),
             max(range(len(ok)), key=lambda i: (ok[i].prompt_len, ok[i].n_out))}
    rest = traffic.rng_for(seed, "check").permutation(len(ok)).tolist()
    while rest and sum(ok[i].n_out for i in picks) < min_tokens:
        picks.add(rest.pop())
    return [ok[i] for i in sorted(picks)]


def reference_logits(cell: Cell, weights, rec: Record, max_len: int, n_read: int, **kw):
    """The plain reference's logits at each position where ``rec`` served a
    token: one pass over its prompt and served tokens."""
    import jax.numpy as jnp

    seq = rec.prompt + rec.tokens[:-1]
    read = list(range(len(rec.prompt) - 1, len(seq)))
    ref = cell.arch.logits_at(weights, cell.sizes, seq, read, max_len, n_read, **kw)
    if not bool(jnp.isfinite(ref).all()):
        raise RuntimeError("reference logits are not finite")
    return ref


def gap_below_best(ref, tokens):
    """How far each token's reference logit lies below the reference's best."""
    import jax.numpy as jnp

    return ref.max(axis=-1) - jnp.take_along_axis(ref, jnp.asarray(tokens)[:, None], axis=-1)[:, 0]


def warm_output_lengths(jax, plan) -> None:
    """``generate`` stacks one finiteness flag per output token and reduces
    them: a program compiled per output length. Warm each length the timed
    requests ask for, without decoding that many tokens."""
    import jax.numpy as jnp

    flag = jnp.isfinite(jnp.zeros((1,), jnp.float32)).all()
    for n in sorted({r.max_new for r in plan.timed}):
        jax.block_until_ready(jnp.stack([flag] * n).all())


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             kernel_mode: str = "pallas", program: dict | None = None,
             keep_trace: str | None = None, engine_hook=None, check: bool = True,
             log=print) -> tuple[dict, list]:
    """Set-up, window, drain, check; returns (result, records).

    ``program`` overrides the configuration's ``program`` entry and
    ``engine_hook(engine)`` runs on the built engine (tests: reduced widths
    on the CPU, planted faults; calibration: the weights)."""
    import jax
    import numpy as np

    from bench import engine as engine_lib
    from bench import trace as trace_lib

    dev = jax.devices()[0]
    counter = CompileCounter(jax)
    p = cell.params
    plan = traffic.plan(cell.mix, p["rate_per_s"], cell.sizes["vocab"], seed, seconds)
    pool_blocks = p["pool_blocks"]
    if plan.pool_writes() > pool_blocks:
        raise SystemExit(f"plan writes {plan.pool_writes()} blocks into a pool of {pool_blocks}")
    eng, weights = engine_lib.build(program or cell.config["program"], cell.sizes, cell.arch,
                                    pool_blocks, plan.max_len, seed, kernel_mode)
    if engine_hook is not None:
        engine_hook(eng)
    for prompt in plan.setup_prompts:
        _, info = eng.generate(prompt, 1)
        if info["hit_tokens"]:
            raise SystemExit("a set-up prefix hit the pool before it was published")
    for r in plan.warm:
        _, info = eng.generate(r.prompt, r.max_new)
        if r.prefix >= 0 and info["hit_tokens"] != len(plan.setup_prompts[r.prefix]):
            raise SystemExit(f"warm-up hit {info['hit_tokens']} tokens, not the whole prefix")
    warm_output_lengths(jax, plan)
    log(f"shapes warmed: {plan.n_shapes} {json.dumps(plan.shapes)}; max_len {plan.max_len}; "
        f"pool {pool_blocks} blocks x {eng.pool.layout.block_bytes} B, "
        f"planned writes {plan.pool_writes()} blocks")
    log(f"set-up compiles: {counter.n} programs, {counter.secs:.3f} s, "
        f"persistent-cache hits {counter.cache_hits}")

    trace_dir = None
    annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    if traced:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans: the harness's annotations only
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    n_before = counter.n
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    records, late = serve_window(eng.generate, plan.timed, seconds, annotate, t0)
    t_end = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    in_window = counter.n - n_before
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    eng = None  # the program's state goes before the reference runs
    gc.collect()

    values, counts = end_to_end(records, seconds, t0)
    failed = sum(not r.ok for r in records)
    log(f"window: {len(records)} requests due in {seconds} s, {failed} failed, "
        f"drained {t_end - t0 - seconds:.3f} s after the close; compiles in window {in_window} "
        f"{counter.names[n_before:]}")
    log(f"requests behind each metric: {json.dumps(counts)}")
    if late:
        log(f"loop lateness when idle: n {len(late)} p50 {percentile(late, 50) * 1e3:.3f} ms "
            f"max {max(late) * 1e3:.3f} ms")
    for r in records:
        if r.error:
            log(f"failed request: {r.error}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem_peak}
    out_metrics = {}
    breakdown = None
    if traced:
        tr = trace_lib.load(trace_lib.find_xplane(trace_dir))
        sm = trace_lib.summarize(tr)
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = sm.busy_s
        device["window_s"] = sm.window_s
        breakdown = trace_lib.breakdown(sm)
        log(f"idle by host phase (s): {json.dumps(trace_lib.idle_by_phase(sm))}")
        log("programs (device s, calls): " + json.dumps(
            {k: [v["seconds"], v["calls"]] for k, v in sm.programs.items()}))
        ctx = MetricContext(records, sm, cell.sizes, flops.peak(dev.device_kind), cell.arch)
        for m in cell.per_layer:
            v = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py")).read(ctx)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                out_metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif values.get(m["name"]) is not None:
                out_metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    if not traced:
        log("end-to-end: " + json.dumps({k: v["value"] for k, v in out_metrics.items()}))

    # correctness: the plain reference over a sample of the served requests
    t_check = time.perf_counter()
    samples = sample_for_check(records, seed, p["check_tokens"]) if check else []
    n_read = traffic.size_support(cell.mix["output_tokens"])[1]
    gaps = [gap_below_best(reference_logits(cell, weights, r, plan.max_len, n_read), r.tokens)
            for r in samples]
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    n_tok = sum(int(g.shape[0]) for g in gaps)
    log(f"check: {len(samples)} requests, {n_tok} served tokens, reference "
        f"{time.perf_counter() - t_check:.3f} s")
    compared = {
        "max_logit_gap": {"value": widest, "limit": p["max_logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = bool(samples) and widest <= p["max_logit_gap"] and failed == 0
    result = {"correct": correct, "attempted": len(records), "failed": failed,
              "metrics": out_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result, records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profile in this directory (default: deleted)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    cell = Cell.load(args.workload)

    import jax

    devs = jax.devices()
    print(f"device: platform {devs[0].platform} device_kind {devs[0].device_kind!r} "
          f"count {len(devs)}", flush=True)
    if devs[0].platform != "tpu":
        print("no TPU: this benchmark measures the chip and has no CPU fallback",
              file=sys.stderr)
        return 3
    if len(devs) < cell.chips:
        print(f"the cell needs {cell.chips} chips, JAX finds {len(devs)}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      keep_trace=args.keep_trace, log=lambda s: print(s, flush=True))
    for k, v in result["compared"].items():
        print(f"compared: {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
