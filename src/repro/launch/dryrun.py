import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and extract roofline inputs from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
        --out results/dryrun

Per cell this records (JSON, one file per cell):
  * compiled.memory_analysis()   (per-device bytes: args/output/temp)
  * compiled.cost_analysis()     (XLA's numbers — under-count scans; kept
                                  for reference)
  * our HLO analysis             (repro.launch.hlo_analysis — trip-count
                                  corrected flops/bytes/collective bytes)
  * lower/compile wall time, HLO sizes, analytic MODEL_FLOPS
"""

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402


def model_flops(cfg, shape) -> float:
    """Analytic useful FLOPs for the cell (6·N·D train, 2·N_active fwd)."""
    n_active = cfg.active_param_count()
    n_total = cfg.param_count()
    tokens = shape.global_batch * (1 if shape.is_decode else shape.seq_len)
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    return 2.0 * n_active * tokens


def attn_model_flops(cfg, shape) -> float:
    """Analytic causal-attention FLOPs (not in 6·N·D; reported separately)."""
    n_attn = len(cfg.attn_layer_ids())
    if n_attn == 0 or cfg.n_heads == 0:
        return 0.0
    h, d = cfg.n_heads, cfg.head_dim
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        per = 2 * 2 * h * d * s * s / 2  # causal half, fwd
        return 3 * per * b * n_attn  # fwd + bwd(2x)
    if shape.kind == "prefill":
        return 2 * 2 * h * d * s * s / 2 * b * n_attn
    return 2 * 2 * h * d * s * b * n_attn  # decode: q=1 vs kv=s


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             runtime_overrides: dict | None = None, tag: str = "") -> dict:
    from repro.configs.base import RuntimeConfig, SHAPES, shape_applicable
    from repro.configs.registry import get_config
    from repro.distributed.sharding import AxisRules
    from repro.launch import steps as steps_lib
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}.{shape_name}.{mesh_name}" + (f".{tag}" if tag else "")
    rec: dict = {"cell": cell_id, "arch": arch, "shape": shape_name,
                 "mesh": mesh_name, "tag": tag or "baseline"}

    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    runtime = RuntimeConfig(**(runtime_overrides or {}))
    rec["runtime"] = dataclasses.asdict(runtime)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = AxisRules.create(mesh)
    n_chips = mesh.size

    t0 = time.time()
    try:
        cell = steps_lib.build_cell(cfg, shape, rules, runtime)
        lowered = steps_lib.lower_cell(cell, mesh)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        return rec

    rec["status"] = "ok"
    rec["notes"] = cell.notes
    rec["n_chips"] = n_chips
    rec["t_lower_s"] = round(t_lower, 2)
    rec["t_compile_s"] = round(t_compile, 2)

    try:
        ma = compiled.memory_analysis()
        print(ma)  # required by spec: proves it fits
        rec["memory_analysis"] = {
            "argument_size_bytes": int(ma.argument_size_in_bytes),
            "output_size_bytes": int(ma.output_size_in_bytes),
            "temp_size_bytes": int(ma.temp_size_in_bytes),
            "alias_size_bytes": int(ma.alias_size_in_bytes),
            "generated_code_size_bytes": int(ma.generated_code_size_in_bytes),
        }
        live = (
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes
        )
        rec["memory_analysis"]["live_bytes_per_device"] = int(live)
    except Exception as e:  # noqa: BLE001
        rec["memory_analysis"] = {"error": str(e)}

    try:
        ca = compiled.cost_analysis()
        print({k: ca[k] for k in ("flops", "transcendentals") if k in ca})
        rec["xla_cost_analysis"] = {
            k: float(v)
            for k, v in ca.items()
            if isinstance(v, (int, float)) and "{" not in k
        }
    except Exception as e:  # noqa: BLE001
        rec["xla_cost_analysis"] = {"error": str(e)}

    hlo = compiled.as_text()
    rec["hlo_chars"] = len(hlo)
    rec["hlo_analysis"] = analyze_hlo(hlo)
    rec["model_flops_total"] = model_flops(cfg, shape)
    rec["attn_model_flops_total"] = attn_model_flops(cfg, shape)
    rec["param_count"] = cfg.param_count()
    rec["active_param_count"] = cfg.active_param_count()

    if out_dir:
        os.makedirs(os.path.join(out_dir, "hlo"), exist_ok=True)
        with gzip.open(
            os.path.join(out_dir, "hlo", cell_id + ".hlo.gz"), "wt"
        ) as f:
            f.write(hlo)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--runtime-json", default=None,
                    help='RuntimeConfig overrides, e.g. \'{"decode_kv":"replicated"}\'')
    args = ap.parse_args()

    from repro.configs.base import SHAPES
    from repro.configs.registry import ASSIGNED

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    overrides = json.loads(args.runtime_json) if args.runtime_json else None

    cells: list[tuple[str, str, bool]] = []
    archs = list(ASSIGNED) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if (args.both_meshes or (args.all and not args.multi_pod)) else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    summary = []
    for arch, shape, mp in cells:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        cell_id = f"{arch}.{shape}.{mesh_name}" + (f".{args.tag}" if args.tag else "")
        path = os.path.join(args.out, cell_id + ".json")
        if os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            print(f"[cached] {cell_id}: {rec.get('status')}")
            summary.append(rec)
            continue
        print(f"[run] {cell_id}")
        rec = run_cell(arch, shape, mp, args.out, overrides, args.tag)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec.get("status")
        extra = ""
        if status == "ok":
            ha = rec["hlo_analysis"]
            extra = (
                f" flops/dev={ha['flops']:.3e} bytes/dev={ha['bytes_accessed']:.3e}"
                f" coll/dev={ha['collective_bytes']:.3e}"
                f" compile={rec['t_compile_s']}s"
            )
        print(f"[done] {cell_id}: {status}{extra}")
        summary.append(rec)

    n_ok = sum(1 for r in summary if r.get("status") == "ok")
    n_skip = sum(1 for r in summary if r.get("status") == "skipped")
    n_err = sum(1 for r in summary if r.get("status") == "error")
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    for r in summary:
        if r.get("status") == "error":
            print(f"  ERROR {r['cell']}: {r['error']}")


if __name__ == "__main__":
    main()
