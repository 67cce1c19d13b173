"""Training launcher: ``python -m repro.launch.train --arch olmo-1b --smoke``.

Single-process (CPU/dev) path runs for real; on a pod the same script is
launched per host after ``jax.distributed.initialize()`` (the mesh and
shardings are host-count agnostic). Supports checkpoint restart (resumes
params/opt/data state) and heartbeat-file liveness for the watchdog.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="1x1", help="DxM, e.g. 2x4 (fake devices)")
    ap.add_argument("--heartbeat-file", default=None)
    args = ap.parse_args()

    d, m = (int(x) for x in args.mesh.split("x"))
    if d * m > 1:
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={d*m}"
        )

    import jax

    from repro.configs.base import RuntimeConfig
    from repro.configs.registry import get_config, reduced_config
    from repro.data.pipeline import DataConfig, make_dataset
    from repro.distributed.sharding import AxisRules
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import Model
    from repro.training import optimizer as opt_lib
    from repro.training.optimizer import OptimizerConfig
    from repro.training.train_loop import TrainLoopConfig, run_train_loop

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.smoke else get_config(args.arch)
    rules = None
    if d * m > 1:
        mesh = jax.make_mesh(
            (d, m), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
        )
        rules = AxisRules.create(mesh)
    runtime = RuntimeConfig(
        remat="full", attn_chunk_q=64, attn_chunk_kv=64, moe_dispatch="einsum"
    )
    model = Model(cfg, runtime, rules)
    opt_cfg = OptimizerConfig(peak_lr=args.lr, warmup_steps=5, total_steps=args.steps)

    data = make_dataset(
        DataConfig(
            seq_len=args.seq_len,
            global_batch=args.batch,
            vocab_size=cfg.vocab_size,
            dp_size=1,
        )
    )

    params = opt_state = None
    start_step = 0
    if args.resume and args.checkpoint_dir:
        from repro.checkpoint.checkpointer import Checkpointer

        ck = Checkpointer(args.checkpoint_dir)
        step = ck.latest_step()
        if step is not None:
            params0 = model.init(jax.random.key(0))
            opt0 = opt_lib.init_opt_state(opt_cfg, params0)
            tree = ck.restore(step, {"params": params0, "opt_state": opt0})
            params, opt_state = tree["params"], tree["opt_state"]
            data.load_state_dict(ck.load_extra(step).get("data_state", {}))
            start_step = step
            print(f"resumed from step {step}")

    hb = args.heartbeat_file

    def on_metrics(step, metrics):
        print(json.dumps({"step": step, **metrics}))
        if hb:
            with open(hb, "w") as f:
                f.write(f"{time.time()} {step}")

    ctx = rules.mesh if rules is not None else _nullcontext()
    with ctx:
        run_train_loop(
            model,
            opt_cfg,
            TrainLoopConfig(
                steps=args.steps,
                log_every=5,
                checkpoint_every=args.checkpoint_every,
                checkpoint_dir=args.checkpoint_dir,
            ),
            iter(data),
            params=params,
            opt_state=opt_state,
            start_step=start_step,
            on_metrics=on_metrics,
        )


class _nullcontext:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
