"""Roofline HLO analyzer: validated against analytically-known programs."""

import subprocess
import sys
import textwrap


def test_analyzer_counts_scan_trip_counts():
    """A 10-layer scan of known matmuls on 8 fake devices: analyzer flops
    must match the analytic per-device count within 5% (XLA's own
    cost_analysis undercounts ~10x here). Runs in a subprocess so the fake
    device count never leaks into this test session."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_analysis import analyze_hlo

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        def body(x, w):
            def layer(h, wl):
                h = jnp.tanh(h @ wl)
                h = jax.lax.with_sharding_constraint(
                    h, NamedSharding(mesh, P("data", None, "model")))
                return h, None
            x, _ = jax.lax.scan(layer, x, w)
            return x.sum()

        B, S, D, L = 8, 16, 256, 10
        x = jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((L, D, D), jnp.bfloat16)
        with mesh:
            f = jax.jit(body, in_shardings=(
                NamedSharding(mesh, P("data", None, "model")),
                NamedSharding(mesh, P(None, None, "model"))))
            c = f.lower(x, w).compile()
        res = analyze_hlo(c.as_text())
        expected = 2 * (B//2) * S * D * (D//4) * L
        ratio = res["flops"] / expected
        assert 0.95 < ratio < 1.10, (res["flops"], expected)
        assert res["collective_counts"].get("all-gather", 0) >= L
        assert res["unknown_trip_loops"] == 0
        print("OK", ratio)
        """
    )
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_analyzer_on_plain_text():
    from repro.launch.hlo_analysis import analyze_hlo

    hlo = """
HloModule test

ENTRY %main (p0: f32[128,256], p1: f32[256,64]) -> f32[128,64] {
  %p0 = f32[128,256]{1,0} parameter(0)
  %p1 = f32[256,64]{1,0} parameter(1)
  ROOT %dot.1 = f32[128,64]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    res = analyze_hlo(hlo)
    assert res["flops"] == 2 * 128 * 256 * 64
    # bytes: read both operands + write output
    assert res["bytes_accessed"] == 4 * (128 * 256 + 256 * 64 + 128 * 64)


def test_dryrun_artifacts_are_complete():
    """The committed dry-run results must cover every runnable cell on both
    production meshes (deliverable e) with zero errors."""
    import glob
    import json
    import os

    recs = []
    for f in glob.glob("results/dryrun/*.json"):
        with open(f) as fh:
            recs.append(json.load(fh))
    if not recs:
        import pytest

        pytest.skip("no dry-run artifacts present")
    from repro.configs.registry import ASSIGNED

    base = [
        r
        for r in recs
        if r.get("tag", "baseline") == "baseline" and r.get("arch") in ASSIGNED
    ]
    by_status = {}
    for r in base:
        by_status.setdefault(r["status"], []).append(r["cell"])
    assert not by_status.get("error"), by_status.get("error")
    # 10 archs x 4 shapes x 2 meshes = 80; 8 archs skip long_500k on each mesh
    assert len(by_status.get("ok", [])) >= 64
    assert len(by_status.get("skipped", [])) == 16
