"""Logical-axis sharding rules -> mesh PartitionSpecs.

Every parameter / activation in the model zoo is annotated with a tuple of
*logical* axis names.  ``AxisRules`` maps logical names to mesh axes for the
production meshes:

  single-pod  : (16, 16)      axes ("data", "model")
  multi-pod   : (2, 16, 16)   axes ("pod", "data", "model")

Weights are TP-sharded over ``model`` (heads / d_ff / vocab / experts) and
FSDP-sharded over ``data`` (+``pod`` in the multi-pod mesh) on the remaining
large dimension.  The ``pod`` axis is pure data parallelism for activations.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


MeshAxes = tuple[str, ...] | str | None


def _default_rules(multi_pod: bool) -> dict[str, MeshAxes]:
    fsdp: MeshAxes = ("pod", "data") if multi_pod else ("data",)
    batch: MeshAxes = ("pod", "data") if multi_pod else ("data",)
    return {
        # --- weight axes ---
        "embed": fsdp,  # d_model dim of weights (FSDP)
        "vocab": "model",
        "heads": "model",
        "kv_heads": None,  # replicated (GQA kv < TP degree)
        "kv_flat": "model",  # flattened (hkv*hd) KV projection columns
        "head_dim": None,
        "mlp": "model",
        "experts": "model",
        "expert_mlp": None,
        "ssm_inner": "model",  # d_inner / ssm heads
        "ssm_state": None,
        "conv_dim": None,
        "layers": None,  # stacked-scan leading dim
        "norm": None,
        # --- activation axes ---
        "batch": batch,
        "seq": None,
        "act_embed": None,  # d_model dim of activations
        "act_heads": "model",
        "act_mlp": "model",
        "act_vocab": "model",
        "kv_seq": "model",  # pool-interleaved KV sequence (Beluga O9)
        "kv_seq_long": ("data", "model"),  # long-context single-request decode
        "pool_blocks": "model",  # Beluga pool block interleaving
    }


@dataclass(frozen=True)
class AxisRules:
    mesh: Mesh
    rules: dict[str, MeshAxes]
    # Explicit row-parallel matmuls: shard_map + psum of bf16 partials.
    # Halves TP all-reduce bytes vs letting the partitioner reduce the f32
    # accumulator (measured 2x on every train cell) — Megatron-style
    # collective precision control.
    rowp_bf16: bool = False

    @classmethod
    def create(
        cls,
        mesh: Mesh,
        overrides: dict[str, MeshAxes] | None = None,
        rowp_bf16: bool = False,
    ) -> "AxisRules":
        multi_pod = "pod" in mesh.axis_names
        rules = _default_rules(multi_pod)
        if overrides:
            rules.update(overrides)
        return cls(mesh=mesh, rules=rules, rowp_bf16=rowp_bf16)

    # ------------------------------------------------------------------
    def spec(self, logical_axes: tuple[str | None, ...]) -> P:
        """PartitionSpec for a tuple of logical axis names."""
        out: list[MeshAxes] = []
        used: set[str] = set()
        for ax in logical_axes:
            if ax is None:
                out.append(None)
                continue
            if ax not in self.rules:
                raise KeyError(f"unknown logical axis {ax!r}")
            mesh_ax = self.rules[ax]
            # drop mesh axes already used by an earlier dim (illegal in a spec)
            if isinstance(mesh_ax, tuple):
                mesh_ax = tuple(m for m in mesh_ax if m not in used)
                mesh_ax = mesh_ax if mesh_ax else None
            elif mesh_ax in used:
                mesh_ax = None
            if mesh_ax is None:
                out.append(None)
            elif isinstance(mesh_ax, tuple):
                used.update(mesh_ax)
                out.append(mesh_ax)
            else:
                used.add(mesh_ax)
                out.append(mesh_ax)
        return P(*out)

    def sharding(self, logical_axes: tuple[str | None, ...]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes))

    def axis_size(self, name: str) -> int:
        return self.mesh.shape[name]

    @property
    def tp(self) -> int:
        return self.mesh.shape["model"]

    @property
    def dp(self) -> int:
        n = self.mesh.shape["data"]
        if "pod" in self.mesh.axis_names:
            n *= self.mesh.shape["pod"]
        return n


def constrain(x: jax.Array, rules: AxisRules, logical_axes: tuple) -> jax.Array:
    """with_sharding_constraint by logical axes (no-op without a mesh)."""
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(x, rules.sharding(logical_axes))


# ---------------------------------------------------------------------------
# Param-tree <-> spec-tree plumbing
# ---------------------------------------------------------------------------


class ParamSpec:
    """A leaf descriptor: shape + dtype + logical axes + init scale."""

    __slots__ = ("shape", "dtype", "logical_axes", "init", "scale")

    def __init__(self, shape, dtype, logical_axes, init="normal", scale=0.02):
        assert len(shape) == len(logical_axes), (shape, logical_axes)
        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype
        self.logical_axes = tuple(logical_axes)
        self.init = init
        self.scale = scale

    def __repr__(self):
        return f"ParamSpec({self.shape}, {self.dtype}, {self.logical_axes})"


def is_param_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def tree_specs(param_tree: Any, rules: AxisRules) -> Any:
    """Map a tree of ParamSpec leaves to PartitionSpecs."""
    return jax.tree.map(
        lambda p: rules.spec(p.logical_axes), param_tree, is_leaf=is_param_spec
    )


def tree_shardings(param_tree: Any, rules: AxisRules) -> Any:
    return jax.tree.map(
        lambda p: rules.sharding(p.logical_axes), param_tree, is_leaf=is_param_spec
    )


def tree_shape_dtype(param_tree: Any) -> Any:
    import jax.numpy as jnp

    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype)),
        param_tree,
        is_leaf=is_param_spec,
    )


def init_tree(param_tree: Any, key: jax.Array) -> Any:
    """Materialize parameters (smoke tests / examples only)."""
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(param_tree, is_leaf=is_param_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, spec in zip(keys, leaves):
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, spec.dtype)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, spec.dtype)
        elif spec.init == "normal":
            arr = (
                jax.random.normal(k, spec.shape, jnp.float32) * spec.scale
            ).astype(spec.dtype)
        elif spec.init == "ssm_a":  # A_log init: log of uniform [1, 16]
            u = jax.random.uniform(k, spec.shape, jnp.float32, 1.0, 16.0)
            arr = jnp.log(u).astype(spec.dtype)
        elif spec.init == "ssm_dt":  # dt_bias: softplus^-1(uniform[1e-3, 1e-1])
            u = jax.random.uniform(k, spec.shape, jnp.float32, 1e-3, 1e-1)
            arr = (u + jnp.log(-jnp.expm1(-u))).astype(spec.dtype)
        else:
            raise ValueError(spec.init)
        out.append(arr)
    return jax.tree.unflatten(treedef, out)
