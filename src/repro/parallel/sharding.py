"""Logical-axis sharding rules (DP / FSDP / TP / EP / vocab-parallel).

Every parameter is initialized together with a tuple of *logical* axis
names (repro.models.* return ``(params, specs)`` trees). This module
maps logical names -> mesh axes for a given (config, mesh) pair and
produces jax.sharding.NamedSharding trees for pjit, plus activation
constraint helpers used inside the model code.

Mesh axes (launch/mesh.py): ("pod", "data", "model") multi-pod or
("data", "model") single-pod.

Rules:
  batch        -> (pod, data)            data parallel
  vocab        -> model                  vocab-parallel embed / lm head
  heads, kv_heads, q_dim, kv_dim, mlp, ssm_inner -> model   (TP)
  experts      -> model                  expert parallel
  embed        -> data when cfg.fsdp     (ZeRO-3-style param sharding;
                                          XLA inserts the all-gathers)
  layers, seq, * -> None

Divisibility is checked per-arch: a logical axis whose dim does not
divide the mesh axis falls back to replication (recorded, so DESIGN.md
can note e.g. kv_heads=8 < model=16 -> replicated KV).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class ShardingRules:
    mesh: Mesh
    rules: dict[str, Any]                  # logical name -> mesh axis/axes
    fallbacks: list[tuple[str, int, int]] = dataclasses.field(
        default_factory=list)              # (axis, dim, mesh_size) replaced

    def axis_size(self, mesh_axes) -> int:
        if mesh_axes is None:
            return 1
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        n = 1
        for a in mesh_axes:
            n *= self.mesh.shape[a]
        return n

    def spec_for(self, axes: tuple[str | None, ...],
                 shape: tuple[int, ...] | None = None) -> P:
        out = []
        used: set[str] = set()
        for i, name in enumerate(axes):
            mesh_axes = self.rules.get(name) if name else None
            if mesh_axes is not None and shape is not None:
                size = self.axis_size(mesh_axes)
                if shape[i] % size != 0:
                    self.fallbacks.append((name, shape[i], size))
                    mesh_axes = None
            if mesh_axes is not None:
                # one positional dim per mesh axis: first logical axis
                # wins (e.g. MoE experts -> EP; the expert-internal mlp
                # dim stays unsharded)
                flat = ((mesh_axes,) if isinstance(mesh_axes, str)
                        else tuple(mesh_axes))
                if any(a in used for a in flat):
                    mesh_axes = None
                else:
                    used.update(flat)
            out.append(mesh_axes)
        return P(*out)

    def sharding_for(self, axes, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(axes, shape))


def make_rules(cfg, mesh: Mesh) -> ShardingRules:
    """Build the logical->mesh mapping for one architecture."""
    axes = dict(mesh.shape)
    dp = tuple(a for a in ("pod", "data") if a in axes)
    tp = "model" if "model" in axes else None
    rules = {
        "batch": dp if len(dp) > 1 else (dp[0] if dp else None),
        "seq": None,
        "embed": ("data" if (cfg is not None and getattr(cfg, "fsdp", False)
                             and "data" in axes) else None),
        "embed_act": None,
        "vocab": tp,
        "q_dim": tp,
        "kv_dim": tp,
        "heads": tp,
        "kv_heads": tp,
        "mlp": tp,
        "experts": tp,
        "ssm_inner": tp,
        "ssm_heads": tp,
        "conv_dim": tp,
        "layers": None,
        "ssm_state": None,
        "head_dim": None,
        "capacity": None,
    }
    # Uneven-head attention (llama4 heads=40 on a 16-way model axis):
    # GSPMD partially replicates heads and all-reduces f32 score tensors
    # (~30 GiB/block). Two explicit remedies were measured and REFUTED
    # (EXPERIMENTS.md §Perf): context-parallel q-seq sharding (93 s
    # collective) and attention-DP over data x model (1546 s) — both
    # lose to XLA's own partial-replication schedule via boundary
    # reshards. batch_attn therefore aliases the plain batch rule; the
    # durable fix is deployment-level (TP sub-groups of 8, or
    # head-padded serving configs), recorded in the §Perf log.
    rules["seq_ctx"] = None
    rules["batch_attn"] = rules["batch"]
    return ShardingRules(mesh, rules)


def params_shardings(rules: ShardingRules, params, specs):
    """NamedSharding tree matching the params tree."""
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_s = treedef.flatten_up_to(specs)
    out = [rules.sharding_for(s, np.shape(p)) for p, s in
           zip(flat_p, flat_s)]
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(params):
    """ShapeDtypeStruct tree (for .lower without allocation)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), params)


# ---------------------------------------------------------------------------
# Activation constraints inside model code (no-op without a context)
# ---------------------------------------------------------------------------

_ACTIVE_RULES: list[ShardingRules] = []


class use_rules:
    """Context manager activating sharding constraints in model code."""

    def __init__(self, rules: ShardingRules):
        self.rules = rules

    def __enter__(self):
        _ACTIVE_RULES.append(self.rules)
        return self.rules

    def __exit__(self, *exc):
        _ACTIVE_RULES.pop()


def constrain(x: jax.Array, *axes: str | None) -> jax.Array:
    """with_sharding_constraint against the active logical rules."""
    if not _ACTIVE_RULES:
        return x
    rules = _ACTIVE_RULES[-1]
    if len(axes) != x.ndim:
        raise ValueError(f"constrain: {len(axes)} axes for rank {x.ndim}")
    spec = rules.spec_for(tuple(axes), tuple(x.shape))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))
