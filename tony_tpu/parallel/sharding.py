"""Logical-axis → mesh-axis sharding rules.

Models annotate parameters with *logical* axis names ("vocab", "embed",
"heads", ...); rules map those to mesh axes. This is the scaling-book /
flax-partitioning recipe done minimally: pick a mesh, annotate shardings,
let XLA insert the collectives.

Default rules implement combined FSDP + tensor parallelism for transformer
blocks: weights shard their output-feature dim on tp and their input dim on
fsdp, so forward all-gathers ride the fsdp axis while matmul partials
reduce-scatter on tp — the standard Megatron/FSDP hybrid, expressed purely
as PartitionSpecs.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# (logical axis, mesh axis | tuple of mesh axes | None). First match wins;
# None = replicate. Tuples shard one logical dim over several mesh axes
# jointly (batch over dp AND fsdp — the standard FSDP batch layout).
DEFAULT_RULES: tuple[tuple[str, Any], ...] = (
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    ("vocab", "tp"),
    ("embed", "fsdp"),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("head_dim", None),
    ("mlp", "tp"),
    ("expert", "ep"),
    # at-rest layer stacks shard their leading dim over pp, so params +
    # optimizer state stop being pp-replicated (26 -> 9 GiB/chip at 8B
    # on pp=4 x fsdp=4, tools/aot_8b_result.json). For the plain (v=1)
    # schedule the staged constrain is then a LOCAL reshape; the
    # interleaved schedule's round-robin chunk layout instead costs one
    # cross-pp weight reshuffle per step (~ms over ICI vs a seconds-long
    # 8B step — and still strictly better than pp-replicated state).
    # pp=1 meshes unaffected.
    ("layers", "pp"),
    ("stage", "pp"),
    ("norm", None),
)


def logical_to_mesh_axes(logical_axes: Sequence[Optional[str]],
                        rules=DEFAULT_RULES,
                        mesh=None) -> P:
    """('vocab','embed') -> PartitionSpec('tp','fsdp'). Axes mapped to mesh
    axes absent from `mesh` stay replicated, so the same model code runs on
    any mesh shape. `mesh` may be a Mesh or AbstractMesh."""
    rule_map = dict(rules)
    available = set(mesh.axis_names) if mesh is not None else None

    def resolve(mesh_ax):
        if mesh_ax is None:
            return None
        if isinstance(mesh_ax, tuple):
            kept = tuple(a for a in mesh_ax
                         if available is None or a in available)
            return kept if kept else None
        if available is not None and mesh_ax not in available:
            return None
        return mesh_ax

    spec = []
    used: set = set()
    for ax in logical_axes:
        mesh_ax = resolve(rule_map.get(ax)) if ax is not None else None
        # a mesh axis may shard at most one tensor dim: first dim wins,
        # later dims fall back to replication (e.g. activations carrying
        # both a batch dim on fsdp and an embed dim whose rule is fsdp)
        if isinstance(mesh_ax, tuple):
            mesh_ax = tuple(a for a in mesh_ax if a not in used) or None
            if mesh_ax is not None:
                used.update(mesh_ax)
        elif mesh_ax is not None:
            if mesh_ax in used:
                mesh_ax = None
            else:
                used.add(mesh_ax)
        spec.append(mesh_ax)
    # drop trailing Nones for canonical specs
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def constrain(x, logical_axes: Sequence[Optional[str]],
              rules=DEFAULT_RULES):
    """with_sharding_constraint against the ambient (set_mesh) mesh; no-op
    when no mesh is active so model code is mesh-agnostic. Axes the ambient
    context holds Manually (inside shard_map) are dropped from the spec —
    with_sharding_constraint may only reference Auto axes there."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    manual = mesh.manual_axes
    spec = logical_to_mesh_axes(logical_axes, rules, mesh)
    if manual:
        cleaned = []
        for entry in spec:
            if isinstance(entry, tuple):
                kept = tuple(a for a in entry if a not in manual)
                cleaned.append(kept if kept else None)
            else:
                cleaned.append(None if entry in manual else entry)
        while cleaned and cleaned[-1] is None:
            cleaned.pop()
        if not any(cleaned):
            return x
        spec = P(*cleaned)
    return jax.lax.with_sharding_constraint(x, spec)


def make_partition_spec(logical_tree: Any, rules=DEFAULT_RULES,
                        mesh: Optional[Mesh] = None) -> Any:
    """Map a pytree of logical-axes tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical_to_mesh_axes(axes, rules, mesh),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def opt_state_specs(opt_state_tree: Any, param_specs: Any) -> Any:
    """PartitionSpecs for an optax state tree, derived structurally from
    the params' specs: any state leaf whose key-path SUFFIX matches a
    parameter's path (f32 masters, Adam mu/nu — optax state mirrors the
    param treedef) gets that parameter's spec; everything else (step
    counts, scalars) replicates.

    Why explicit specs instead of relying on jit propagation: XLA's
    sharding propagation is free to leave `optimizer.init` outputs
    replicated (observed on the v5p-32 AOT compile, tools/aot_8b.py —
    the Adam moments came out replicated, 64 GB/chip at 8B where the
    sharded plan needs 4 GB). At 8B this is the difference between
    fitting and OOM, so the trainer pins init's out_shardings with
    these."""
    from jax.tree_util import (
        tree_flatten_with_path, tree_unflatten,
    )

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    spec_leaves, _ = tree_flatten_with_path(param_specs, is_leaf=is_spec)
    by_path = {tuple(str(k) for k in path): spec
               for path, spec in spec_leaves}
    leaves, treedef = tree_flatten_with_path(opt_state_tree)
    out = []
    for path, leaf in leaves:
        keys = tuple(str(k) for k in path)
        spec = P()
        for i in range(len(keys)):
            cand = by_path.get(keys[i:])
            if cand is not None and len(cand) <= getattr(
                    leaf, "ndim", len(getattr(leaf, "shape", ()))):
                spec = cand
                break
        out.append(spec)
    return tree_unflatten(treedef, out)


def shard_pytree(tree: Any, logical_tree: Any, mesh: Mesh,
                 rules=DEFAULT_RULES) -> Any:
    """Device-put a pytree of arrays with NamedShardings derived from its
    logical axes."""
    specs = make_partition_spec(logical_tree, rules, mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)
