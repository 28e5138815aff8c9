"""Logical-axis sharding rules with divisibility-checked fallback.

Model code annotates parameters and activations with *logical* axis names
("embed", "heads", "ffn", "vocab", "experts", "batch", "seq", ...). A rule
table maps logical axes to physical mesh axes; ``logical_to_physical`` drops
any mapping whose dimension size does not divide the mesh axis size (e.g.
yi-6b's 4 KV heads on a model=16 axis -> replicated), so every config lowers
on every mesh without hand-tuning.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default rule table for the production meshes (data, model) / (pod, data, model).
# Batch-like axes shard over data(+pod); weight axes shard over model.
DEFAULT_RULES: Dict[str, Union[str, Tuple[str, ...], None]] = {
    "batch": ("pod", "data"),
    "pairs": ("pod", "data"),
    "workers": ("pod", "data"),
    "seq": None,
    # sequence-parallel residual: the inter-layer activation is sharded over
    # the model axis between blocks (Megatron-SP style) so deep stacks don't
    # hold O(layers * B * T * d) replicated residuals under remat
    "seq_sp": "model",
    # decode KV caches: shard the cache sequence dim over model when KV heads
    # don't divide the model axis (flash-decoding style partial softmax)
    "cache_seq": "model",
    # FSDP: weight embed dims shard over the data axis (ZeRO-3 style); XLA
    # all-gathers per layer and reduce-scatters gradients
    "embed": "data",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "expert_ffn": None,
    "heads_flat": "model",  # fused (H*Dh) output dims (rwkv r/k/v/g mats)
    "embed2": None,
    "proj": "model",        # DML: k rows of L
    "feat": None,           # DML: d columns of L
    "gallery": ("pod", "data"),  # serve: pre-projected gallery rows
    "neighbors": None,      # serve: per-query top-k result dim
    "state": None,          # SSM state dim
    "conv": None,
    "layers": None,         # scan-over-layers leading axis
}


def _mesh_axis_size(mesh: Mesh, axis: Union[str, Tuple[str, ...]]) -> int:
    if isinstance(axis, str):
        return mesh.shape[axis]
    n = 1
    for a in axis:
        n *= mesh.shape[a]
    return n


def logical_to_physical(logical: Sequence[Optional[str]], mesh: Mesh,
                        rules: Optional[dict] = None,
                        shape: Optional[Sequence[int]] = None) -> P:
    """Map logical axis names to a PartitionSpec, dropping non-dividing axes.

    Args:
      logical: one logical name (or None) per tensor dimension.
      mesh: target mesh; mappings to axes absent from the mesh are dropped.
      rules: overrides of DEFAULT_RULES.
      shape: if given, a mapping is kept only when shape[i] divides the mesh
        axis size (replicate otherwise).
    """
    table = dict(DEFAULT_RULES)
    if rules:
        table.update(rules)
    used = set()
    spec = []
    for i, name in enumerate(logical):
        phys = table.get(name) if name is not None else None
        if phys is None:
            spec.append(None)
            continue
        axes = (phys,) if isinstance(phys, str) else tuple(phys)
        axes = tuple(a for a in axes if a in mesh.shape and a not in used)
        if not axes:
            spec.append(None)
            continue
        if shape is not None:
            size = _mesh_axis_size(mesh, axes)
            if shape[i] % size != 0:
                # try single-axis fallback before replicating entirely
                axes = tuple(a for a in axes if shape[i] % mesh.shape[a] == 0)
                axes = axes[:1]
                if not axes:
                    spec.append(None)
                    continue
        used.update(axes)
        spec.append(axes[0] if len(axes) == 1 else axes)
    return P(*spec)


def shardable(x: jax.Array, logical: Sequence[Optional[str]]):
    """Tag helper used by model code: returns (x, logical) pairs for tables."""
    return x, tuple(logical)


def make_param_shardings(logical_tree, mesh: Mesh, shapes_tree=None,
                         rules: Optional[dict] = None):
    """Map a pytree of logical-axis tuples (+ optional matching shapes) to
    NamedShardings."""
    if shapes_tree is None:
        return jax.tree.map(
            lambda lg: NamedSharding(mesh, logical_to_physical(lg, mesh, rules)),
            logical_tree, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.map(
        lambda lg, shp: NamedSharding(
            mesh, logical_to_physical(lg, mesh, rules, shape=shp)),
        logical_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple))


def constrain(x: jax.Array, logical: Sequence[Optional[str]],
              mesh: Optional[Mesh] = None, rules: Optional[dict] = None):
    """with_sharding_constraint by logical names. No-op outside a mesh ctx."""
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = logical_to_physical(logical, mesh, rules, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh() -> Optional[Mesh]:
    try:
        from jax._src.mesh import thread_resources
        m = thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None
