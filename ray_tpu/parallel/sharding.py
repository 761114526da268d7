"""Logical-axis sharding rules: map parameter/activation logical axes onto
mesh axes (the GSPMD recipe from the scaling playbook: annotate inputs +
params, let XLA insert collectives).

Net-new TPU-first design (no counterpart in the reference, which leaves
sharding to vLLM/torch — SURVEY §2.7 "TPU-rebuild note").
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.parallel.mesh import mesh_shape

# A rule maps a logical axis name to one mesh axis, a tuple of mesh axes, or
# None (replicate).
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# The standard transformer ruleset: batch over (data, fsdp); sequence over
# seq; embed sharded over fsdp for ZeRO; heads/mlp over tensor.
DEFAULT_RULES: Rules = {
    "batch": ("data", "fsdp"),
    "seq": "seq",
    "embed": None,
    "embed_fsdp": "fsdp",
    "vocab": "tensor",
    "heads": "tensor",
    "kv_heads": "tensor",
    "head_dim": None,
    "mlp": "tensor",
    "expert": "expert",
    "stage": "stage",
}


def spec_for(logical_axes: Sequence[Optional[str]], rules: Optional[Rules] = None,
             mesh: Optional[Mesh] = None) -> PartitionSpec:
    """PartitionSpec from logical axis names, dropping axes whose mesh size is
    1 (so one model definition runs on any mesh)."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    sizes = mesh_shape(mesh) if mesh is not None else None
    out = []
    for name in logical_axes:
        mapped = rules.get(name) if name is not None else None
        if mapped is None:
            out.append(None)
            continue
        axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        if sizes is not None:
            axes = tuple(a for a in axes if sizes.get(a, 1) > 1)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def sharding_for(mesh: Mesh, logical_axes: Sequence[Optional[str]],
                 rules: Optional[Rules] = None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(logical_axes, rules, mesh))


def spec_for_shape(logical_axes: Sequence[Optional[str]],
                   shape: Sequence[int], mesh: Mesh,
                   rules: Optional[Rules] = None) -> PartitionSpec:
    """spec_for, with any dimension a mapped mesh axis does not divide left
    replicated (e.g. 2 KV heads on tensor=4)."""
    return _drop_indivisible(spec_for(logical_axes, rules, mesh), shape, mesh)


# ---------------------------------------------------------------------------
# Path-pattern param sharding: model families declare regex → logical axes.
# ---------------------------------------------------------------------------
class ParamShardingRules:
    """Maps parameter tree paths (joined with '/') to logical axis tuples via
    ordered regex patterns; first match wins."""

    def __init__(self, patterns: Sequence[Tuple[str, Tuple[Optional[str], ...]]],
                 rules: Optional[Rules] = None):
        self._patterns = [(re.compile(p), axes) for p, axes in patterns]
        self._rules = rules

    def logical_axes(self, path: str, ndim: int) -> Tuple[Optional[str], ...]:
        for pattern, axes in self._patterns:
            if pattern.search(path):
                if len(axes) != ndim:
                    raise ValueError(
                        f"rule {pattern.pattern!r} has {len(axes)} axes but "
                        f"param {path} has ndim={ndim}")
                return axes
        return (None,) * ndim

    def tree_shardings(self, mesh: Mesh, params: Any) -> Any:
        """PyTree of NamedShardings matching `params` (works on shapes from
        jax.eval_shape too)."""

        def one(path, leaf):
            path_str = "/".join(_key_str(k) for k in path)
            axes = self.logical_axes(path_str, getattr(leaf, "ndim", 0))
            return NamedSharding(mesh, spec_for_shape(
                axes, getattr(leaf, "shape", ()), mesh, self._rules))

        return jax.tree_util.tree_map_with_path(one, params)


def _drop_indivisible(spec: PartitionSpec, shape: Sequence[int],
                      mesh: Mesh) -> PartitionSpec:
    """Replicate any dimension whose size a mapped mesh axis doesn't divide
    (e.g. 2 KV heads on tensor=4): sharding there would be an error, and
    replication is the correct degradation for small dims."""
    sizes = mesh_shape(mesh)
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        total = 1
        kept = []
        for a in axes:
            n = sizes.get(a, 1)
            if shape[i] % (total * n) == 0:
                kept.append(a)
                total *= n
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def _key_str(k: Any) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    if hasattr(k, "name"):
        return str(k.name)
    return str(k)


def shard_tree(tree: Any, shardings: Any) -> Any:
    """Device-put a pytree with the given shardings."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s), tree, shardings)
