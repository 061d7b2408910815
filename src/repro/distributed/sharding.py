"""PartitionSpec rules for params, optimizer state and activations.

Default parameter rule (FSDP × TP, the 1000+-node-friendly layout):
  * last dim        -> "model"            (tensor parallel)
  * second-to-last  -> ("pod", "data")    (fully-sharded data parallel;
                                           "pod" only on multi-pod meshes)
  * leading stack/expert axes -> replicated (scanned layer axis) unless
    the axis divides the model axis exactly and the tensor is an MoE
    expert stack (expert parallelism is explored in §Perf instead).
A dim is sharded only when its size divides the mesh-axis size — any
remainder falls back to replication for that dim (never a compile
failure, at worst a wider collective recorded by the roofline pass).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "auto_axes",
    "named",
    "replicated",
    "param_sharding_rule",
    "tree_param_shardings",
    "tree_replicated",
    "axis_size",
    "data_axes",
]


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return int(mesh.shape[axes])
    return int(np.prod([mesh.shape[a] for a in axes]))


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis ``Auto``.

    ``jax.make_mesh`` builds ``Explicit`` axes, under which an eager
    slice of a sharded array and ``with_sharding_constraint`` refuse to
    guess a layout.  The plane and the launch cells place and slice
    their arrays themselves, so they run on the ``Auto`` form of
    whatever mesh the caller built."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_axes(mesh: Mesh) -> tuple:
    """The mesh axes that carry data parallelism, as a tuple usable both
    as a PartitionSpec entry and with :func:`axis_size`.  The single
    definition of "which axes shard the batch/database" — the step
    builders, the index plane, and the param rule all derive from here
    instead of re-spelling the pod special case."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


_dp_axes = data_axes  # back-compat spelling (pre-index-plane callers)


def param_sharding_rule(mesh: Mesh, shape: Sequence[int]) -> NamedSharding:
    """The default FSDP×TP rule described in the module docstring."""
    ndim = len(shape)
    spec: list = [None] * ndim
    dp = data_axes(mesh)
    if ndim >= 1 and shape[-1] % axis_size(mesh, "model") == 0 and shape[-1] >= axis_size(mesh, "model"):
        # 1-D tensors stay replicated (tiny norms/biases)
        if ndim >= 2:
            spec[-1] = "model"
    if ndim >= 2:
        dp_size = axis_size(mesh, dp)
        if shape[-2] % dp_size == 0 and shape[-2] >= dp_size:
            spec[-2] = dp if len(dp) > 1 else dp[0]
    return NamedSharding(mesh, P(*spec))


def tree_param_shardings(mesh: Mesh, abstract_params: Any):
    """Map the rule over an eval_shape'd param pytree."""
    return jax.tree_util.tree_map(
        lambda leaf: param_sharding_rule(mesh, leaf.shape), abstract_params
    )


def tree_replicated(mesh: Mesh, abstract_tree: Any):
    return jax.tree_util.tree_map(lambda _: replicated(mesh), abstract_tree)
