"""Production meshes + FL node-axis helpers.

TPU v5e target: 256 chips/pod. Single-pod mesh (16, 16) over
("data", "model"): 16 FL nodes x 16-way tensor parallel. Multi-pod
(2, 16, 16) over ("pod", "data", "model"): 32 FL nodes on a 2 x 16 node
torus whose inter-pod edges ride DCI.

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before any initialization).
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_production_mesh",
    "make_test_mesh",
    "node_axes",
    "model_axis",
    "n_fl_nodes",
    "n_model_shards",
]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape: Tuple[int, ...] = (2, 2, 2)) -> Mesh:
    """Small mesh for CPU tests (requires XLA host-device override)."""
    axes = ("pod", "data", "model")[-len(shape) :] if len(shape) < 3 else ("pod", "data", "model")
    if len(shape) == 2:
        axes = ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def node_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes enumerating FL nodes (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def model_axis(mesh: Mesh):
    """The tensor/FSDP shard axis name, or None on node-only meshes.
    Engines that accept ``model_axis=`` shard each node's flat parameter
    buffer across it (the two-axis ``(gossip_node, model_shard)`` round:
    gossip collectives stay on the node axes; the model axis only tiles
    the columns)."""
    return "model" if "model" in mesh.axis_names else None


def n_fl_nodes(mesh: Mesh) -> int:
    n = 1
    for a in node_axes(mesh):
        n *= mesh.shape[a]
    return n


def n_model_shards(mesh: Mesh) -> int:
    """Size of the 'model' axis (1 when the mesh has none)."""
    return int(mesh.shape["model"]) if "model" in mesh.axis_names else 1
