"""Gossip (mixing) backends: the communication primitive of decentralized FL.

The paper's algorithms interleave local SGD/GT steps with a *mixing* step

    theta_i <- sum_{j in N_i} W_ij theta_j

over the node graph. This module provides three interchangeable backends
operating on **node-stacked pytrees** (every leaf has a leading ``nodes``
axis):

1. ``make_dense_gossip(w)`` -- simulated: ``theta' = W @ Theta`` as ONE
   matmul over the flat-packed state. Works on a single device (CPU-scale
   runs, the EHR reproduction, and the oracle for equivalence tests).
   Supports ANY mixing matrix.

2. ``make_mesh_gossip(mesh, node_axes, specs)`` -- TPU-native: a
   ``shard_map`` over the node mesh axes implementing the ring/torus
   circulant W with ``jax.lax.ppermute`` -- nearest-neighbor ICI transfers,
   the cheapest collective on a TPU torus. The local shards are packed
   into ONE contiguous payload, so a round issues exactly one ppermute per
   graph direction **total** (independent of leaf count); the ``model``-axis
   shards of each leaf pass through untouched because mixing is elementwise
   across nodes.

3. ``make_allgather_gossip(mesh, node_axes, specs, w)`` -- TPU fallback for
   ARBITRARY graphs: ONE all-gather of the packed node payload over the
   node axes, contracted with the W row. O(N x) more collective bytes than
   ppermute gossip -- kept for generality and as the roofline
   counter-example.

**Flat-buffer engine.** All backends route through ``core.packing``: the
node-stacked pytree is collapsed into a single ``(nodes, total_params)``
buffer (pack/unpack are reshape+concat/slice copies), turning
a round from O(n_leaves) collectives/matmuls into O(1). The historical
leaf-by-leaf implementations are kept as ``*_per_leaf`` references -- the
equivalence oracles and the benchmark baseline (``benchmarks/
gossip_bench.py`` measures the speedup; ``tests/test_gossip_flat.py``
property-tests flat == per-leaf).

Wire-byte accounting: a full-precision flat round moves ``total_params *
itemsize(wire_dtype)`` bytes per direction per node; see
``core.compression`` / ``core.packing.flat_wire_bytes`` for the int8 path.

All backends support a ``wire_dtype`` (e.g. ``jnp.bfloat16``): payloads are
rounded to the wire dtype before communication and the weighted sum is
accumulated in fp32. This is the beyond-paper "bf16 gossip" optimization
(halves the collective term); ``wire_dtype=None`` is the paper-faithful
full-precision wire.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.packing import pack, unpack

# Replication of the shard_map outputs cannot be inferred through the
# pack (concat/slice) ops and the hand-rolled ppermute mixes, so the
# static varying-manual-axes check is off; every out_spec names the node
# axes it varies over explicitly.
_shard_map = functools.partial(jax.shard_map, check_vma=False)

PyTree = Any
GossipFn = Callable[[PyTree], PyTree]
FlatMixFn = Callable[[jnp.ndarray], jnp.ndarray]

__all__ = [
    "make_dense_gossip",
    "make_dense_flat_mix",
    "make_dense_gossip_per_leaf",
    "make_mesh_gossip",
    "make_mesh_flat_mix",
    "make_mesh_gossip_per_leaf",
    "make_allgather_gossip",
    "make_allgather_gossip_per_leaf",
    "make_mean_consensus",
    "mesh_gossip_directions",
    "mesh_gossip_dense_equivalent",
]


def _wire(x: jnp.ndarray, wire_dtype) -> jnp.ndarray:
    """Round a payload to the wire dtype (simulating the comm precision)."""
    if wire_dtype is None:
        return x
    return x.astype(wire_dtype).astype(x.dtype)


def _split_w(w: np.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(diag, off-diagonal) of W as fp32 device constants."""
    w = np.asarray(w, dtype=np.float64)
    w_self = jnp.asarray(np.diag(w), dtype=jnp.float32)
    w_off = jnp.asarray(w - np.diag(np.diag(w)), dtype=jnp.float32)
    return w_self, w_off


# ---------------------------------------------------------------------------
# 1. Dense-W simulated backend (any graph, any device count)
# ---------------------------------------------------------------------------


def make_dense_flat_mix(w: np.ndarray, wire_dtype=None) -> FlatMixFn:
    """Flat-native dense mixing: ONE ``W @ Theta`` matmul on the packed
    ``(nodes, total)`` buffer.

    The diagonal (self) term is kept at full precision; only off-diagonal
    contributions pass through the wire dtype, mirroring what a real
    transport would quantize.
    """
    w_self, w_off = _split_w(w)
    n = w_self.shape[0]

    def mix(flat: jnp.ndarray) -> jnp.ndarray:
        if flat.ndim != 2 or flat.shape[0] != n:
            raise ValueError(f"flat buffer {flat.shape} != ({n}, total)")
        xf = flat.astype(jnp.float32)
        sent = _wire(xf, wire_dtype)
        return (w_off @ sent + w_self[:, None] * xf).astype(flat.dtype)

    return mix


def make_dense_gossip(w: np.ndarray, wire_dtype=None) -> GossipFn:
    """theta' = W @ Theta over the leading node axis of every leaf.

    Packs the pytree into one ``(nodes, total)`` buffer and issues a single
    matmul regardless of leaf count (the per-leaf path is
    :func:`make_dense_gossip_per_leaf`)."""
    mix = make_dense_flat_mix(w, wire_dtype)

    def gossip(tree: PyTree) -> PyTree:
        flat, layout = pack(tree)
        return unpack(mix(flat), layout)

    return gossip


def make_dense_gossip_per_leaf(w: np.ndarray, wire_dtype=None) -> GossipFn:
    """Leaf-by-leaf reference implementation: one einsum per leaf per round.

    Kept as the equivalence oracle for the flat engine and the benchmark
    baseline; O(n_leaves) dispatches -- do not use on the hot path."""
    w_self, w_off = _split_w(w)
    n = w_self.shape[0]

    def mix_leaf(x: jnp.ndarray) -> jnp.ndarray:
        if x.shape[0] != n:
            raise ValueError(f"leaf leading axis {x.shape[0]} != n_nodes {n}")
        flat = x.reshape(n, -1)
        sent = _wire(flat, wire_dtype).astype(jnp.float32)
        mixed = w_off @ sent + w_self[:, None] * flat.astype(jnp.float32)
        return mixed.astype(x.dtype).reshape(x.shape)

    return lambda tree: jax.tree_util.tree_map(mix_leaf, tree)


def make_mean_consensus(n: int) -> GossipFn:
    """W = (1/N) 1 1^T: exact averaging. This is the fictitious fusion
    center / FedAvg-server mixing (and the limit of infinitely many gossip
    rounds)."""
    return make_dense_gossip(np.full((n, n), 1.0 / n))


# ---------------------------------------------------------------------------
# 2. Mesh (ring/torus) ppermute backend -- the TPU-native path
# ---------------------------------------------------------------------------


def mesh_gossip_directions(
    axis_sizes: Dict[str, int], self_weight: Optional[float] = None
) -> Tuple[float, Tuple[Tuple[str, int, float], ...]]:
    """Directions of the circulant torus W over the given node axes.

    Returns (w_self, ((axis_name, shift, weight), ...)). An axis of size 2
    contributes ONE direction (its +1 and -1 neighbors coincide); size 1
    axes contribute none; larger axes contribute +/-1.
    """
    dirs = []
    for name, size in axis_sizes.items():
        if size == 2:
            dirs.append((name, 1))
        elif size > 2:
            dirs.append((name, 1))
            dirs.append((name, -1))
    if not dirs:
        return 1.0, ()
    w_self = 1.0 / (len(dirs) + 1) if self_weight is None else float(self_weight)
    if not (0.0 < w_self <= 1.0):
        raise ValueError("self_weight must be in (0, 1]")
    share = (1.0 - w_self) / len(dirs)
    return w_self, tuple((name, shift, share) for name, shift in dirs)


def mesh_gossip_dense_equivalent(
    axis_sizes: Dict[str, int],
    self_weight: Optional[float] = None,
    axes_subset: Optional[Sequence[str]] = None,
) -> np.ndarray:
    """The dense W the ppermute backend realizes (row-major node order).

    Used as the oracle in sharded-vs-simulated equivalence tests, as the
    compile-time W of the fused engine's mesh build, and to check
    Assumption 1 for the production topology. ``axes_subset`` restricts
    the mixing directions to those axes (hierarchical gossip: the other
    axes contribute no edges, so e.g. ("data",) on a (pod, data) mesh
    yields the intra-pod block-diagonal W).
    """
    names = list(axis_sizes)
    sizes = [axis_sizes[k] for k in names]
    n = int(np.prod(sizes))
    active = dict(axis_sizes)
    if axes_subset is not None:
        for a in axes_subset:
            if a not in axis_sizes:
                raise ValueError(f"axes_subset {axes_subset} not in {names}")
        active = {a: axis_sizes[a] for a in axes_subset}
    w_self, dirs = mesh_gossip_directions(active, self_weight)
    w = np.eye(n) * w_self if dirs else np.eye(n)
    idx = np.arange(n).reshape(sizes)
    for name, shift, weight in dirs:
        ax = names.index(name)
        # receiving from the node `shift` positions back along axis `ax`
        src = np.roll(idx, shift, axis=ax).reshape(-1)
        for dst_node, src_node in enumerate(src.tolist()):
            w[dst_node, src_node] += weight
    return w


def _mesh_dirs(mesh, node_axes, axes_subset, self_weight):
    node_axes = tuple(node_axes)
    active = tuple(axes_subset) if axes_subset is not None else node_axes
    for a in active:
        if a not in node_axes:
            raise ValueError(f"axes_subset {active} not within node_axes {node_axes}")
    axis_sizes = {a: mesh.shape[a] for a in active}
    return mesh_gossip_directions(axis_sizes, self_weight)


def make_mesh_gossip(
    mesh: Mesh,
    node_axes: Sequence[str],
    specs: PyTree,
    self_weight: Optional[float] = None,
    wire_dtype=None,
    axes_subset: Optional[Sequence[str]] = None,
) -> GossipFn:
    """Ring/torus gossip via ppermute inside a shard_map.

    The local shards of every leaf are packed into ONE contiguous fp32
    buffer inside the shard_map body, so the compiled round contains
    exactly one ``collective-permute`` per torus direction no matter how
    many leaves the state has (asserted against the compiled HLO in
    tests/test_gossip_flat.py). With a narrow ``wire_dtype`` the ENTIRE
    neighbor path stays in that dtype -- payload, permute, weighting -- so
    no convert exists for XLA's simplifier to hoist across the permute
    (which would silently re-widen the wire); the self term and the final
    accumulation stay in fp32.

    Args:
      mesh: the device mesh (must contain every axis in ``specs``).
      node_axes: mesh axes enumerating FL nodes, e.g. ("data",) or
        ("pod", "data"). Every leaf's spec must shard its leading axis over
        exactly these (``P((*node_axes,), ...)``).
      specs: pytree of PartitionSpec matching the state pytree.
      self_weight: W_ii; default 1/(ndirs+1) (1/3 ring, 1/5 torus).
      wire_dtype: payload dtype on the wire (None = fp32).
      axes_subset: if given, gossip ONLY along these node axes (the others
        contribute no direction). This powers *hierarchical gossip*: mix
        over the cheap intra-pod "data" links every round and over the
        expensive inter-pod links less often.
    """
    w_self, dirs = _mesh_dirs(mesh, node_axes, axes_subset, self_weight)

    def body(tree: PyTree) -> PyTree:
        flat, layout = pack(tree)  # local shards -> one (local_nodes, T) buffer
        wire = wire_dtype or flat.dtype
        payload = flat.astype(wire)
        acc = flat.astype(jnp.float32) * w_self
        for axis_name, shift, weight in dirs:
            n = mesh.shape[axis_name]
            perm = [(i, (i + shift) % n) for i in range(n)]
            recv = jax.lax.ppermute(payload, axis_name, perm)
            acc = acc + (recv * jnp.asarray(weight, wire)).astype(jnp.float32)
        return unpack(acc, layout)

    sm = _shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs)
    return lambda tree: sm(tree)


def make_mesh_flat_mix(
    mesh: Mesh,
    node_axes: Sequence[str],
    self_weight: Optional[float] = None,
    wire_dtype=None,
    axes_subset: Optional[Sequence[str]] = None,
) -> FlatMixFn:
    """Flat-native ring/torus gossip: ppermute directly on the packed
    ``(nodes, total)`` buffer, sharded ``P(node_axes, None)``.

    The mesh counterpart of :func:`make_dense_flat_mix` for the flat
    engine (``make_fl_round(engine=FlatEngine(...))``): the state
    ALREADY lives flat, so the
    shard_map body skips the per-call pack/unpack of :func:`make_mesh_gossip`
    and is exactly one ppermute per torus direction. Same wire-dtype
    semantics as the tree backend (the whole neighbor path stays in
    ``wire_dtype``; self term and accumulation in fp32).
    """
    w_self, dirs = _mesh_dirs(mesh, node_axes, axes_subset, self_weight)
    spec = P(tuple(node_axes), None)

    def body(flat: jnp.ndarray) -> jnp.ndarray:
        wire = wire_dtype or flat.dtype
        payload = flat.astype(wire)
        acc = flat.astype(jnp.float32) * w_self
        for axis_name, shift, weight in dirs:
            n = mesh.shape[axis_name]
            perm = [(i, (i + shift) % n) for i in range(n)]
            recv = jax.lax.ppermute(payload, axis_name, perm)
            acc = acc + (recv * jnp.asarray(weight, wire)).astype(jnp.float32)
        return acc.astype(flat.dtype)

    sm = _shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec)
    return lambda flat: sm(flat)


def make_mesh_gossip_per_leaf(
    mesh: Mesh,
    node_axes: Sequence[str],
    specs: PyTree,
    self_weight: Optional[float] = None,
    wire_dtype=None,
    axes_subset: Optional[Sequence[str]] = None,
) -> GossipFn:
    """Leaf-by-leaf mesh gossip reference: one ppermute per direction PER
    LEAF. Equivalence oracle + the collective-count counter-example for
    the HLO dry-run test."""
    w_self, dirs = _mesh_dirs(mesh, node_axes, axes_subset, self_weight)

    def mix_leaf(x: jnp.ndarray) -> jnp.ndarray:
        wire = wire_dtype or x.dtype
        payload = x.astype(wire)
        acc = x.astype(jnp.float32) * w_self
        for axis_name, shift, weight in dirs:
            n = mesh.shape[axis_name]
            perm = [(i, (i + shift) % n) for i in range(n)]
            recv = jax.lax.ppermute(payload, axis_name, perm)
            acc = acc + (recv * jnp.asarray(weight, wire)).astype(jnp.float32)
        return acc.astype(x.dtype)

    def body(tree: PyTree) -> PyTree:
        return jax.tree_util.tree_map(mix_leaf, tree)

    sm = _shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs)
    return lambda tree: sm(tree)


# ---------------------------------------------------------------------------
# 3. All-gather backend for arbitrary graphs at scale
# ---------------------------------------------------------------------------


def _allgather_row(mesh, node_axes, wmat):
    """This shard's W row, via the flat node index (row-major node order)."""
    idx = 0
    for a in node_axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return jax.lax.dynamic_slice_in_dim(wmat, idx, 1, axis=0)[0]  # (n,)


def make_allgather_gossip(
    mesh: Mesh,
    node_axes: Sequence[str],
    specs: PyTree,
    w: np.ndarray,
    wire_dtype=None,
) -> GossipFn:
    """Arbitrary-W gossip: ONE all-gather of the packed node payload over
    the node axes, then contract with this node's W row. Collective bytes
    ~ N x the ppermute backend -- the price of a non-torus graph on a torus
    interconnect -- but still a single collective regardless of leaf count.
    """
    node_axes = tuple(node_axes)
    n = int(np.prod([mesh.shape[a] for a in node_axes]))
    if w.shape != (n, n):
        raise ValueError(f"W shape {w.shape} != ({n},{n})")
    w_rows = jnp.asarray(w, dtype=jnp.float32)  # (n, n), replicated

    def body(tree: PyTree, wmat: jnp.ndarray) -> PyTree:
        row = _allgather_row(mesh, node_axes, wmat)
        flat, layout = pack(tree)  # (1, T_local) node slice
        payload = flat[0] if wire_dtype is None else flat[0].astype(wire_dtype)
        full = jax.lax.all_gather(payload, node_axes, tiled=False).reshape(n, -1)
        mixed = row @ full.astype(jnp.float32)
        return unpack(mixed[None].astype(flat.dtype), layout)

    sm = _shard_map(
        body, mesh=mesh, in_specs=(specs, P(None, None)), out_specs=specs
    )
    return lambda tree: sm(tree, w_rows)


def make_allgather_gossip_per_leaf(
    mesh: Mesh,
    node_axes: Sequence[str],
    specs: PyTree,
    w: np.ndarray,
    wire_dtype=None,
) -> GossipFn:
    """Leaf-by-leaf all-gather gossip reference: one all-gather PER LEAF."""
    node_axes = tuple(node_axes)
    n = int(np.prod([mesh.shape[a] for a in node_axes]))
    if w.shape != (n, n):
        raise ValueError(f"W shape {w.shape} != ({n},{n})")
    w_rows = jnp.asarray(w, dtype=jnp.float32)

    def body(tree: PyTree, wmat: jnp.ndarray) -> PyTree:
        row = _allgather_row(mesh, node_axes, wmat)

        def mix_leaf(x: jnp.ndarray) -> jnp.ndarray:
            payload = x[0] if wire_dtype is None else x[0].astype(wire_dtype)
            full = jax.lax.all_gather(payload, node_axes, tiled=False).reshape(n, -1)
            mixed = row @ full.astype(jnp.float32)
            return mixed.astype(x.dtype).reshape(x.shape[1:])[None]

        return jax.tree_util.tree_map(mix_leaf, tree)

    sm = _shard_map(
        body, mesh=mesh, in_specs=(specs, P(None, None)), out_specs=specs
    )
    return lambda tree: sm(tree, w_rows)
