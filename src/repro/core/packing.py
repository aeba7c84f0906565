"""Flat-buffer packing: the node-stacked pytree as ONE contiguous matrix.

Every gossip backend mixes along the leading ``nodes`` axis and treats the
rest of each leaf as an opaque payload. Traversing the pytree leaf-by-leaf
therefore pays per-leaf overhead (one einsum / one ppermute-per-direction /
one quantize pass *per leaf per round*) for no semantic gain. This module
collapses the state into a single ``(nodes, total_params)`` buffer plus a
static :class:`FlatLayout` record (per-leaf offset/shape/dtype), so a gossip
round becomes ONE matmul (dense W), ONE ppermute per torus direction (mesh
backend), or ONE all-gather (arbitrary W) -- independent of leaf count.

Layouts are static Python data (hashable, usable as a jit static argument);
``pack``/``unpack`` are reshapes + concatenate / slices, and the round trip
is lossless: each leaf is stored in its own dtype's bit-width inside a
common buffer dtype wide enough to hold it exactly (fp32 holds
bf16/fp16/fp32 losslessly).

Conversion goes through 128-lane rows where it can. The conversion is not
free: on a TPU the ``(n, total)`` buffer and a node-stacked leaf tile their
elements differently, so every conversion copies. Concatenating leaves on
the COLUMN axis (``leaf.reshape(n, -1)``) makes XLA relayout each leaf one
node at a time (a ``while`` loop over the nodes, each step padding a tile
into a zero-filled staging buffer) before a final concatenate: five or six
passes per leaf. A leaf whose offset and size are both multiples of
:data:`LANES` is instead viewed as ``(n, size // LANES, LANES)``, the rows
its tiles share with the buffer's; the pieces concatenate on the ROW axis
and the whole reshapes to ``(n, total)``, which XLA lowers to plain copies.
Runs of unaligned columns (a small leaf, the padding tail) are concatenated
on the column axis as before and join as whole rows where the run ends on a
row boundary; a layout with no aligned leaf (:attr:`FlatLayout.row_columns`
== 0), or whose ``total`` is not whole rows, converts as before. The
buffer's columns, and so every value in it, are the same either way.

Wire-byte accounting: a flat int8 payload costs ``total`` bytes +
4 bytes per (node, scale-chunk) for the scales -- see
:func:`flat_wire_bytes` and ``compression.compressed_wire_bytes`` for the
per-leaf equivalent.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

#: lanes of a TPU vector register: the row width the buffer's tiles and a
#: leaf's tiles share, and the unit of the row-path conversion
LANES = 128

__all__ = [
    "FlatLayout",
    "pack",
    "pack_layout",
    "pack_like",
    "unpack",
    "flat_wire_bytes",
    "flat_wire_bytes_per_shard",
    "scoped_layout",
    "compact_pos_dtype",
    "compact_index_bytes",
    "bitmap_bytes_per_chunk",
]


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    offset: int  # column offset into the flat buffer
    shape: Tuple[int, ...]  # per-node shape (leading nodes axis stripped)
    dtype: str  # original leaf dtype name, restored by unpack

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static description of a packed node-stacked pytree -- the CONTRACT
    between the tree world and the flat engine.

    A layout promises, for a buffer ``flat`` of shape
    ``(n_nodes, total)``:

    * **Column map.** Leaf ``k`` (in ``tree_flatten`` order) occupies
      columns ``[leaves[k].offset, leaves[k].offset + leaves[k].size)``;
      leaves are contiguous, in order, and non-overlapping
      (``offset[k+1] == offset[k] + size[k]``).
    * **Padding.** Columns ``[used, total)`` are structural zero padding
      (``pack(..., pad_to=k)`` rounds ``total`` up so the buffer tiles
      evenly into kernel ``scale_chunk`` blocks). Engine ops must keep
      them zero-preserving: every shipped backend is columnwise, so zeros
      mix/update/quantize to zeros and ``unpack`` never reads them.
    * **Dtype round trip.** ``unpack(pack(tree)) == tree`` exactly when
      ``storage_dtype`` holds every leaf dtype losslessly (the fp32
      default covers fp32/bf16/fp16): each leaf is stored widened to the
      buffer dtype and ``unpack`` restores ``leaves[k].dtype``. A NARROW
      ``storage_dtype`` (bf16 flat storage -- halves the HBM traffic of
      every buffer-wide op) rounds wider leaves on pack; engines that
      opt in keep fp32 only in their mix accumulators.
    * **Static + hashable.** Layouts are plain Python data (treedef +
      tuple of :class:`LeafSpec`), computable from ShapeDtypeStructs alone
      (:func:`pack_layout`) -- usable as a jit static argument and at
      trace time in lowering-only dry runs.

    Mutating state between pack and unpack is fine as long as shapes stay
    ``(n_nodes, total)``: the flat/fused GossipEngines
    (``make_fl_round(engine=...)``) run whole training rounds on the
    buffer and unpack only at the read-out boundary.
    """

    treedef: Any
    leaves: Tuple[LeafSpec, ...]
    n_nodes: int
    total: int
    #: dtype the flat buffer is STORED in ("float32" default; "bfloat16"
    #: halves HBM traffic of every buffer-wide op -- engines keep fp32
    #: only in the mix accumulator). Not necessarily lossless for wider
    #: leaf dtypes.
    storage_dtype: str = "float32"
    #: how many equal column tiles the buffer splits into on a two-axis
    #: ``(gossip_node, model_shard)`` mesh: shard s owns columns
    #: ``[s * shard_width, (s + 1) * shard_width)``. ``total`` is padded
    #: so every shard is a whole number of kernel chunks (pack with
    #: ``pad_to=scale_chunk, shards=S``); the default 1 is the
    #: single-axis layout every pre-two-axis engine uses.
    shards: int = 1

    def __post_init__(self):
        if self.shards < 1:
            raise ValueError(f"shards={self.shards} must be >= 1")
        if self.total % self.shards:
            raise ValueError(
                f"layout.total {self.total} not divisible by "
                f"shards={self.shards}; pack with pad_to and shards "
                "together so each shard is a whole tile"
            )

    @property
    def used(self) -> int:
        return sum(l.size for l in self.leaves)

    @property
    def n_leaves(self) -> int:
        return len(self.leaves)

    @property
    def row_leaves(self) -> Tuple[bool, ...]:
        """Per leaf, whether it converts through whole ``LANES``-wide
        rows (offset and size both multiples of :data:`LANES`, in a buffer
        of whole rows); the others take the column path."""
        if self.total % LANES:
            return (False,) * len(self.leaves)
        return tuple(l.offset % LANES == 0 and l.size % LANES == 0
                     for l in self.leaves)

    @property
    def row_columns(self) -> int:
        """Columns that ``pack_like``/``unpack`` convert through rows."""
        return sum(l.size for l, r in zip(self.leaves, self.row_leaves) if r)

    @property
    def row_share(self) -> float:
        """:attr:`row_columns` as a share of :attr:`used`."""
        return self.row_columns / self.used

    @property
    def shard_width(self) -> int:
        """Columns each model shard owns (``total / shards``)."""
        return self.total // self.shards

    def with_shards(self, shards: int) -> "FlatLayout":
        """The same layout re-tiled over ``shards`` model shards (the
        padded ``total`` must already divide evenly -- pack with
        ``shards=`` to get the right padding up front)."""
        return dataclasses.replace(self, shards=int(shards))


def _layout(treedef, leaf_list, n_nodes: int, pad_to: int,
            storage_dtype, shards: int = 1) -> FlatLayout:
    specs = []
    off = 0
    for leaf in leaf_list:
        shape = tuple(leaf.shape[1:])
        specs.append(LeafSpec(off, shape, jnp.dtype(leaf.dtype).name))
        off += specs[-1].size
    # each model shard must itself tile into whole pad_to (scale_chunk)
    # blocks, so the effective rounding unit is pad_to * shards
    unit = max(pad_to, 1) * max(int(shards), 1)
    total = off if unit <= 1 else ((off + unit - 1) // unit) * unit
    return FlatLayout(treedef, tuple(specs), n_nodes, total,
                      jnp.dtype(storage_dtype).name, max(int(shards), 1))


def pack_layout(tree: PyTree, pad_to: int = 1,
                storage_dtype=jnp.float32, shards: int = 1) -> FlatLayout:
    """Compute the layout without materializing the buffer (works on
    ShapeDtypeStructs too -- used by lowering-only dry runs).
    ``shards > 1`` pads ``total`` to a multiple of ``pad_to * shards``
    so every model shard is a whole number of kernel chunks."""
    leaf_list, treedef = jax.tree_util.tree_flatten(tree)
    if not leaf_list:
        raise ValueError("cannot pack an empty pytree")
    n_nodes = leaf_list[0].shape[0]
    for leaf in leaf_list:
        if leaf.ndim < 1 or leaf.shape[0] != n_nodes:
            raise ValueError(
                f"leaf shape {leaf.shape} is not node-stacked for n={n_nodes}"
            )
    return _layout(treedef, leaf_list, n_nodes, pad_to, storage_dtype, shards)


def pack(
    tree: PyTree, pad_to: int = 1, buffer_dtype=jnp.float32, shards: int = 1
) -> Tuple[jnp.ndarray, FlatLayout]:
    """Pack a node-stacked pytree into one ``(nodes, total)`` buffer.

    Args:
      tree: pytree whose every leaf is ``(nodes, ...)``.
      pad_to: round ``total`` up to a multiple (zero-filled tail) so the
        buffer tiles evenly into kernel chunks.
      buffer_dtype: storage dtype of the flat buffer (recorded as
        ``layout.storage_dtype``). fp32 holds fp32/bf16/fp16 losslessly;
        bf16 storage rounds fp32 leaves (the flat engine's bf16 mode).

    Returns:
      (flat, layout) with ``flat.shape == (nodes, layout.total)``.
    """
    layout = pack_layout(tree, pad_to, storage_dtype=buffer_dtype,
                         shards=shards)
    return pack_like(tree, layout), layout


def _as_rows(cols: jnp.ndarray) -> jnp.ndarray:
    """``(n, k * LANES)`` columns as ``(n, k, LANES)`` rows."""
    return cols.reshape(cols.shape[0], cols.shape[1] // LANES, LANES)


def pack_like(tree: PyTree, layout: FlatLayout, buffer_dtype=None) -> jnp.ndarray:
    """Pack a pytree into an EXISTING layout (same structure and per-leaf
    shapes; zero-padded to ``layout.total``; stored in the layout's
    ``storage_dtype`` unless overridden). Used to flatten gradients into
    the same columns as the packed parameters they update. Leaves in
    :attr:`FlatLayout.row_leaves` join as rows (module docstring)."""
    leaf_list, treedef = jax.tree_util.tree_flatten(tree)
    if treedef != layout.treedef:
        raise ValueError(f"tree structure {treedef} != layout {layout.treedef}")
    if buffer_dtype is None:
        buffer_dtype = layout.storage_dtype
    n = layout.n_nodes
    for leaf, spec in zip(leaf_list, layout.leaves):
        if leaf.shape != (n,) + spec.shape:
            raise ValueError(f"leaf shape {leaf.shape} != layout {(n,) + spec.shape}")
    with jax.named_scope("flat_pack"):
        cols = [l.reshape(n, -1).astype(buffer_dtype) for l in leaf_list]
        on_rows = list(layout.row_leaves)
        if layout.total > layout.used:
            cols.append(jnp.zeros((n, layout.total - layout.used), buffer_dtype))
            on_rows.append(False)
        if not any(on_rows):
            return jnp.concatenate(cols, axis=1)
        rows, run = [], []  # run: unaligned column pieces since a row boundary
        for col, row in zip(cols, on_rows):
            if row:
                if run:  # an aligned leaf starts where the run ends
                    rows.append(_as_rows(jnp.concatenate(run, axis=1)))
                    run = []
                rows.append(_as_rows(col))
            else:
                run.append(col)
        if run:  # ends at total, a whole row
            rows.append(_as_rows(jnp.concatenate(run, axis=1)))
        # The barrier makes the buffer in its own layout, in the storage
        # dtype. Left free, XLA folds the rows -> buffer relayout into
        # each consumer after its float32 cast, moving twice the bytes
        # (6 ms a round slower for smollm-360m on a v5e).
        return jax.lax.optimization_barrier(
            jnp.concatenate(rows, axis=1).reshape(n, layout.total))


def unpack(flat: jnp.ndarray, layout: FlatLayout) -> PyTree:
    """Invert :func:`pack`: slice, reshape, and restore each leaf's dtype.
    Leaves in :attr:`FlatLayout.row_leaves` are sliced as rows of the
    buffer's ``(n, total // LANES, LANES)`` view (module docstring)."""
    if flat.shape != (layout.n_nodes, layout.total):
        raise ValueError(
            f"flat buffer {flat.shape} does not match layout "
            f"({layout.n_nodes}, {layout.total})"
        )
    n = layout.n_nodes
    with jax.named_scope("flat_unpack"):
        on_rows = layout.row_leaves
        rows = _as_rows(flat) if any(on_rows) else None
        leaves = [
            (jax.lax.slice_in_dim(rows, s.offset // LANES,
                                  (s.offset + s.size) // LANES, axis=1)
             if row else
             jax.lax.slice_in_dim(flat, s.offset, s.offset + s.size, axis=1))
            .reshape((n,) + s.shape)
            .astype(s.dtype)
            for s, row in zip(layout.leaves, on_rows)
        ]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def compact_pos_dtype(scale_chunk: int):
    """Dtype of the compact wire's in-chunk position buffer: int16 when a
    chunk index fits (the common case -- chunk <= 32768), int32 otherwise.
    The SAME boundary drives :func:`flat_wire_bytes`, so the accounting
    is the bytes the collective actually moves."""
    return jnp.int16 if scale_chunk <= 2 ** 15 else jnp.int32


def bitmap_bytes_per_chunk(scale_chunk: int) -> int | None:
    """Bytes of one chunk's presence bitmap, or None when the bitmap
    encoding is unavailable (chunk not byte-aligned). The SAME predicate
    gates the engine's encoding choice and the accounting."""
    return scale_chunk // 8 if scale_chunk % 8 == 0 else None


def compact_index_bytes(scale_chunk: int, topk: int) -> int:
    """Index bytes ONE chunk's compact top-k payload ships: the cheaper
    of explicit positions (k x int16/int32, :func:`compact_pos_dtype`)
    and the presence bitmap (chunk/8 B, byte-aligned chunks only). The
    bitmap wins for k > chunk/16 (int16 positions) -- the boundary the
    sharded engine's ``wire_encoding`` mirrors exactly, so the accounted
    bytes ARE the collective operand bytes."""
    explicit = topk * jnp.dtype(compact_pos_dtype(scale_chunk)).itemsize
    bitmap = bitmap_bytes_per_chunk(scale_chunk)
    return explicit if bitmap is None else min(explicit, bitmap)


def flat_wire_bytes(
    layout: FlatLayout, degree: int, scale_chunk: int = 0,
    topk: int | None = None,
) -> int:
    """Per-node egress bytes per round for an int8 flat payload, times the
    out-degree.

    Dense int8 (``topk=None``): 1 B/param + 4 B per scale chunk
    (``scale_chunk=0``: one scale per node).

    Top-k sparsified (``topk=k``): the COMPACT encoding the wire-stage
    kernels actually emit (``kernels.gossip.wire_stage_compact`` + the
    engine's encoding epilogue) -- per scale chunk, exactly k int8 values
    + the CHEAPER index encoding (:func:`compact_index_bytes`: explicit
    int16/int32 positions vs the chunk/8-byte presence bitmap, picked
    per (k, chunk)) + the 4 B scale, capped at the dense chunk bytes (a
    sender whose compact encoding would exceed dense just ships dense).
    This is not a model: the collective's operand shapes ARE these
    buffers (asserted against the jaxpr in tests/test_schedule.py and
    tests/test_dynamics.py).
    """
    n_scales = 1 if scale_chunk <= 0 else -(-layout.total // scale_chunk)
    if topk is None or scale_chunk <= 0 or topk >= scale_chunk:
        return degree * (layout.total + 4 * n_scales)
    index_bytes = compact_index_bytes(scale_chunk, topk)
    per_chunk = min(topk + index_bytes + 4, scale_chunk + 4)
    return degree * (n_scales * per_chunk)


def scoped_layout(
    layout: FlatLayout, ranges, scale_chunk: int
) -> Tuple[FlatLayout, Tuple[Tuple[int, int], ...]]:
    """Accounting layout + shard-local column ranges for a SCOPED wire.

    A :class:`~repro.core.scope.FederationScope` restricts gossip to the
    merged, disjoint global column ``ranges`` of ``layout``. The fused
    engines gather those columns into one contiguous scoped buffer (per
    shard tile on a two-axis mesh), run the unchanged wire stage on it,
    and scatter the mixed result back -- so the wire state (recon, EF
    residual, in-flight rings), the quantization scales, the collective
    operands, and the byte accounting all live at the SCOPED width.

    Returns ``(wire_layout, local_ranges)``:

    * ``local_ranges`` -- the ranges intersected with one shard tile, in
      SHARD-LOCAL coordinates. They must come out IDENTICAL for every
      shard (each shard's wire slice must be the same width and chunk
      geometry -- the same reason ``with_shards`` pads per shard);
      a scope whose ranges straddle shard tiles unevenly is refused with
      the mismatching shards named.
    * ``wire_layout`` -- a synthetic single-leaf :class:`FlatLayout`
      whose ``total`` is the chunk-padded scoped width (x shards, shards
      preserved) and whose ``used`` is the un-padded shared column count,
      so :func:`flat_wire_bytes` / :func:`flat_wire_bytes_per_shard` on
      it ARE the scoped wire accounting, byte-compatible with the
      collective operands the scoped round lowers to.
    """
    ranges = tuple((int(a), int(b)) for a, b in ranges)
    pos = 0
    for a, b in ranges:
        if not (pos <= a < b <= layout.total):
            raise ValueError(
                f"scoped ranges {ranges!r} must be sorted, disjoint, "
                f"non-empty, within [0, {layout.total})"
            )
        pos = b
    s = layout.shards
    w = layout.shard_width
    per_shard = []
    for i in range(s):
        lo, hi = i * w, (i + 1) * w
        local = tuple(
            (max(a, lo) - lo, min(b, hi) - lo)
            for a, b in ranges if a < hi and b > lo
        )
        per_shard.append(local)
    if any(local != per_shard[0] for local in per_shard):
        widths = [sum(b - a for a, b in local) for local in per_shard]
        raise ValueError(
            f"scoped ranges are not uniform across the {s} model shards "
            f"(per-shard shared widths {widths}); every shard tile must "
            "carry the same scoped slice -- align the scope's ranges "
            "with the shard tiles (shard_width="
            f"{w}) or run single-axis"
        )
    local_ranges = per_shard[0]
    shared_local = sum(b - a for a, b in local_ranges)
    if shared_local == 0:
        raise ValueError(
            f"scoped ranges {ranges!r} share no columns; a scope must "
            "leave something on the wire"
        )
    unit = max(int(scale_chunk), 1)
    padded_local = ((shared_local + unit - 1) // unit) * unit
    wire_layout = FlatLayout(
        treedef=jax.tree_util.tree_structure(0),
        leaves=(LeafSpec(0, (shared_local * s,), "float32"),),
        n_nodes=layout.n_nodes,
        total=padded_local * s,
        storage_dtype=layout.storage_dtype,
        shards=s,
    )
    return wire_layout, local_ranges


def flat_wire_bytes_per_shard(
    layout: FlatLayout, degree: int, scale_chunk: int = 0,
    topk: int | None = None,
) -> int:
    """Per-(node, shard) egress bytes per round on a two-axis mesh: each
    model shard ships its own chunk-aligned slice of the wire, so the
    per-shard bytes are exactly ``flat_wire_bytes / shards`` -- the
    identity the sharded engine's per-tile collective operands realize
    (and the jaxpr assertions in tests/test_two_axis.py check). Requires
    the shard-aligned padding :func:`pack_layout` with ``shards=``
    guarantees (``total % (scale_chunk * shards) == 0``)."""
    s = layout.shards
    if s <= 1:
        return flat_wire_bytes(layout, degree, scale_chunk, topk)
    if scale_chunk > 0 and layout.shard_width % scale_chunk:
        raise ValueError(
            f"shard width {layout.shard_width} not a multiple of "
            f"scale_chunk {scale_chunk}; pack with pad_to={scale_chunk}, "
            f"shards={s}"
        )
    n_scales = 1 if scale_chunk <= 0 else layout.shard_width // scale_chunk
    if topk is None or scale_chunk <= 0 or topk >= scale_chunk:
        return degree * (layout.shard_width + 4 * n_scales)
    index_bytes = compact_index_bytes(scale_chunk, topk)
    per_chunk = min(topk + index_bytes + 4, scale_chunk + 4)
    return degree * (n_scales * per_chunk)
