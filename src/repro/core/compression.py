"""Compressed gossip: int8 quantization with error feedback (beyond-paper).

The paper saves communication ROUNDS (Q local steps); this module saves
BYTES PER ROUND: neighbor payloads are quantized to int8 (4x smaller than
fp32) with symmetric scaling, and the quantization residual is fed back
into the next round's payload (error feedback / EF-SGD style), which keeps
the long-run mixing unbiased -- plain quantized gossip accumulates an
O(quant-err / spectral-gap) consensus floor, while EF drives it to the
same floor as exact gossip (property-tested).

**Flat-buffer engine.** The hot path operates on the packed
``(nodes, total_params)`` buffer from ``core.packing``: ONE
quantize-mix-EF pass per round instead of one per leaf, with scales
computed per ``(node, scale_chunk)`` column block (finer than the
historical per-leaf scales for big leaves, coarser for confetti-sized
ones; the chunk is the tile of the fused Pallas kernel in
``repro.kernels.gossip``, which eliminates the materialized payload/dq/
recon intermediates entirely). ``make_compressed_dense_gossip`` wraps the
flat engine in pack/unpack for the tree API;
``make_compressed_dense_gossip_per_leaf`` keeps the historical per-leaf
implementation as the equivalence oracle.

State per node: the shared reconstruction theta_hat (what neighbors can
rebuild from wire traffic alone) + the error-feedback residual. The
compressed gossip has signature

    (tree, state) -> (mixed_tree, new_state)

threaded at the driver level (tests/test_compression.py shows the FL
loop; comm accounting in benchmarks/comm_bytes.py).

Quantizer: symmetric int8: q = round(x / s), s = max|x| / 127, dequant =
q * s. Wire payload per round = 1 byte/param + 4 bytes per scale block
(per-node-per-leaf for the per-leaf path -- ``compressed_wire_bytes`` --
per ``(node, scale_chunk)`` for the flat engine --
``packing.flat_wire_bytes``).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import pack, unpack

PyTree = Any
FlatGossipFn = Callable[
    [jnp.ndarray, "dict[str, jnp.ndarray]"],
    Tuple[jnp.ndarray, "dict[str, jnp.ndarray]"],
]

# Default scale granularity of the flat engine == the default VMEM tile of
# the fused kernel (one fp32 scale per 512 int8 params: 0.8% wire overhead).
DEFAULT_SCALE_CHUNK = 512

__all__ = [
    "DEFAULT_SCALE_CHUNK",
    "quantize_int8",
    "dequantize_int8",
    "make_compressed_dense_gossip",
    "make_compressed_dense_gossip_per_leaf",
    "make_compressed_flat_gossip",
    "init_compression_state",
    "init_flat_compression_state",
    "zeros_like_residual",
    "compressed_wire_bytes",
]


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-node symmetric int8. x: (nodes, ...) -> (q int8, scale (nodes,))."""
    flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
    scale = jnp.max(jnp.abs(flat), axis=1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(flat / safe[:, None]), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    flat = q.reshape(q.shape[0], -1).astype(jnp.float32)
    return (flat * scale[:, None]).reshape(q.shape)


def zeros_like_residual(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda x: jnp.zeros_like(x, jnp.float32), tree)


def init_compression_state(tree: PyTree) -> PyTree:
    """{recon, residual} per leaf. ``recon`` is the shared reconstruction
    every neighbor can maintain from the wire traffic alone (starts at 0:
    the first round effectively transmits the full parameters)."""
    z = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x, jnp.float32), tree)
    return {"recon": z, "residual": jax.tree_util.tree_map(jnp.copy, z)}


def init_flat_compression_state(flat: jnp.ndarray) -> dict:
    """Flat-engine compression state: {recon, residual} as (nodes, total)
    fp32 buffers (zeros: the first round transmits the full parameters)."""
    z = jnp.zeros(flat.shape, jnp.float32)
    return {"recon": z, "residual": z}


def make_compressed_flat_gossip(
    w: np.ndarray,
    error_feedback: bool = True,
    difference_coding: bool = True,
    scale_chunk: int = DEFAULT_SCALE_CHUNK,
    impl: str = "jnp",
    topk: int | None = None,
) -> FlatGossipFn:
    """Flat-native CHOCO-style gossip on the packed ``(nodes, total)``
    buffer (``total`` must be a multiple of ``scale_chunk``; pack with
    ``pad_to=scale_chunk``).

    Difference coding: both sides share a reconstruction theta_hat built
    purely from wire traffic, and only the change is quantized:

        payload = theta - theta_hat + residual
        q, s    = int8(payload)               <- the only wire bytes
        theta_hat' = theta_hat + dq(q, s)
        residual'  = payload - dq(q, s)       (EF)
        theta' = W_ii theta + sum_{j!=i} W_ij theta_hat_j'

    As consensus approaches, payload scales -> 0, so quantization error
    -> 0 and the mixing becomes EXACT in the limit. Plain quantized gossip
    -- and even EF over full-parameter payloads -- stalls at an
    O(max|theta| / 127 / gap) consensus floor because the quantization
    STEP never shrinks (measured; see tests).

    ``impl="jnp"`` runs the chunked jnp reference; ``impl="pallas"`` the
    fused VMEM-tiled kernel (``repro.kernels.gossip``) that computes
    quantize -> W-row mix -> dequant + EF in one pass with no materialized
    full-size payload/dq/recon intermediates. ``topk=k`` ships only the k
    largest-|payload| columns per scale chunk (sub-int8 wire bytes; the EF
    residual absorbs the truncation, so consensus contraction survives --
    property-tested in tests/test_topk_property.py).
    """
    if impl == "jnp":
        from repro.kernels.gossip.ref import gossip_mix_ref as mix_impl
    elif impl == "pallas":
        from repro.kernels.gossip.ops import gossip_mix as mix_impl
        from repro.kernels.gossip.ops import require_topk_lowering

        require_topk_lowering(topk)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    w = np.asarray(w, dtype=np.float64)
    w_self = jnp.asarray(np.diag(w), dtype=jnp.float32)
    w_off = jnp.asarray(w - np.diag(np.diag(w)), dtype=jnp.float32)

    def gossip(flat: jnp.ndarray, state: dict) -> Tuple[jnp.ndarray, dict]:
        mixed, recon, res, _ = mix_impl(
            flat.astype(jnp.float32),
            state["recon"],
            state["residual"],
            w_off,
            w_self,
            scale_chunk=scale_chunk,
            error_feedback=error_feedback,
            difference_coding=difference_coding,
            topk=topk,
        )
        return mixed.astype(flat.dtype), {"recon": recon, "residual": res}

    return gossip


def make_compressed_dense_gossip(
    w: np.ndarray,
    error_feedback: bool = True,
    difference_coding: bool = True,
    scale_chunk: int = DEFAULT_SCALE_CHUNK,
    impl: str = "jnp",
) -> Callable[[PyTree, PyTree], Tuple[PyTree, PyTree]]:
    """Tree-API wrapper of :func:`make_compressed_flat_gossip`: packs the
    parameters and the {recon, residual} state into flat buffers, runs ONE
    quantize-mix-EF pass, and unpacks. Signature and state layout are
    unchanged from the historical per-leaf version
    (:func:`make_compressed_dense_gossip_per_leaf`)."""
    flat_gossip = make_compressed_flat_gossip(
        w, error_feedback, difference_coding, scale_chunk, impl
    )

    def gossip(tree: PyTree, state: PyTree) -> Tuple[PyTree, PyTree]:
        flat, layout = pack(tree, pad_to=scale_chunk)
        recon, f32_layout = pack(state["recon"], pad_to=scale_chunk)
        res, _ = pack(state["residual"], pad_to=scale_chunk)
        mixed, new_state = flat_gossip(flat, {"recon": recon, "residual": res})
        return unpack(mixed, layout), {
            "recon": unpack(new_state["recon"], f32_layout),
            "residual": unpack(new_state["residual"], f32_layout),
        }

    return gossip


def make_compressed_dense_gossip_per_leaf(
    w: np.ndarray, error_feedback: bool = True, difference_coding: bool = True
) -> Callable[[PyTree, PyTree], Tuple[PyTree, PyTree]]:
    """Historical leaf-by-leaf CHOCO gossip (per-node-per-LEAF scales, one
    quantize+matmul pass and three materialized full-size intermediates
    per leaf per round). Kept as the flat engine's equivalence oracle and
    the benchmark baseline."""
    w = np.asarray(w, dtype=np.float64)
    n = w.shape[0]
    w_self = jnp.asarray(np.diag(w), dtype=jnp.float32)
    w_off = jnp.asarray(w - np.diag(np.diag(w)), dtype=jnp.float32)

    def mix_leaf(x, recon, res):
        xf = x.astype(jnp.float32)
        base = recon if difference_coding else jnp.zeros_like(recon)
        payload = xf - base + (res if error_feedback else 0.0)
        q, s = quantize_int8(payload)
        dq = dequantize_int8(q, s)
        new_recon = base + dq
        new_res = payload - dq if error_feedback else res
        mixed = w_off @ new_recon.reshape(n, -1) + w_self[:, None] * xf.reshape(n, -1)
        return mixed.reshape(x.shape).astype(x.dtype), new_recon, new_res

    def gossip(tree: PyTree, state: PyTree) -> Tuple[PyTree, PyTree]:
        triples = jax.tree_util.tree_map(mix_leaf, tree, state["recon"], state["residual"])
        is_triple = lambda v: isinstance(v, tuple)
        mixed = jax.tree_util.tree_map(lambda p: p[0], triples, is_leaf=is_triple)
        recon = jax.tree_util.tree_map(lambda p: p[1], triples, is_leaf=is_triple)
        res = jax.tree_util.tree_map(lambda p: p[2], triples, is_leaf=is_triple)
        return mixed, {"recon": recon, "residual": res}

    return gossip


def compressed_wire_bytes(tree: PyTree, degree: int) -> int:
    """Per-node egress bytes per round for the PER-LEAF path: 1 B/param +
    4 B scale per leaf, times the out-degree. The flat engine's accounting
    (4 B per ``scale_chunk`` columns instead) is
    ``packing.flat_wire_bytes``."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        per_node = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
        total += per_node + 4
    return degree * total
