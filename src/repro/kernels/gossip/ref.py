"""Chunked jnp oracles for the fused gossip / round megakernels.

Computes the CHOCO-gossip round on a flat ``(nodes, total)`` buffer with
per-``(node, scale_chunk)`` int8 scales -- bit-identical math to the
Pallas kernels (``gossip.py``), which tile the same computation over
``(nodes, scale_chunk)`` VMEM blocks. These references materialize the
full-size payload/dq/recon intermediates the kernels fuse away; they are
the interpret-mode correctness oracles and the single-device simulated
path.

The round oracles (:func:`fused_round_ref`, :func:`fused_round_gt_ref`)
are deliberately written as the COMPOSITION of the plain local update and
:func:`gossip_mix_ref` -- "fused == local-step-then-gossip" therefore
holds by construction on the reference side, and the megakernels are
property-tested against it (tests/test_megakernel.py).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.packing import compact_pos_dtype

__all__ = [
    "gossip_mix_ref",
    "fused_round_ref",
    "fused_round_gt_ref",
    "wire_stage_ref",
    "wire_stage_gt_ref",
    "wire_stage_compact_ref",
    "wire_stage_gt_compact_ref",
    "scatter_compact_dq",
    "compact_to_bitmap",
    "scatter_bitmap_dq",
]


def _dp_wire(payload, dp_clip, dp_noise, error_feedback: bool):
    """The DP epilogue shared by every wire oracle: per-node L2 clip of
    the wire payload to ``dp_clip`` plus pre-scaled Gaussian ``dp_noise``
    (std = sigma * clip, generated by ``core.privacy.dp_noise``). The
    RETURNED wire is what gets quantized and shipped; the caller keeps
    computing the EF residual against the UNCLIPPED payload, so error
    feedback absorbs clip + noise + quantization error together --
    requires error feedback for exactly that reason."""
    if dp_noise is None:
        return payload
    if not error_feedback:
        raise ValueError(
            "dp needs error_feedback=True: the residual is what absorbs "
            "the clip + noise perturbation (otherwise the wire walk "
            "diverges from the parameters)"
        )
    nrm = jnp.sqrt(jnp.sum(payload * payload, axis=1, keepdims=True))
    cs = jnp.minimum(
        1.0, jnp.asarray(dp_clip, jnp.float32)
        / jnp.maximum(nrm, jnp.float32(1e-12))
    )
    return cs * payload + dp_noise


def _quantize_ef_chunks(payload, scale_chunk: int, topk):
    """Shared quantize core: per-(node, scale_chunk) int8 with optional
    top-k masking (same tie-keeping threshold formula as the kernel tile,
    applied chunk-by-chunk -- bit-identical). Returns (q, scales, dq)."""
    n, t = payload.shape
    p3 = payload.reshape(n, t // scale_chunk, scale_chunk)
    if topk is not None and topk < scale_chunk:
        thr = jnp.sort(jnp.abs(p3), axis=2)[:, :, scale_chunk - topk][:, :, None]
        p3 = jnp.where(jnp.abs(p3) >= thr, p3, 0.0)
    scales = jnp.max(jnp.abs(p3), axis=2) / 127.0  # (n, n_chunks)
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round(p3 / safe[:, :, None]), -127, 127)
    dq = (q * scales[:, :, None]).reshape(n, t)
    return q, scales, dq


def _quantize_ef_compact_chunks(payload, scale_chunk: int, topk: int):
    """Compact-gather quantize core: EXACT-k selection per (node, chunk)
    via ``jax.lax.top_k`` on |payload| (ties broken toward the lower
    index -- bit-identical to the kernel's per-tile epilogue), int8
    quantization of the survivors, and the dense dq scattered back for
    the sender-side recon/EF updates. Returns (q (n, C*k) fp32 ints,
    pos (n, C*k) int32, scales (n, C), dq (n, t))."""
    n, t = payload.shape
    c = t // scale_chunk
    p2 = payload.reshape(n * c, scale_chunk)
    _, pos = jax.lax.top_k(jnp.abs(p2), topk)  # (n*c, k) int32
    vals = jnp.take_along_axis(p2, pos, axis=-1)
    scales = jnp.max(jnp.abs(vals), axis=-1, keepdims=True) / 127.0
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round(vals / safe), -127, 127)
    rows = jax.lax.broadcasted_iota(jnp.int32, pos.shape, 0)
    dq = jnp.zeros_like(p2).at[rows, pos].add(q * scales).reshape(n, t)
    return (q.reshape(n, c * topk), pos.reshape(n, c * topk),
            scales.reshape(n, c), dq)


def scatter_compact_dq(
    q: jnp.ndarray,
    pos: jnp.ndarray,
    scales: jnp.ndarray,
    scale_chunk: int,
    total: int,
) -> jnp.ndarray:
    """RECEIVE-side scatter-accumulate of the compact top-k wire: rebuild
    the dense dequantized payload from exactly what crossed the
    collective.

    Args:
      q: (rows, n_chunks * k) int8 values.
      pos: (rows, n_chunks * k) int16/int32 in-chunk positions.
      scales: (rows, n_chunks) fp32 per-chunk scales.
      scale_chunk / total: the layout geometry.

    Returns the (rows, total) fp32 dense dq -- exactly equal to the
    masked-dense ``dq`` of :func:`_quantize_ef_compact_chunks` (lossless
    round trip; property-tested in tests/test_schedule.py) -- which feeds
    the running ``mix_recon`` accumulator."""
    rows, ck = q.shape
    if total % scale_chunk:
        raise ValueError(f"total {total} not a multiple of scale_chunk {scale_chunk}")
    c = total // scale_chunk
    if ck % c:
        raise ValueError(f"compact width {ck} not a multiple of n_chunks {c}")
    k = ck // c
    v3 = q.astype(jnp.float32).reshape(rows, c, k) * scales[:, :, None]
    cols = pos.astype(jnp.int32).reshape(rows, c, k) + (
        jnp.arange(c, dtype=jnp.int32) * scale_chunk)[None, :, None]
    r = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 0)
    return jnp.zeros((rows, total), jnp.float32).at[r, cols].add(v3)


def compact_to_bitmap(
    q: jnp.ndarray,
    pos: jnp.ndarray,
    scale_chunk: int,
    topk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Re-encode one compact top-k payload with a PRESENCE BITMAP index:
    explicit in-chunk positions cost ``k x 2`` bytes (int16), the bitmap
    a flat ``chunk/8`` bytes -- cheaper whenever ``k > chunk/16``
    (``packing.compact_index_bytes`` picks the same boundary, so the
    accounting is the bytes that actually cross).

    Args:
      q: (rows, n_chunks * k) int8 values in |value|-descending top_k
        order (what the compact wire-stage kernels emit).
      pos: (rows, n_chunks * k) int16/int32 in-chunk positions.
      scale_chunk / topk: the encoding geometry (chunk must be a
        multiple of 8 -- byte-aligned bitmaps only).

    Returns ``(vals, bits)``: the SAME k values per chunk re-sorted into
    ascending-position order (rows, n_chunks * k) int8 -- the order the
    bitmap decode implies -- and the packed LSB-first presence bitmap
    (rows, n_chunks * chunk // 8) uint8. Lossless:
    :func:`scatter_bitmap_dq` rebuilds exactly
    :func:`scatter_compact_dq`'s dense payload (property-tested)."""
    if scale_chunk % 8:
        raise ValueError(
            f"bitmap wire needs a byte-aligned chunk, got {scale_chunk}"
        )
    rows, ck = q.shape
    if ck % topk:
        raise ValueError(f"compact width {ck} not a multiple of k={topk}")
    c = ck // topk
    p3 = pos.astype(jnp.int32).reshape(rows, c, topk)
    v3 = q.reshape(rows, c, topk)
    order = jnp.argsort(p3, axis=-1)
    vals = jnp.take_along_axis(v3, order, axis=-1)
    # uint8 throughout: this runs on the per-round wire path inside the
    # shard_map body, and the positions of a byte's 8 bits are disjoint,
    # so the weighted sum never exceeds 255 -- a wider one-hot would move
    # 4x the dense payload's bytes just to pack k bits per chunk
    one_hot = jnp.zeros((rows, c, scale_chunk), jnp.uint8)
    r_i = jax.lax.broadcasted_iota(jnp.int32, p3.shape, 0)
    c_i = jax.lax.broadcasted_iota(jnp.int32, p3.shape, 1)
    one_hot = one_hot.at[r_i, c_i, p3].set(1)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    bits = jnp.sum(
        one_hot.reshape(rows, c, scale_chunk // 8, 8) * weights,
        axis=-1, dtype=jnp.uint8,
    )
    return vals.reshape(rows, ck), bits.reshape(rows, c * (scale_chunk // 8))


def scatter_bitmap_dq(
    vals: jnp.ndarray,
    bits: jnp.ndarray,
    scales: jnp.ndarray,
    scale_chunk: int,
    total: int,
) -> jnp.ndarray:
    """RECEIVE-side decode of the bitmap compact wire: rebuild the dense
    dequantized payload from (k ascending-position int8 values, packed
    presence bitmap, fp32 scales) -- the bitmap twin of
    :func:`scatter_compact_dq`, and exactly equal to it.

    Decode: unpack the LSB-first bits, prefix-sum them along the chunk to
    map each present column to its slot in the ascending-position value
    list, and gather."""
    rows, ck = vals.shape
    if total % scale_chunk or scale_chunk % 8:
        raise ValueError(
            f"bad geometry: total={total}, scale_chunk={scale_chunk}"
        )
    c = total // scale_chunk
    if ck % c:
        raise ValueError(f"compact width {ck} not a multiple of n_chunks {c}")
    k = ck // c
    b3 = bits.reshape(rows, c, scale_chunk // 8)
    shifts = jnp.arange(8, dtype=jnp.uint32)
    present = (
        (b3[..., None].astype(jnp.uint32) >> shifts) & jnp.uint32(1)
    ).reshape(rows, c, scale_chunk).astype(jnp.int32)
    slot = jnp.cumsum(present, axis=-1) - 1  # index into the value list
    v3 = vals.astype(jnp.float32).reshape(rows, c, k) * scales[:, :, None]
    gathered = jnp.take_along_axis(v3, jnp.clip(slot, 0, k - 1), axis=-1)
    return jnp.where(present > 0, gathered, 0.0).reshape(rows, total)


def gossip_mix_ref(
    x: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    w_off: jnp.ndarray,
    w_self: jnp.ndarray,
    *,
    scale_chunk: int,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    stale_mix: bool = False,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One compressed gossip round on flat buffers.

    Args:
      x: (n, t) fp32 node-stacked flat parameters, t % scale_chunk == 0.
      recon: (n, t) fp32 shared reconstruction (wire-reconstructible).
      res: (n, t) fp32 error-feedback residual.
      w_off: (n, n) fp32 off-diagonal mixing weights (zero diagonal).
      w_self: (n,) fp32 self weights (the W diagonal).
      scale_chunk: columns per int8 scale block.
      topk: if set, only the k largest-|payload| columns per scale chunk
        go on the wire (ties at the threshold kept); with error feedback
        the truncated mass is absorbed by the residual, so top-k gossip
        still contracts to consensus (property-tested).
      stale_mix: mix against the INPUT recon (the neighbor reconstruction
        as of the END of the previous round) instead of ``new_recon`` --
        the pipelined round schedule's one-round-stale dynamics. recon/EF
        updates are unchanged.
      dp_clip / dp_noise: the differential-privacy epilogue -- the wire
        payload is L2-clipped per node to ``dp_clip`` and perturbed by
        the pre-scaled Gaussian ``dp_noise`` (n, t) BEFORE quantization;
        the EF residual keeps tracking the unclipped payload, absorbing
        the perturbation (needs error_feedback=True). See
        ``core.privacy``.

    Returns:
      (mixed, new_recon, new_res, scales) with scales (n, t // scale_chunk).
    """
    n, t = x.shape
    if t % scale_chunk:
        raise ValueError(f"total {t} not a multiple of scale_chunk {scale_chunk}")
    base = recon if difference_coding else jnp.zeros_like(recon)
    payload = x - base + (res if error_feedback else 0.0)
    wire = _dp_wire(payload, dp_clip, dp_noise, error_feedback)

    _, scales, dq = _quantize_ef_chunks(wire, scale_chunk, topk)

    new_recon = base + dq
    new_res = payload - dq if error_feedback else res
    nbr = recon if stale_mix else new_recon
    mixed = jnp.dot(w_off, nbr, precision=jax.lax.Precision.HIGHEST) + (
        w_self[:, None] * x
    )
    return mixed, new_recon, new_res, scales


def fused_round_ref(
    x: jnp.ndarray,
    g: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    w_off: jnp.ndarray,
    w_self: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    scale_chunk: int,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    stale_mix: bool = False,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """DSGD round oracle: the local update ``h = x - alpha * g`` followed
    by one compressed gossip round on h (adapt-then-combine ordering).

    Same signature contract as :func:`gossip_mix_ref` plus the flat
    gradient buffer ``g`` (n, t) and the scalar step size ``alpha``.
    """
    h = x - alpha * g
    return gossip_mix_ref(
        h,
        recon,
        res,
        w_off,
        w_self,
        scale_chunk=scale_chunk,
        error_feedback=error_feedback,
        difference_coding=difference_coding,
        topk=topk,
        stale_mix=stale_mix,
        dp_clip=dp_clip,
        dp_noise=dp_noise,
    )


def fused_round_gt_ref(
    x: jnp.ndarray,
    t: jnp.ndarray,
    g: jnp.ndarray,
    g_prev: jnp.ndarray,
    recon_x: jnp.ndarray,
    res_x: jnp.ndarray,
    recon_t: jnp.ndarray,
    res_t: jnp.ndarray,
    w_off: jnp.ndarray,
    w_self: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    scale_chunk: int,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    stale_mix: bool = False,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
    dp_noise_t: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGT round oracle (adapt-then-combine gradient tracking):

        t_half = t + g - g_prev          (tracker absorbs the innovation)
        h      = x - alpha * t_half      (parameter update)
        t'     = quantize-mix(t_half)    (compressed gossip, tracker wire)
        x'     = quantize-mix(h)         (compressed gossip, param wire)

    ``mean_i t_half = mean_i t + mean_i (g - g_prev)`` so the tracking
    invariant ``mean_i t == mean_i g`` is preserved by any
    doubly-stochastic W up to the (vanishing, EF-corrected) quantization
    drift. Returns (mixed_x, mixed_t, new_recon_x, new_res_x, new_recon_t,
    new_res_t, scales_x, scales_t); the caller stores ``g`` as the next
    round's ``g_prev``.
    """
    t_half = t + g - g_prev
    h = x - alpha * t_half
    mt, nrt, nst, sct = gossip_mix_ref(
        t_half,
        recon_t,
        res_t,
        w_off,
        w_self,
        scale_chunk=scale_chunk,
        error_feedback=error_feedback,
        difference_coding=difference_coding,
        topk=topk,
        stale_mix=stale_mix,
        dp_clip=dp_clip,
        dp_noise=dp_noise_t,
    )
    mx, nrx, nsx, scx = gossip_mix_ref(
        h,
        recon_x,
        res_x,
        w_off,
        w_self,
        scale_chunk=scale_chunk,
        error_feedback=error_feedback,
        difference_coding=difference_coding,
        topk=topk,
        stale_mix=stale_mix,
        dp_clip=dp_clip,
        dp_noise=dp_noise,
    )
    return mx, mt, nrx, nsx, nrt, nst, scx, sct


def wire_stage_ref(
    x: jnp.ndarray,
    g: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    scale_chunk: int,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGD wire-stage oracle (the pre-collective half of the SHARDED
    fused round): local update + difference coding + (top-k) int8
    quantize + EF, with the optional DP clip+noise epilogue of
    :func:`gossip_mix_ref`. Returns (h, q int8, scales, new_recon,
    new_res); the sharded engine moves (q, scales) over the wire and
    finishes the mix against its running neighbor-reconstruction
    accumulator."""
    n, t = x.shape
    if t % scale_chunk:
        raise ValueError(f"total {t} not a multiple of scale_chunk {scale_chunk}")
    h = x - alpha * g
    base = recon if difference_coding else jnp.zeros_like(recon)
    payload = h - base + (res if error_feedback else 0.0)
    wire = _dp_wire(payload, dp_clip, dp_noise, error_feedback)
    q, scales, dq = _quantize_ef_chunks(wire, scale_chunk, topk)
    new_recon = base + dq
    new_res = payload - dq if error_feedback else res
    return h, q.reshape(n, t).astype(jnp.int8), scales, new_recon, new_res


def wire_stage_gt_ref(
    x: jnp.ndarray,
    t: jnp.ndarray,
    g: jnp.ndarray,
    g_prev: jnp.ndarray,
    recon_x: jnp.ndarray,
    res_x: jnp.ndarray,
    recon_t: jnp.ndarray,
    res_t: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    scale_chunk: int,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
    dp_noise_t: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGT wire-stage oracle: tracker arithmetic + parameter update +
    both wires' quantize-EF. Returns (h, t_half, q_x, scales_x,
    new_recon_x, new_res_x, q_t, scales_t, new_recon_t, new_res_t)."""
    t_half = t + g - g_prev
    zeros = jnp.zeros_like(g)
    ht, qt, sct, nrt, nst = wire_stage_ref(
        t_half, zeros, recon_t, res_t, alpha, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, dp_clip=dp_clip, dp_noise=dp_noise_t,
    )
    h, qx, scx, nrx, nsx = wire_stage_ref(
        x, t_half, recon_x, res_x, alpha, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, dp_clip=dp_clip, dp_noise=dp_noise,
    )
    del ht  # == t_half (zero gradient)
    return h, t_half, qx, scx, nrx, nsx, qt, sct, nrt, nst


def wire_stage_compact_ref(
    x: jnp.ndarray,
    g: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    scale_chunk: int,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGD compact wire-stage oracle: local update + difference coding +
    EXACT-k selection + int8 quantize + EF. Returns (h, q int8
    (n, n_chunks*k), pos int16/int32 (n, n_chunks*k), scales
    (n, n_chunks), new_recon, new_res) -- only (q, pos, scales) cross the
    wire; :func:`scatter_compact_dq` rebuilds the dense dq on the
    receiver. Note the privacy-positive DP ordering: top-k selection
    runs on the NOISED wire, so the selection pattern leaks nothing
    about the raw coordinates."""
    n, t = x.shape
    if t % scale_chunk:
        raise ValueError(f"total {t} not a multiple of scale_chunk {scale_chunk}")
    if topk is None or not (1 <= topk < scale_chunk):
        raise ValueError(
            f"the compact wire needs 1 <= topk < scale_chunk, got "
            f"topk={topk}, scale_chunk={scale_chunk}"
        )
    h = x - alpha * g
    base = recon if difference_coding else jnp.zeros_like(recon)
    payload = h - base + (res if error_feedback else 0.0)
    wire = _dp_wire(payload, dp_clip, dp_noise, error_feedback)
    q, pos, scales, dq = _quantize_ef_compact_chunks(wire, scale_chunk, topk)
    new_recon = base + dq
    new_res = payload - dq if error_feedback else res
    return (h, q.astype(jnp.int8), pos.astype(compact_pos_dtype(scale_chunk)),
            scales, new_recon, new_res)


def wire_stage_gt_compact_ref(
    x: jnp.ndarray,
    t: jnp.ndarray,
    g: jnp.ndarray,
    g_prev: jnp.ndarray,
    recon_x: jnp.ndarray,
    res_x: jnp.ndarray,
    recon_t: jnp.ndarray,
    res_t: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    scale_chunk: int,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
    dp_noise_t: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGT compact wire-stage oracle: tracker arithmetic + parameter
    update + both wires' compact-gather quantize-EF. Returns (h, t_half,
    q_x, pos_x, scales_x, new_recon_x, new_res_x, q_t, pos_t, scales_t,
    new_recon_t, new_res_t)."""
    t_half = t + g - g_prev
    zeros = jnp.zeros_like(g)
    ht, qt, pt, sct, nrt, nst = wire_stage_compact_ref(
        t_half, zeros, recon_t, res_t, alpha, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, dp_clip=dp_clip, dp_noise=dp_noise_t,
    )
    h, qx, px, scx, nrx, nsx = wire_stage_compact_ref(
        x, t_half, recon_x, res_x, alpha, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, dp_clip=dp_clip, dp_noise=dp_noise,
    )
    del ht  # == t_half (zero gradient)
    return h, t_half, qx, px, scx, nrx, nsx, qt, pt, sct, nrt, nst
