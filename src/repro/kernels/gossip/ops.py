"""jit'd dispatch for the fused gossip / round megakernels.

Every entry point resolves Pallas ``interpret`` mode OUTSIDE the jit
through :func:`repro.kernels.pallas_interpret` (never on a TPU backend,
the default everywhere else).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import pallas_interpret
from repro.kernels.gossip.gossip import (
    fused_round_gt_pallas,
    fused_round_pallas,
    gossip_mix_pallas,
    wire_stage_compact_pallas,
    wire_stage_gt_compact_pallas,
    wire_stage_gt_pallas,
    wire_stage_pallas,
)

__all__ = ["gossip_mix", "fused_round", "fused_round_gt", "wire_stage",
           "wire_stage_gt", "wire_stage_compact", "wire_stage_gt_compact",
           "require_topk_lowering"]


def require_topk_lowering(topk) -> None:
    """Refuse a top-k wire where the kernels compile for the chip.

    The top-k selections (``jnp.sort`` for the masked wire,
    ``jax.lax.top_k`` for the compact one) have no Mosaic lowering, so
    on a TPU the sparsified kernels cannot be built. There is no dense
    or jnp substitute: the caller drops ``topk``, or runs the jnp oracle
    (``impl="jnp"``) knowingly.
    """
    if topk is not None and not pallas_interpret():
        raise NotImplementedError(
            f"topk={topk}: the Pallas top-k wire does not compile for the "
            "chip (Mosaic has no lowering for sort / top_k); drop topk for "
            "the dense int8 wire"
        )


def _dp_substitute(h, base, res, dp_clip, dp_noise):
    """Residual substitution: fold the DP clip + noise epilogue into the
    UNCHANGED Pallas kernels.

    The kernels compute their wire payload as ``(h - base) + res``. For
    DP we want them to quantize ``wire = cs * payload + noise`` instead
    (per-node L2 clip scale ``cs``, pre-scaled Gaussian ``noise`` --
    bitwise the same formula as ``ref._dp_wire``). Substituting
    ``res_sub = res + (wire - payload)`` makes the kernel's payload equal
    ``wire`` (to 1 ulp of float association), so its q / scales / recon
    outputs are the DP wire's -- ONE pallas_call per round is preserved
    and the kernel bodies never learn about privacy. The kernel's EF
    residual is then ``wire - dq``; adding the returned ``correction =
    payload - wire`` restores the true residual ``payload - dq``, i.e.
    error feedback absorbs clip + noise + quantization together.

    Requires error feedback: without it the kernel's payload is
    ``h - base`` with no residual term to substitute through, and the
    perturbation would accumulate as an uncorrected walk.
    """
    payload = (h - base) + res
    nrm = jnp.sqrt(jnp.sum(payload * payload, axis=1, keepdims=True))
    cs = jnp.minimum(
        1.0, jnp.asarray(dp_clip, jnp.float32)
        / jnp.maximum(nrm, jnp.float32(1e-12))
    )
    wire = cs * payload + dp_noise
    return res + (wire - payload), payload - wire


def _require_ef_for_dp(error_feedback: bool) -> None:
    if not error_feedback:
        raise ValueError(
            "dp needs error_feedback=True: the residual is what absorbs "
            "the clip + noise perturbation (otherwise the wire walk "
            "diverges from the parameters)"
        )


@functools.partial(
    jax.jit,
    static_argnames=("scale_chunk", "error_feedback", "difference_coding",
                     "topk", "stale_mix", "interpret"),
)
def _gossip_mix(x, recon, res, w_off, w_self, scale_chunk, error_feedback,
                difference_coding, topk, stale_mix, interpret):
    return gossip_mix_pallas(
        x,
        recon,
        res,
        w_off,
        w_self,
        scale_chunk=scale_chunk,
        error_feedback=error_feedback,
        difference_coding=difference_coding,
        topk=topk,
        stale_mix=stale_mix,
        interpret=interpret,
    )


def gossip_mix(
    x: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    w_off: jnp.ndarray,
    w_self: jnp.ndarray,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    stale_mix: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One fused quantize -> W-row mix -> dequant + EF gossip round on the
    flat node-stacked state.

    Shapes and dtypes (n = nodes, t = flat width, c = t // scale_chunk):

      x      (n, t) fp32   node-stacked flat parameters (``core.packing``);
                           t must be a multiple of ``scale_chunk`` -- pack
                           with ``pad_to=scale_chunk`` -- else ValueError,
                           exactly like the jnp reference.
      recon  (n, t) fp32   shared reconstruction theta_hat: what every
                           neighbor can rebuild from wire traffic alone.
      res    (n, t) fp32   error-feedback residual.
      w_off  (n, n) fp32   off-diagonal mixing weights (zero diagonal).
      w_self (n,)   fp32   self weights (the W diagonal).

    Returns ``(mixed, new_recon, new_res, scales)``:

      mixed      (n, t) fp32  ``W_off @ new_recon + w_self * x`` -- the
                              gossip output; neighbors are mixed through
                              their reconstructions (what actually crossed
                              the wire), self through the exact value.
      new_recon  (n, t) fp32  ``recon + dequant(q)``; both endpoints of
                              every edge advance it identically, so it
                              never needs (re)transmission.
      new_res    (n, t) fp32  ``payload - dequant(q)``: the quantization
                              error, re-injected into the NEXT round's
                              payload (error feedback). With EF +
                              difference coding the payload magnitude --
                              and hence the int8 step -- vanishes as
                              consensus is approached, so mixing becomes
                              exact in the limit; without EF the round
                              stalls at an O(max|x|/127/gap) floor.
      scales     (n, c) fp32  per-(node, chunk) symmetric int8 scales --
                              the only fp32 values on the wire (4 bytes
                              per ``scale_chunk`` int8 payload bytes).

    Flags: ``difference_coding=False`` quantizes x itself instead of the
    delta against ``recon``; ``error_feedback=False`` passes ``res``
    through untouched; ``topk=k`` ships only the k largest-|payload|
    columns per scale chunk (EF absorbs the truncation -- sub-int8 wire
    bytes, see ``packing.flat_wire_bytes``); ``stale_mix=True`` mixes
    against the INPUT recon (the pipelined schedule's one-round-stale
    neighbor information).
    """
    return _gossip_mix(
        x, recon, res, w_off, w_self, scale_chunk, error_feedback,
        difference_coding, topk, stale_mix, pallas_interpret(),
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale_chunk", "error_feedback", "difference_coding",
                     "topk", "stale_mix", "interpret"),
)
def _fused_round(x, g, recon, res, w_off, w_self, alpha, scale_chunk,
                 error_feedback, difference_coding, topk, stale_mix,
                 interpret):
    return fused_round_pallas(
        x,
        g,
        recon,
        res,
        w_off,
        w_self,
        alpha,
        scale_chunk=scale_chunk,
        error_feedback=error_feedback,
        difference_coding=difference_coding,
        topk=topk,
        stale_mix=stale_mix,
        interpret=interpret,
    )


def fused_round(
    x: jnp.ndarray,
    g: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    w_off: jnp.ndarray,
    w_self: jnp.ndarray,
    alpha: jnp.ndarray,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    stale_mix: bool = False,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """DSGD round megakernel: ``h = x - alpha * g`` fused ahead of
    :func:`gossip_mix` in ONE Pallas pass -- one kernel call is a whole
    communication round over the flat state.

    ``g`` is the flat gradient buffer (same (n, t) layout as x, packed by
    ``core.packing.pack_like``); ``alpha`` the scalar step size. Remaining
    operands, outputs, EF, ``topk`` and ``stale_mix`` semantics exactly
    as :func:`gossip_mix` applied to h. ``dp_clip``/``dp_noise`` turn on
    the differential-privacy wire epilogue via residual substitution
    (:func:`_dp_substitute`) -- still ONE pallas_call.
    """
    if dp_noise is None:
        return _fused_round(
            x, g, recon, res, w_off, w_self, alpha, scale_chunk,
            error_feedback, difference_coding, topk, stale_mix,
            pallas_interpret(),
        )
    _require_ef_for_dp(error_feedback)
    h = x - alpha * g
    base = recon if difference_coding else jnp.zeros_like(recon)
    res_sub, corr = _dp_substitute(h, base, res, dp_clip, dp_noise)
    mixed, new_recon, new_res, scales = _fused_round(
        x, g, recon, res_sub, w_off, w_self, alpha, scale_chunk,
        error_feedback, difference_coding, topk, stale_mix,
        pallas_interpret(),
    )
    return mixed, new_recon, new_res + corr, scales


@functools.partial(
    jax.jit,
    static_argnames=("scale_chunk", "error_feedback", "difference_coding",
                     "topk", "stale_mix", "interpret"),
)
def _fused_round_gt(x, t, g, g_prev, recon_x, res_x, recon_t, res_t, w_off,
                    w_self, alpha, scale_chunk, error_feedback,
                    difference_coding, topk, stale_mix, interpret):
    return fused_round_gt_pallas(
        x,
        t,
        g,
        g_prev,
        recon_x,
        res_x,
        recon_t,
        res_t,
        w_off,
        w_self,
        alpha,
        scale_chunk=scale_chunk,
        error_feedback=error_feedback,
        difference_coding=difference_coding,
        topk=topk,
        stale_mix=stale_mix,
        interpret=interpret,
    )


def fused_round_gt(
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
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    stale_mix: bool = False,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
    dp_noise_t: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGT round megakernel: tracker arithmetic ``t_half = t + g - g_prev``,
    parameter update ``h = x - alpha * t_half``, and the quantize-mix-EF
    stage applied to BOTH buffers, in ONE Pallas pass.

    ``(recon_x, res_x)`` / ``(recon_t, res_t)`` are independent compression
    states for the parameter and tracker wires (both travel int8). Returns
    ``(mixed_x, mixed_t, new_recon_x, new_res_x, new_recon_t, new_res_t,
    scales_x, scales_t)``; store ``g`` as the next round's ``g_prev``. See
    ``ref.fused_round_gt_ref`` for the exact update equations;
    ``stale_mix`` mixes both wires against their input recons.
    ``dp_clip``/``dp_noise``/``dp_noise_t`` turn on the DP epilogue on
    both wires via residual substitution -- still ONE pallas_call.
    """
    if dp_noise is None:
        return _fused_round_gt(
            x, t, g, g_prev, recon_x, res_x, recon_t, res_t, w_off, w_self,
            alpha, scale_chunk, error_feedback, difference_coding, topk,
            stale_mix, pallas_interpret(),
        )
    _require_ef_for_dp(error_feedback)
    t_half = t + g - g_prev
    h = x - alpha * t_half
    base_x = recon_x if difference_coding else jnp.zeros_like(recon_x)
    base_t = recon_t if difference_coding else jnp.zeros_like(recon_t)
    res_x_sub, corr_x = _dp_substitute(h, base_x, res_x, dp_clip, dp_noise)
    res_t_sub, corr_t = _dp_substitute(
        t_half, base_t, res_t, dp_clip, dp_noise_t
    )
    mx, mt, nrx, nsx, nrt, nst, scx, sct = _fused_round_gt(
        x, t, g, g_prev, recon_x, res_x_sub, recon_t, res_t_sub, w_off,
        w_self, alpha, scale_chunk, error_feedback, difference_coding, topk,
        stale_mix, pallas_interpret(),
    )
    return mx, mt, nrx, nsx + corr_x, nrt, nst + corr_t, scx, sct


@functools.partial(
    jax.jit,
    static_argnames=("scale_chunk", "error_feedback", "difference_coding",
                     "topk", "interpret"),
)
def _wire_stage(x, g, recon, res, alpha, scale_chunk, error_feedback,
                difference_coding, topk, interpret):
    return wire_stage_pallas(
        x, g, recon, res, alpha, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, interpret=interpret,
    )


def wire_stage(
    x: jnp.ndarray,
    g: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    alpha: jnp.ndarray,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGD wire stage of the sharded fused round (pre-collective half):
    local update + difference coding + (top-k) int8 quantize + EF in ONE
    Pallas pass on this shard's rows, with the optional DP clip+noise
    epilogue via residual substitution. Returns (h, q int8, scales,
    new_recon, new_res); see ``core.engine.ShardedFusedEngine`` for the
    post-wire mix."""
    if dp_noise is None:
        return _wire_stage(
            x, g, recon, res, alpha, scale_chunk, error_feedback,
            difference_coding, topk, pallas_interpret(),
        )
    _require_ef_for_dp(error_feedback)
    h = x - alpha * g
    base = recon if difference_coding else jnp.zeros_like(recon)
    res_sub, corr = _dp_substitute(h, base, res, dp_clip, dp_noise)
    h_out, q, scales, new_recon, new_res = _wire_stage(
        x, g, recon, res_sub, alpha, scale_chunk, error_feedback,
        difference_coding, topk, pallas_interpret(),
    )
    return h_out, q, scales, new_recon, new_res + corr


@functools.partial(
    jax.jit,
    static_argnames=("scale_chunk", "error_feedback", "difference_coding",
                     "topk", "interpret"),
)
def _wire_stage_gt(x, t, g, g_prev, recon_x, res_x, recon_t, res_t, alpha,
                   scale_chunk, error_feedback, difference_coding, topk,
                   interpret):
    return wire_stage_gt_pallas(
        x, t, g, g_prev, recon_x, res_x, recon_t, res_t, alpha,
        scale_chunk=scale_chunk, error_feedback=error_feedback,
        difference_coding=difference_coding, topk=topk, interpret=interpret,
    )


def wire_stage_gt(
    x: jnp.ndarray,
    t: jnp.ndarray,
    g: jnp.ndarray,
    g_prev: jnp.ndarray,
    recon_x: jnp.ndarray,
    res_x: jnp.ndarray,
    recon_t: jnp.ndarray,
    res_t: jnp.ndarray,
    alpha: jnp.ndarray,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
    dp_noise_t: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGT wire stage of the sharded fused round: tracker arithmetic +
    parameter update + both wires' quantize-EF in ONE Pallas pass, with
    the optional DP epilogue on both wires via residual substitution.
    Returns (h, t_half, q_x, scales_x, new_recon_x, new_res_x, q_t,
    scales_t, new_recon_t, new_res_t)."""
    if dp_noise is None:
        return _wire_stage_gt(
            x, t, g, g_prev, recon_x, res_x, recon_t, res_t, alpha,
            scale_chunk, error_feedback, difference_coding, topk,
            pallas_interpret(),
        )
    _require_ef_for_dp(error_feedback)
    t_half = t + g - g_prev
    h = x - alpha * t_half
    base_x = recon_x if difference_coding else jnp.zeros_like(recon_x)
    base_t = recon_t if difference_coding else jnp.zeros_like(recon_t)
    res_x_sub, corr_x = _dp_substitute(h, base_x, res_x, dp_clip, dp_noise)
    res_t_sub, corr_t = _dp_substitute(
        t_half, base_t, res_t, dp_clip, dp_noise_t
    )
    (h_out, th, qx, scx, nrx, nsx, qt, sct, nrt, nst) = _wire_stage_gt(
        x, t, g, g_prev, recon_x, res_x_sub, recon_t, res_t_sub, alpha,
        scale_chunk, error_feedback, difference_coding, topk,
        pallas_interpret(),
    )
    return h_out, th, qx, scx, nrx, nsx + corr_x, qt, sct, nrt, nst + corr_t


@functools.partial(
    jax.jit,
    static_argnames=("scale_chunk", "error_feedback", "difference_coding",
                     "topk", "bitmap", "interpret"),
)
def _wire_stage_compact(x, g, recon, res, alpha, scale_chunk, error_feedback,
                        difference_coding, topk, bitmap, interpret):
    return wire_stage_compact_pallas(
        x, g, recon, res, alpha, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, bitmap=bitmap, interpret=interpret,
    )


def wire_stage_compact(
    x: jnp.ndarray,
    g: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    alpha: jnp.ndarray,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    bitmap: bool = False,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGD wire stage with the compact-gather epilogue (the truly sparse
    top-k wire): local update + difference coding + EXACT-k selection +
    int8 quantize + EF in ONE Pallas pass, with the optional DP epilogue
    via residual substitution (selection runs on the NOISED wire -- the
    sparsity pattern itself is privatized). Returns (h, q int8
    (n, n_chunks*k), pos int16/int32, scales, new_recon, new_res); only
    (q, pos, scales) cross the collective and
    ``ref.scatter_compact_dq`` rebuilds the dense dq on the receiver.
    ``bitmap=True`` folds the bitmap re-encode into the same kernel: the
    index output is the packed presence bitmap (uint8, chunk/8 per
    chunk), decoded by ``ref.scatter_bitmap_dq``."""
    if dp_noise is None:
        return _wire_stage_compact(
            x, g, recon, res, alpha, scale_chunk, error_feedback,
            difference_coding, topk, bitmap, pallas_interpret(),
        )
    _require_ef_for_dp(error_feedback)
    h = x - alpha * g
    base = recon if difference_coding else jnp.zeros_like(recon)
    res_sub, corr = _dp_substitute(h, base, res, dp_clip, dp_noise)
    h_out, q, pos, scales, new_recon, new_res = _wire_stage_compact(
        x, g, recon, res_sub, alpha, scale_chunk, error_feedback,
        difference_coding, topk, bitmap, pallas_interpret(),
    )
    return h_out, q, pos, scales, new_recon, new_res + corr


@functools.partial(
    jax.jit,
    static_argnames=("scale_chunk", "error_feedback", "difference_coding",
                     "topk", "bitmap", "interpret"),
)
def _wire_stage_gt_compact(x, t, g, g_prev, recon_x, res_x, recon_t, res_t,
                           alpha, scale_chunk, error_feedback,
                           difference_coding, topk, bitmap, interpret):
    return wire_stage_gt_compact_pallas(
        x, t, g, g_prev, recon_x, res_x, recon_t, res_t, alpha,
        scale_chunk=scale_chunk, error_feedback=error_feedback,
        difference_coding=difference_coding, topk=topk, bitmap=bitmap,
        interpret=interpret,
    )


def wire_stage_gt_compact(
    x: jnp.ndarray,
    t: jnp.ndarray,
    g: jnp.ndarray,
    g_prev: jnp.ndarray,
    recon_x: jnp.ndarray,
    res_x: jnp.ndarray,
    recon_t: jnp.ndarray,
    res_t: jnp.ndarray,
    alpha: jnp.ndarray,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    bitmap: bool = False,
    dp_clip: float | None = None,
    dp_noise: jnp.ndarray | None = None,
    dp_noise_t: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """DSGT wire stage with the compact-gather epilogue on BOTH wires, in
    ONE Pallas pass, with the optional DP epilogue via residual
    substitution. Returns (h, t_half, q_x, pos_x, scales_x, new_recon_x,
    new_res_x, q_t, pos_t, scales_t, new_recon_t, new_res_t).
    ``bitmap=True`` folds the bitmap re-encode into the kernel on both
    wires (index outputs become packed presence bitmaps)."""
    if dp_noise is None:
        return _wire_stage_gt_compact(
            x, t, g, g_prev, recon_x, res_x, recon_t, res_t, alpha,
            scale_chunk, error_feedback, difference_coding, topk, bitmap,
            pallas_interpret(),
        )
    _require_ef_for_dp(error_feedback)
    t_half = t + g - g_prev
    h = x - alpha * t_half
    base_x = recon_x if difference_coding else jnp.zeros_like(recon_x)
    base_t = recon_t if difference_coding else jnp.zeros_like(recon_t)
    res_x_sub, corr_x = _dp_substitute(h, base_x, res_x, dp_clip, dp_noise)
    res_t_sub, corr_t = _dp_substitute(
        t_half, base_t, res_t, dp_clip, dp_noise_t
    )
    (h_out, th, qx, px, scx, nrx, nsx,
     qt, pt, sct, nrt, nst) = _wire_stage_gt_compact(
        x, t, g, g_prev, recon_x, res_x_sub, recon_t, res_t_sub, alpha,
        scale_chunk, error_feedback, difference_coding, topk, bitmap,
        pallas_interpret(),
    )
    return (h_out, th, qx, px, scx, nrx, nsx + corr_x,
            qt, pt, sct, nrt, nst + corr_t)
