"""Fused gossip + round megakernel bodies -- Pallas.

Grid = (cdiv(n_chunks, cpb),): each program owns ``cpb`` consecutive
scale chunks, i.e. one ``(nodes, cpb * scale_chunk)`` column block of the
flat state. Compressed gossip is columnwise-independent -- the int8 scale
is per (node, chunk), the W contraction runs over the nodes axis that is
fully resident in the tile, and the local-update / EF arithmetic is
elementwise -- so inside the tile the block is viewed as
``(nodes, cpb, scale_chunk)`` and every per-chunk reduction runs over the
last axis, exactly as the jnp oracles in ``ref.py`` compute it. Per tile
the shared quantize-mix stage computes, entirely in VMEM with no
materialized full-size intermediates:

    payload = x - recon + res            (difference coding + EF)
    s       = max|payload| / 127         per (node, chunk)  <- wire scales
    q       = clip(round(payload / s))                      <- wire payload
    dq      = q * s
    recon'  = recon + dq
    res'    = payload - dq
    mixed   = W_off @ recon' + w_self * x    (MXU: (n,n) x (n,cols))

Several chunks per step keep every block lane-aligned on a TPU: the data
block is ``cpb * scale_chunk`` columns wide, and the per-step scales leave
as one ``(1, n, cpb)`` block of an ``(n_steps, n, cpb)`` buffer (its last
two dims are whole, so any ``n`` and ``cpb`` are legal) that the wrapper
re-lays to ``(n, n_chunks)``. ``cpb`` is sized from ``n`` and the
kernel's buffer count so the double-buffered blocks stay inside
:data:`VMEM_BLOCK_BYTES`; a last partial step is masked by the pipeline.

Five kernels share that stage:

* :func:`gossip_mix_pallas` -- the stage alone (PR 1's fused
  quantize-mix-EF gossip round);
* :func:`fused_round_pallas` -- the DSGD **round megakernel**: the local
  update ``h = x - alpha * g`` runs in-register ahead of the stage, so one
  kernel call is a whole communication round (update + quantize + mix +
  EF) over the flat state;
* :func:`fused_round_gt_pallas` -- the DSGT round megakernel: tracker
  arithmetic ``t_half = t + g - g_prev``, parameter update
  ``h = x - alpha * t_half``, then the quantize-mix stage applied to BOTH
  buffers inside the same program (two MXU contractions against the same
  resident W tile);
* :func:`wire_stage_pallas` / :func:`wire_stage_gt_pallas` -- the
  SHARDED fused round's pre-collective half: everything above EXCEPT the
  W contraction (update + diff-code + top-k + int8 quantize + EF),
  emitting the int8 payload + fp32 scales that cross the wire; the mix
  finishes outside the kernel against the engine's running
  neighbor-reconstruction accumulator (``core.engine.ShardedFusedEngine``);
* :func:`wire_stage_compact_pallas` / :func:`wire_stage_gt_compact_pallas`
  -- the TRULY SPARSE top-k wire: the same wire stage with a
  compact-gather epilogue. Selection is EXACT-k (``jax.lax.top_k`` on
  |payload|, ties broken toward the lower index -- identically in the jnp
  oracle), and the tile emits ``(k int8 values, k in-chunk positions,
  one fp32 scale)`` per scale chunk instead of the masked-dense buffer.
  Only those compact buffers cross the collective; the receive side
  scatter-accumulates them back to dense (``ref.scatter_compact_dq``)
  before the W contraction. The EF/recon updates still use the full
  dense dequant (computed in-tile -- dq never hits the wire), so masking
  defers signal exactly as in the masked-dense path. With
  ``bitmap=True`` the tile ALSO runs the bitmap re-encode epilogue
  in-kernel (argsort the k survivors into ascending-position order +
  bit-pack the presence bitmap, ``chunk/8`` uint8 per chunk) -- the
  same math ``ref.compact_to_bitmap`` used to apply as jnp
  post-processing outside the kernel, now fused into the same program
  so the wire operands leave the kernel collective-ready (bit-identical
  buffers, same single pallas_call).

The quantize-mix kernels additionally take ``stale_mix`` (the PIPELINED
round schedule): the W contraction runs against the INPUT ``recon`` --
the reconstruction every neighbor had already advanced to at the END of
the previous round -- instead of ``new_recon``, so the mix consumes
one-round-stale neighbor information while this round's payload is still
"in flight". ``new_recon`` advances regardless (both endpoints replay
the wire), which is what makes stale mixing exactly the
sequential-with-one-round-delay dynamics.

Replacing the unfused path's full-size fp32 intermediates (the updated
parameters h, payload, dq, recon') with one HBM read of each input and one
write of each output; the recon / residual (and, on the wire stages, the
parameter) inputs are aliased to their updated outputs, so a caller that
donates its state updates it in place. ``alpha`` rides along as a (1, 1)
operand mapped to every program. The jnp oracles in ``ref.py`` are
bit-identical math (interpret-mode property tests in
tests/test_gossip_flat.py and tests/test_megakernel.py);
tests/test_tpu_compile.py compiles the round kernels for a TPU v5e at
real widths.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "gossip_mix_pallas",
    "fused_round_pallas",
    "fused_round_gt_pallas",
    "wire_stage_pallas",
    "wire_stage_gt_pallas",
    "wire_stage_compact_pallas",
    "wire_stage_gt_compact_pallas",
]

#: Budget for one grid step's double-buffered data blocks. Half of the
#: 16 MiB scoped-VMEM default on TPU v5e: the other half holds the
#: in-tile temporaries (payload, dq, the 3-D chunk views).
VMEM_BLOCK_BYTES = 8 * 2**20

#: The W contraction runs at full fp32 precision (multi-pass on the MXU)
#: in the kernels and in the jnp oracles alike, so the Pallas and jnp
#: rounds agree to fp32 rounding on a TPU, where the default f32 matmul
#: precision is a single bf16 pass.
MIX_PRECISION = jax.lax.Precision.HIGHEST


def _chunks_per_step(n: int, n_chunks: int, scale_chunk: int,
                     n_buffers: int) -> int:
    """Scale chunks per grid step: as many as fit ``n_buffers``
    double-buffered fp32 ``(n, cols)`` blocks (rows padded to the 8-row
    sublane tile) into :data:`VMEM_BLOCK_BYTES`, rounded so the block
    width stays a multiple of the 128-lane tile."""
    rows = -(-n // 8) * 8
    per_chunk = 2 * n_buffers * rows * scale_chunk * 4
    cpb = max(1, VMEM_BLOCK_BYTES // per_chunk)
    if cpb >= n_chunks:
        return n_chunks
    lane = 128 // math.gcd(scale_chunk, 128)
    return min(n_chunks, max(lane, cpb // lane * lane))


def _chunks3(a, scale_chunk: int):
    """View a ``(n, cpb * scale_chunk)`` tile as ``(n, cpb, scale_chunk)``."""
    n, w = a.shape
    return a.reshape(n, w // scale_chunk, scale_chunk)


def _topk_mask(p3, topk):
    """Keep only the ``topk`` largest-|.| columns of each (node, chunk)
    row of a ``(n, cpb, chunk)`` view; everything else becomes a
    structural zero on the wire (ties at the threshold are all kept --
    deterministic, and shared bit-for-bit with the jnp oracle which
    applies the same formula chunk-by-chunk). ``topk >= chunk`` disables
    the mask."""
    chunk = p3.shape[-1]
    if topk is None or topk >= chunk:
        return p3
    thr = jnp.sort(jnp.abs(p3), axis=-1)[..., chunk - topk][..., None]
    return jnp.where(jnp.abs(p3) >= thr, p3, 0.0)


def _quantize_ef(x, recon, res, *, scale_chunk, error_feedback,
                 difference_coding, topk):
    """Difference-code, (optionally top-k mask,) int8-quantize, and EF
    update of ONE (nodes, cpb * chunk) tile -- everything that happens
    BEFORE the wire. Returns (payload_q as fp32 ints, scale (n, cpb),
    new_recon, new_res). With top-k the EF residual absorbs the truncated
    mass (payload - dq is the FULL payload minus the sparse dequant), so
    masking never loses signal, it only defers it."""
    base = recon if difference_coding else jnp.zeros_like(recon)
    payload = x - base
    if error_feedback:
        payload = payload + res

    sel = _topk_mask(_chunks3(payload, scale_chunk), topk)
    scale = jnp.max(jnp.abs(sel), axis=-1) / 127.0  # (n, cpb)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(sel / safe[:, :, None]), -127, 127)
    dq = (q * scale[:, :, None]).reshape(payload.shape)

    new_recon = base + dq
    new_res = payload - dq if error_feedback else res
    return q.reshape(payload.shape), scale, new_recon, new_res


def _topk_gather(p3, topk):
    """EXACT-k selection per (node, chunk) row of a ``(n, cpb, chunk)``
    view: the values and in-chunk positions of the k largest-|.| columns
    (``jax.lax.top_k`` on |payload|; ties broken toward the lower index,
    deterministically and identically in the jnp oracle). Unlike
    :func:`_topk_mask` this never keeps threshold ties beyond k -- the
    compact wire has exactly k slots per chunk."""
    _, idx = jax.lax.top_k(jnp.abs(p3), topk)  # (n, cpb, k) int32
    vals = jnp.take_along_axis(p3, idx, axis=-1)
    return vals, idx


def _quantize_ef_compact(x, recon, res, *, scale_chunk, error_feedback,
                         difference_coding, topk):
    """Compact-gather variant of :func:`_quantize_ef`: exact-k selection,
    int8 quantization of the k SURVIVORS only, and the dense dq scattered
    back in-tile for the recon/EF updates (dq never crosses the wire).
    Returns (q (n, cpb, k) as fp32 ints, pos (n, cpb, k) int32, scale
    (n, cpb), new_recon, new_res)."""
    base = recon if difference_coding else jnp.zeros_like(recon)
    payload = x - base
    if error_feedback:
        payload = payload + res

    p3 = _chunks3(payload, scale_chunk)
    vals, pos = _topk_gather(p3, topk)
    scale = jnp.max(jnp.abs(vals), axis=-1) / 127.0  # (n, cpb)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(vals / safe[:, :, None]), -127, 127)

    rows = jax.lax.broadcasted_iota(jnp.int32, pos.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, pos.shape, 1)
    dq = jnp.zeros_like(p3).at[rows, cols, pos].add(q * scale[:, :, None])
    dq = dq.reshape(payload.shape)

    new_recon = base + dq
    new_res = payload - dq if error_feedback else res
    return q, pos, scale, new_recon, new_res


def _bitmap_pack(q, pos, scale_chunk):
    """In-tile bitmap re-encode of ONE compact (n, cpb, k) selection:
    re-sort the k survivors of every chunk into ascending-position order
    and bit-pack the LSB-first presence bitmap (``scale_chunk // 8``
    uint8 per chunk) -- the same formula as ``ref.compact_to_bitmap``,
    bit-identical, so the emitted buffers ARE the collective operands.
    Positions within a chunk are distinct, so the argsort order is
    unambiguous. Returns (vals (n, cpb, k) fp32 ints, bits
    (n, cpb, chunk//8) uint8)."""
    order = jnp.argsort(pos, axis=-1)
    vals = jnp.take_along_axis(q, order, axis=-1)
    n, cpb, _ = pos.shape
    one_hot = jnp.zeros((n, cpb, scale_chunk), jnp.uint8)
    r_i = jax.lax.broadcasted_iota(jnp.int32, pos.shape, 0)
    c_i = jax.lax.broadcasted_iota(jnp.int32, pos.shape, 1)
    one_hot = one_hot.at[r_i, c_i, pos].set(1)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    bits = jnp.sum(
        one_hot.reshape(n, cpb, scale_chunk // 8, 8) * weights,
        axis=-1, dtype=jnp.uint8,
    )
    return vals, bits


def _mix(woff, nbr, wself, x):
    return jnp.dot(woff, nbr, preferred_element_type=jnp.float32,
                   precision=MIX_PRECISION) + wself * x


def _quantize_mix(x, recon, res, woff, wself, *, scale_chunk,
                  error_feedback, difference_coding, topk=None,
                  stale_mix=False):
    """The shared in-VMEM stage: difference-code, int8-quantize (top-k
    sparsified when ``topk`` is set), W-row mix, and error-feedback update
    of ONE (nodes, cols) tile. Returns (mixed, new_recon, new_res, scale).

    ``stale_mix`` (the pipelined round schedule) contracts W against the
    INPUT recon -- the neighbor reconstruction as of the END of the
    previous round -- instead of ``new_recon``; the recon/EF updates are
    unchanged, so the wire semantics are identical, only the mix consumes
    one-round-stale neighbor information."""
    _, scale, new_recon, new_res = _quantize_ef(
        x, recon, res, scale_chunk=scale_chunk,
        error_feedback=error_feedback,
        difference_coding=difference_coding, topk=topk,
    )
    nbr = recon if stale_mix else new_recon
    return _mix(woff, nbr, wself, x), new_recon, new_res, scale


def _kernel(x_ref, recon_ref, res_ref, woff_ref, wself_ref,
            mixed_ref, nrecon_ref, nres_ref, scale_ref, **kw):
    mixed, nrecon, nres, scale = _quantize_mix(
        x_ref[...], recon_ref[...], res_ref[...], woff_ref[...],
        wself_ref[...], **kw,
    )
    mixed_ref[...] = mixed
    nrecon_ref[...] = nrecon
    nres_ref[...] = nres
    scale_ref[0] = scale


def _fused_round_kernel(x_ref, g_ref, recon_ref, res_ref, woff_ref,
                        wself_ref, alpha_ref, mixed_ref, nrecon_ref,
                        nres_ref, scale_ref, **kw):
    # DSGD local update fused ahead of the gossip stage: the half-updated
    # parameters h never touch HBM.
    h = x_ref[...] - alpha_ref[0, 0] * g_ref[...]
    mixed, nrecon, nres, scale = _quantize_mix(
        h, recon_ref[...], res_ref[...], woff_ref[...], wself_ref[...],
        **kw,
    )
    mixed_ref[...] = mixed
    nrecon_ref[...] = nrecon
    nres_ref[...] = nres
    scale_ref[0] = scale


def _fused_round_gt_kernel(x_ref, t_ref, g_ref, gp_ref, rx_ref, sx_ref,
                           rt_ref, st_ref, woff_ref, wself_ref, alpha_ref,
                           mx_ref, mt_ref, nrx_ref, nsx_ref, nrt_ref,
                           nst_ref, scx_ref, sct_ref, **kw):
    # DSGT (adapt-then-combine ordering): tracker absorbs the gradient
    # innovation, parameters step against the updated tracker, and BOTH
    # half-updated buffers go through the quantize-mix stage against the
    # same resident W tile. mean_i t_half preserves the tracking invariant
    # for any doubly-stochastic W.
    woff = woff_ref[...]
    wself = wself_ref[...]
    t_half = t_ref[...] + g_ref[...] - gp_ref[...]
    h = x_ref[...] - alpha_ref[0, 0] * t_half

    mt, nrt, nst, sct = _quantize_mix(
        t_half, rt_ref[...], st_ref[...], woff, wself, **kw,
    )
    mx, nrx, nsx, scx = _quantize_mix(
        h, rx_ref[...], sx_ref[...], woff, wself, **kw,
    )
    mx_ref[...] = mx
    mt_ref[...] = mt
    nrx_ref[...] = nrx
    nsx_ref[...] = nsx
    nrt_ref[...] = nrt
    nst_ref[...] = nst
    scx_ref[0] = scx
    sct_ref[0] = sct


class _Tiling:
    """Grid geometry of one kernel call over an (n, t) flat buffer."""

    def __init__(self, n: int, t: int, scale_chunk: int, n_buffers: int):
        if t % scale_chunk:
            raise ValueError(
                f"total {t} not a multiple of scale_chunk {scale_chunk}"
            )
        self.n = n
        self.n_chunks = t // scale_chunk
        self.cpb = _chunks_per_step(n, self.n_chunks, scale_chunk, n_buffers)
        self.steps = pl.cdiv(self.n_chunks, self.cpb)
        self.tile = self.cols(scale_chunk)
        self.whole = pl.BlockSpec((n, n), lambda c: (0, 0))
        self.one = pl.BlockSpec((n, 1), lambda c: (0, 0))
        self.scalar = pl.BlockSpec((1, 1), lambda c: (0, 0))
        self.col = pl.BlockSpec((1, n, self.cpb), lambda c: (c, 0, 0))
        self.scales = jax.ShapeDtypeStruct((self.steps, n, self.cpb),
                                           jnp.float32)

    def cols(self, per_chunk: int) -> pl.BlockSpec:
        """Block of ``per_chunk`` columns for each chunk of the step."""
        return pl.BlockSpec((self.n, self.cpb * per_chunk),
                            lambda c: (0, c))

    def scales_2d(self, s3: jnp.ndarray) -> jnp.ndarray:
        """Re-lay the kernel's (steps, n, cpb) scales as (n, n_chunks)."""
        s = jnp.moveaxis(s3, 0, 1).reshape(self.n, self.steps * self.cpb)
        return s[:, : self.n_chunks]


def _check_topk(topk) -> None:
    if topk is not None and topk < 1:
        raise ValueError(f"topk must be >= 1 or None, got {topk}")


def _alpha(alpha) -> jnp.ndarray:
    return jnp.asarray(alpha, jnp.float32).reshape(1, 1)


def gossip_mix_pallas(
    x: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    w_off: jnp.ndarray,
    w_self: jnp.ndarray,
    *,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    stale_mix: bool = False,
    interpret: bool = False,
):
    """x, recon, res: (n, t) fp32 with t % scale_chunk == 0; w_off (n, n);
    w_self (n,). Returns (mixed, new_recon, new_res, scales (n, t//chunk)).
    ``topk`` keeps only the k largest-|.| payload columns per scale chunk
    (EF absorbs the truncation); ``stale_mix`` mixes against the INPUT
    recon (the pipelined schedule's one-round-stale neighbor info)."""
    n, t = x.shape
    _check_topk(topk)
    tl = _Tiling(n, t, scale_chunk, n_buffers=6)
    kernel = functools.partial(
        _kernel, scale_chunk=scale_chunk, error_feedback=error_feedback,
        difference_coding=difference_coding, topk=topk, stale_mix=stale_mix,
    )
    buf = jax.ShapeDtypeStruct((n, t), jnp.float32)
    mixed, nrecon, nres, s3 = pl.pallas_call(
        kernel,
        grid=(tl.steps,),
        in_specs=[tl.tile] * 3 + [tl.whole, tl.one],
        out_specs=[tl.tile] * 3 + [tl.col],
        out_shape=[buf, buf, buf, tl.scales],
        input_output_aliases={1: 1, 2: 2},
        interpret=interpret,
        name="gossip_mix",
    )(x, recon, res, w_off, w_self.reshape(n, 1))
    return mixed, nrecon, nres, tl.scales_2d(s3)


def fused_round_pallas(
    x: jnp.ndarray,
    g: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    w_off: jnp.ndarray,
    w_self: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    stale_mix: bool = False,
    interpret: bool = False,
):
    """DSGD round megakernel: ``h = x - alpha * g`` then quantize-mix-EF of
    h (top-k sparsified when ``topk`` is set; mixed against the input
    recon when ``stale_mix``), in ONE pass. x, g, recon, res: (n, t)
    fp32; alpha: scalar. Returns (mixed, new_recon, new_res, scales)."""
    n, t = x.shape
    _check_topk(topk)
    tl = _Tiling(n, t, scale_chunk, n_buffers=7)
    kernel = functools.partial(
        _fused_round_kernel, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, stale_mix=stale_mix,
    )
    buf = jax.ShapeDtypeStruct((n, t), jnp.float32)
    mixed, nrecon, nres, s3 = pl.pallas_call(
        kernel,
        grid=(tl.steps,),
        in_specs=[tl.tile] * 4 + [tl.whole, tl.one, tl.scalar],
        out_specs=[tl.tile] * 3 + [tl.col],
        out_shape=[buf, buf, buf, tl.scales],
        input_output_aliases={2: 1, 3: 2},
        interpret=interpret,
        name="gossip_fused_round",
    )(x, g, recon, res, w_off, w_self.reshape(n, 1), _alpha(alpha))
    return mixed, nrecon, nres, tl.scales_2d(s3)


def fused_round_gt_pallas(
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
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    stale_mix: bool = False,
    interpret: bool = False,
):
    """DSGT round megakernel: tracker arithmetic + parameter update + two
    quantize-mix-EF stages (params and tracker) in ONE pass. All array
    operands (n, tot) fp32 except w_off (n, n) / w_self (n,); alpha scalar.
    ``stale_mix`` mixes both wires against their input recons. Returns
    (mixed_x, mixed_t, new_recon_x, new_res_x, new_recon_t, new_res_t,
    scales_x, scales_t)."""
    n, tot = x.shape
    _check_topk(topk)
    tl = _Tiling(n, tot, scale_chunk, n_buffers=14)
    kernel = functools.partial(
        _fused_round_gt_kernel, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, stale_mix=stale_mix,
    )
    buf = jax.ShapeDtypeStruct((n, tot), jnp.float32)
    mx, mt, nrx, nsx, nrt, nst, scx, sct = pl.pallas_call(
        kernel,
        grid=(tl.steps,),
        in_specs=[tl.tile] * 8 + [tl.whole, tl.one, tl.scalar],
        out_specs=[tl.tile] * 6 + [tl.col, tl.col],
        out_shape=[buf] * 6 + [tl.scales, tl.scales],
        input_output_aliases={4: 2, 5: 3, 6: 4, 7: 5},
        interpret=interpret,
        name="gossip_fused_round_gt",
    )(x, t, g, g_prev, recon_x, res_x, recon_t, res_t, w_off,
      w_self.reshape(n, 1), _alpha(alpha))
    return mx, mt, nrx, nsx, nrt, nst, tl.scales_2d(scx), tl.scales_2d(sct)

# ---------------------------------------------------------------------------
# Wire-stage kernels: the pre-collective half of the SHARDED fused round
# ---------------------------------------------------------------------------


def _wire_stage_kernel(x_ref, g_ref, recon_ref, res_ref, alpha_ref,
                       h_ref, q_ref, scale_ref, nrecon_ref, nres_ref, **kw):
    # Everything a node computes BEFORE its payload crosses the wire:
    # local update, difference coding, (top-k,) int8 quantize, EF. The
    # int8 q + fp32 scales ARE the wire; the W contraction happens after
    # the collective (ppermute / all-gather) outside the kernel, against
    # the running neighbor-reconstruction accumulator.
    h = x_ref[...] - alpha_ref[0, 0] * g_ref[...]
    q, scale, nrecon, nres = _quantize_ef(
        h, recon_ref[...], res_ref[...], **kw,
    )
    h_ref[...] = h
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[0] = scale
    nrecon_ref[...] = nrecon
    nres_ref[...] = nres


def _wire_stage_gt_kernel(x_ref, t_ref, g_ref, gp_ref, rx_ref, sx_ref,
                          rt_ref, st_ref, alpha_ref, h_ref, th_ref, qx_ref,
                          scx_ref, nrx_ref, nsx_ref, qt_ref, sct_ref,
                          nrt_ref, nst_ref, **kw):
    # DSGT wire stage: tracker arithmetic + parameter update + BOTH wires'
    # quantize-EF in one program (same adapt-then-combine ordering as the
    # dense megakernel).
    t_half = t_ref[...] + g_ref[...] - gp_ref[...]
    h = x_ref[...] - alpha_ref[0, 0] * t_half
    qt, sct, nrt, nst = _quantize_ef(t_half, rt_ref[...], st_ref[...], **kw)
    qx, scx, nrx, nsx = _quantize_ef(h, rx_ref[...], sx_ref[...], **kw)
    h_ref[...] = h
    th_ref[...] = t_half
    qx_ref[...] = qx.astype(jnp.int8)
    scx_ref[0] = scx
    nrx_ref[...] = nrx
    nsx_ref[...] = nsx
    qt_ref[...] = qt.astype(jnp.int8)
    sct_ref[0] = sct
    nrt_ref[...] = nrt
    nst_ref[...] = nst


def wire_stage_pallas(
    x: jnp.ndarray,
    g: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    interpret: bool = False,
):
    """DSGD wire stage of the SHARDED fused round: local update + difference
    coding + (top-k) int8 quantize + EF on this shard's (n_local, t) rows,
    in ONE pass. Returns (h, q int8, scales, new_recon, new_res); the
    caller moves (q, scales) over the wire and finishes the mix as
    ``w_self * h + mix_recon + sum_nbr w * dequant(q, s)``. Runs inside a
    shard_map body, so n_local is typically 1 (one node row per
    device)."""
    n, t = x.shape
    _check_topk(topk)
    tl = _Tiling(n, t, scale_chunk, n_buffers=8)
    kernel = functools.partial(
        _wire_stage_kernel, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk,
    )
    buf = jax.ShapeDtypeStruct((n, t), jnp.float32)
    h, q, s3, nrecon, nres = pl.pallas_call(
        kernel,
        grid=(tl.steps,),
        in_specs=[tl.tile] * 4 + [tl.scalar],
        out_specs=[tl.tile, tl.tile, tl.col, tl.tile, tl.tile],
        out_shape=[buf, jax.ShapeDtypeStruct((n, t), jnp.int8), tl.scales,
                   buf, buf],
        input_output_aliases={0: 0, 2: 3, 3: 4},
        interpret=interpret,
        name="gossip_wire_stage",
    )(x, g, recon, res, _alpha(alpha))
    return h, q, tl.scales_2d(s3), nrecon, nres


def wire_stage_gt_pallas(
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
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    interpret: bool = False,
):
    """DSGT wire stage of the SHARDED fused round: tracker arithmetic,
    parameter update, and both wires' quantize-EF in ONE pass. Returns
    (h, t_half, q_x int8, scales_x, new_recon_x, new_res_x, q_t int8,
    scales_t, new_recon_t, new_res_t)."""
    n, tot = x.shape
    _check_topk(topk)
    tl = _Tiling(n, tot, scale_chunk, n_buffers=16)
    kernel = functools.partial(
        _wire_stage_gt_kernel, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk,
    )
    buf = jax.ShapeDtypeStruct((n, tot), jnp.float32)
    qb = jax.ShapeDtypeStruct((n, tot), jnp.int8)
    tile, col = tl.tile, tl.col
    (h, th, qx, scx, nrx, nsx, qt, sct, nrt, nst) = pl.pallas_call(
        kernel,
        grid=(tl.steps,),
        in_specs=[tile] * 8 + [tl.scalar],
        out_specs=[tile, tile, tile, col, tile, tile, tile, col, tile, tile],
        out_shape=[buf, buf, qb, tl.scales, buf, buf, qb, tl.scales, buf,
                   buf],
        input_output_aliases={0: 0, 1: 1, 4: 4, 5: 5, 6: 8, 7: 9},
        interpret=interpret,
        name="gossip_wire_stage_gt",
    )(x, t, g, g_prev, recon_x, res_x, recon_t, res_t, _alpha(alpha))
    return (h, th, qx, tl.scales_2d(scx), nrx, nsx, qt, tl.scales_2d(sct),
            nrt, nst)


# ---------------------------------------------------------------------------
# Compact-gather wire-stage kernels: the TRULY SPARSE top-k wire
# ---------------------------------------------------------------------------


def _check_compact(topk, scale_chunk: int, bitmap: bool) -> None:
    if topk is None or not (1 <= topk < scale_chunk):
        raise ValueError(
            f"the compact wire needs 1 <= topk < scale_chunk, got "
            f"topk={topk}, scale_chunk={scale_chunk} (use the dense wire "
            "stage when the payload is not sparsified)"
        )
    if bitmap and scale_chunk % 8:
        raise ValueError(
            f"bitmap wire needs a byte-aligned chunk, got {scale_chunk}"
        )


def _emit_compact(q_ref, idx_ref, q, pos, *, scale_chunk, pos_dtype,
                  bitmap):
    """Write one wire's compact (values, index) blocks: explicit in-chunk
    positions, or with ``bitmap`` the ascending-position values plus the
    packed presence bitmap."""
    n = q.shape[0]
    if bitmap:
        q, bits = _bitmap_pack(q, pos, scale_chunk)
        idx_ref[...] = bits.reshape(n, -1)
    else:
        idx_ref[...] = pos.reshape(n, -1).astype(pos_dtype)
    q_ref[...] = q.reshape(n, -1).astype(jnp.int8)


def _wire_stage_compact_kernel(x_ref, g_ref, recon_ref, res_ref, alpha_ref,
                               h_ref, q_ref, idx_ref, scale_ref, nrecon_ref,
                               nres_ref, *, pos_dtype, bitmap, **kw):
    # The compact-gather epilogue: the tile still computes the DENSE dq for
    # its own recon/EF updates, but what it emits for the wire is exactly
    # (k int8 values, k in-chunk positions, 1 fp32 scale) per chunk -- the
    # bytes flat_wire_bytes accounts are the bytes that cross the
    # collective. With ``bitmap`` the index side leaves as the packed
    # presence bitmap instead.
    h = x_ref[...] - alpha_ref[0, 0] * g_ref[...]
    q, pos, scale, nrecon, nres = _quantize_ef_compact(
        h, recon_ref[...], res_ref[...], **kw,
    )
    h_ref[...] = h
    _emit_compact(q_ref, idx_ref, q, pos, scale_chunk=kw["scale_chunk"],
                  pos_dtype=pos_dtype, bitmap=bitmap)
    scale_ref[0] = scale
    nrecon_ref[...] = nrecon
    nres_ref[...] = nres


def _wire_stage_gt_compact_kernel(x_ref, t_ref, g_ref, gp_ref, rx_ref,
                                  sx_ref, rt_ref, st_ref, alpha_ref, h_ref,
                                  th_ref, qx_ref, px_ref, scx_ref, nrx_ref,
                                  nsx_ref, qt_ref, pt_ref, sct_ref, nrt_ref,
                                  nst_ref, *, pos_dtype, bitmap, **kw):
    # DSGT compact wire stage: tracker arithmetic + parameter update + BOTH
    # wires' compact-gather quantize-EF in one program (both index sides
    # leave as packed bitmaps when ``bitmap``).
    t_half = t_ref[...] + g_ref[...] - gp_ref[...]
    h = x_ref[...] - alpha_ref[0, 0] * t_half
    qt, pt, sct, nrt, nst = _quantize_ef_compact(
        t_half, rt_ref[...], st_ref[...], **kw,
    )
    qx, px, scx, nrx, nsx = _quantize_ef_compact(
        h, rx_ref[...], sx_ref[...], **kw,
    )
    h_ref[...] = h
    th_ref[...] = t_half
    emit = functools.partial(_emit_compact, scale_chunk=kw["scale_chunk"],
                             pos_dtype=pos_dtype, bitmap=bitmap)
    emit(qx_ref, px_ref, qx, px)
    emit(qt_ref, pt_ref, qt, pt)
    scx_ref[0] = scx
    nrx_ref[...] = nrx
    nsx_ref[...] = nsx
    sct_ref[0] = sct
    nrt_ref[...] = nrt
    nst_ref[...] = nst


def _compact_geometry(tl: _Tiling, topk: int, scale_chunk: int,
                      bitmap: bool):
    """(value block, index block, value shape, index shape) of one
    compact wire: k values per chunk, and k positions or ``chunk // 8``
    bitmap bytes per chunk."""
    from repro.core.packing import compact_pos_dtype

    n, c = tl.n, tl.n_chunks
    if bitmap:
        idx_width, idx_dtype = scale_chunk // 8, jnp.uint8
    else:
        idx_width, idx_dtype = topk, compact_pos_dtype(scale_chunk)
    return (tl.cols(topk), tl.cols(idx_width),
            jax.ShapeDtypeStruct((n, c * topk), jnp.int8),
            jax.ShapeDtypeStruct((n, c * idx_width), idx_dtype))


def wire_stage_compact_pallas(
    x: jnp.ndarray,
    g: jnp.ndarray,
    recon: jnp.ndarray,
    res: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    bitmap: bool = False,
    interpret: bool = False,
):
    """DSGD wire stage with the compact-gather epilogue: local update +
    difference coding + EXACT-k selection + int8 quantize + EF in ONE
    pass. Returns (h, q int8 (n, n_chunks*k), pos (n, n_chunks*k)
    int16/int32, scales (n, n_chunks), new_recon, new_res); the caller
    moves (q, pos, scales) over the wire and the receiver rebuilds the
    dense dq by scatter-accumulate (``ref.scatter_compact_dq``).

    ``bitmap=True`` runs the bitmap re-encode IN-KERNEL (byte-aligned
    chunks only): the value buffer comes out in ascending-position order
    and the index buffer is the packed LSB-first presence bitmap
    (n, n_chunks * chunk // 8) uint8 -- bit-identical to
    ``ref.compact_to_bitmap`` applied to the explicit-positions output,
    decoded by ``ref.scatter_bitmap_dq``."""
    from repro.core.packing import compact_pos_dtype

    n, t = x.shape
    _check_compact(topk, scale_chunk, bitmap)
    tl = _Tiling(n, t, scale_chunk, n_buffers=7)
    kblock, idx_block, q_shape, idx_shape = _compact_geometry(
        tl, topk, scale_chunk, bitmap
    )
    kernel = functools.partial(
        _wire_stage_compact_kernel, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, pos_dtype=compact_pos_dtype(scale_chunk), bitmap=bitmap,
    )
    buf = jax.ShapeDtypeStruct((n, t), jnp.float32)
    h, q, idx, s3, nrecon, nres = pl.pallas_call(
        kernel,
        grid=(tl.steps,),
        in_specs=[tl.tile] * 4 + [tl.scalar],
        out_specs=[tl.tile, kblock, idx_block, tl.col, tl.tile, tl.tile],
        out_shape=[buf, q_shape, idx_shape, tl.scales, buf, buf],
        input_output_aliases={0: 0, 2: 4, 3: 5},
        interpret=interpret,
        name="gossip_wire_stage_compact",
    )(x, g, recon, res, _alpha(alpha))
    return h, q, idx, tl.scales_2d(s3), nrecon, nres


def wire_stage_gt_compact_pallas(
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
    scale_chunk: int = 512,
    error_feedback: bool = True,
    difference_coding: bool = True,
    topk: int | None = None,
    bitmap: bool = False,
    interpret: bool = False,
):
    """DSGT wire stage with the compact-gather epilogue on BOTH wires.
    Returns (h, t_half, q_x, pos_x, scales_x, new_recon_x, new_res_x,
    q_t, pos_t, scales_t, new_recon_t, new_res_t). ``bitmap=True`` runs
    the bitmap re-encode in-kernel on both wires (values in
    ascending-position order, packed presence bitmaps in place of the
    position buffers -- see :func:`wire_stage_compact_pallas`)."""
    from repro.core.packing import compact_pos_dtype

    n, tot = x.shape
    _check_compact(topk, scale_chunk, bitmap)
    tl = _Tiling(n, tot, scale_chunk, n_buffers=14)
    kblock, idx_block, q_shape, idx_shape = _compact_geometry(
        tl, topk, scale_chunk, bitmap
    )
    kernel = functools.partial(
        _wire_stage_gt_compact_kernel, scale_chunk=scale_chunk,
        error_feedback=error_feedback, difference_coding=difference_coding,
        topk=topk, pos_dtype=compact_pos_dtype(scale_chunk), bitmap=bitmap,
    )
    buf = jax.ShapeDtypeStruct((n, tot), jnp.float32)
    tile, col = tl.tile, tl.col
    (h, th, qx, px, scx, nrx, nsx, qt, pt, sct, nrt, nst) = pl.pallas_call(
        kernel,
        grid=(tl.steps,),
        in_specs=[tile] * 8 + [tl.scalar],
        out_specs=[tile, tile, kblock, idx_block, col, tile, tile,
                   kblock, idx_block, col, tile, tile],
        out_shape=[buf, buf, q_shape, idx_shape, tl.scales, buf, buf,
                   q_shape, idx_shape, tl.scales, buf, buf],
        input_output_aliases={0: 0, 1: 1, 4: 5, 5: 6, 6: 10, 7: 11},
        interpret=interpret,
        name="gossip_wire_stage_gt_compact",
    )(x, t, g, g_prev, recon_x, res_x, recon_t, res_t, _alpha(alpha))
    return (h, th, qx, px, tl.scales_2d(scx), nrx, nsx, qt, pt,
            tl.scales_2d(sct), nrt, nst)
