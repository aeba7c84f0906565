"""GQA attention: full-causal, sliding-window, bidirectional, cross, decode;
and multi-head latent attention (MLA), for training and for decode through a
latent cache.

Parameters use FUSED head dims -- wq: (d, H*hd), wk/wv: (d, K*hd),
wo: (H*hd, d) -- because fused dims are divisible by the tensor-parallel
degree (16) for every assigned architecture even when head counts (40, 15,
10) are not. GSPMD shards the fused dims; the per-head einsums below leave
the head axis unconstrained.

``impl`` selects the ref (pure jnp, runs everywhere) or the Pallas flash
kernel path (TPU target; interpret=True on CPU for tests).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init, linear, rmsnorm, rmsnorm_init

PyTree = Any

__all__ = [
    "attn_init",
    "attn_apply",
    "init_kv_cache",
    "attn_decode",
    "cross_attn_init",
    "cross_attn_apply",
    "precompute_cross_kv",
    "mla_init",
    "mla_apply",
    "init_mla_cache",
    "mla_decode",
    "apply_rope_interleaved",
    "NEG_INF",
]

NEG_INF = -1e30


def layout_heads(n_heads: int, pad_to: int) -> int:
    """Physical head count: logical heads padded up to a multiple of
    ``pad_to`` (the TP degree). 16 does not divide 40/15/10-head configs;
    without padding GSPMD factors the model axis and ALL-REDUCES the
    (B, H/8, S, S) fp32 score tensors -- the dominant collective in the
    baseline dry-runs. Padded heads are zero-initialized and their output
    is statically masked, so the model is EXACTLY the logical-head model
    (padded parameters receive zero gradient and never train)."""
    if pad_to <= 0 or n_heads % pad_to == 0:
        return n_heads
    return ((n_heads + pad_to - 1) // pad_to) * pad_to


def _pad_heads(x: jnp.ndarray, n_layout: int) -> jnp.ndarray:
    """(B, T, H, hd) -> (B, T, n_layout, hd) with zero pad heads."""
    h = x.shape[-2]
    if h == n_layout:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-2] = (0, n_layout - h)
    return jnp.pad(x, pad)


def _head_mask(n_heads: int, n_layout: int, dtype) -> Optional[jnp.ndarray]:
    if n_layout == n_heads:
        return None
    return (jnp.arange(n_layout) < n_heads).astype(dtype)[None, None, :, None]


def attn_init(
    key,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    dtype,
    qkv_bias: bool = False,
    n_heads_layout: Optional[int] = None,
) -> Dict:
    hl = n_heads_layout or n_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d_model, hl * head_dim, dtype, bias=qkv_bias),
        "wk": dense_init(kk, d_model, n_kv_heads * head_dim, dtype, bias=qkv_bias),
        "wv": dense_init(kv, d_model, n_kv_heads * head_dim, dtype, bias=qkv_bias),
        "wo": dense_init(ko, hl * head_dim, d_model, dtype),
    }


def _split_heads(x: jnp.ndarray, n: int, hd: int) -> jnp.ndarray:
    return x.reshape(*x.shape[:-1], n, hd)


def _repeat_kv(kv: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(B,T,K,hd) -> (B,T,H,hd) by repeating each kv head H/K times."""
    n_kv = kv.shape[-2]
    if n_kv == n_heads:
        return kv
    return jnp.repeat(kv, n_heads // n_kv, axis=-2)


def _sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool,
    window: int,
    q_offset: jnp.ndarray | int = 0,
    kv_valid: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Reference scaled-dot-product attention, fp32 softmax.

    q: (B,S,H,hd); k,v: (B,T,H,hd). ``q_offset`` is the absolute position
    of q[0] minus that of k[0] (nonzero during decode). ``kv_valid``:
    (B,T) bool mask of populated cache slots.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    qpos = jnp.arange(s)[:, None] + q_offset  # absolute q positions
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    if kv_valid is not None:
        scores = jnp.where(kv_valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _sdpa_blocked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool,
    window: int,
    q_chunk: int = 512,
) -> jnp.ndarray:
    """Flash-style q-blocked attention in pure jnp (EXACT, differentiable).

    Scans over query chunks; each chunk takes a full-row softmax against
    all keys, so peak score memory is (B, H, q_chunk, T) instead of
    (B, H, S, T) -- the S/q_chunk x traffic reduction that the Pallas
    flash kernel realizes on TPU, in a form XLA can compile on any
    backend. This is the §Perf "blocked attention" lever.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    if s % q_chunk:
        return _sdpa(q, k, v, causal=causal, window=window)
    n_chunks = s // q_chunk
    qc = q.reshape(b, n_chunks, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(t)

    def per_chunk(_, xs):
        qi, idx = xs  # (B, cq, H, hd), scalar chunk index
        scores = jnp.einsum("bshd,bthd->bhst", qi, k).astype(jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(hd))
        qpos = idx * q_chunk + jnp.arange(q_chunk)
        mask = jnp.ones((q_chunk, t), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return None, jnp.einsum("bhst,bthd->bshd", probs, v)

    _, out = jax.lax.scan(per_chunk, None, (qc, jnp.arange(n_chunks)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])


def attn_apply(
    p: Dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: Optional[float],
    causal: bool = True,
    window: int = 0,
    impl: str = "ref",
    compute_dtype=jnp.bfloat16,
    n_heads_layout: Optional[int] = None,
) -> jnp.ndarray:
    """Self-attention over a full sequence (training / prefill)."""
    hl = n_heads_layout or n_heads
    q = _split_heads(linear(p["wq"], x, compute_dtype), hl, head_dim)
    k = _split_heads(linear(p["wk"], x, compute_dtype), n_kv_heads, head_dim)
    v = _split_heads(linear(p["wv"], x, compute_dtype), n_kv_heads, head_dim)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    kk = _pad_heads(_repeat_kv(k, n_heads), hl)
    vv = _pad_heads(_repeat_kv(v, n_heads), hl)
    if impl == "flash":
        from repro.kernels.flash_attention import ops as flash_ops

        out = flash_ops.flash_attention(q, kk, vv, causal=causal, window=window)
    elif impl == "blocked":
        out = _sdpa_blocked(q, kk, vv, causal=causal, window=window)
    else:
        out = _sdpa(q, kk, vv, causal=causal, window=window)
    mask = _head_mask(n_heads, hl, out.dtype)
    if mask is not None:
        out = out * mask
    return linear(p["wo"], out.reshape(*x.shape[:-1], hl * head_dim), compute_dtype)


# ---------------------------------------------------------------------------
# decode with KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(
    batch: int, length: int, n_kv_heads: int, head_dim: int, dtype=jnp.bfloat16
) -> Dict:
    """Contiguous cache (full attention) or ring buffer (window attention --
    pass length=window). ``pos`` is the absolute next-token position."""
    return {
        "k": jnp.zeros((batch, length, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, length, n_kv_heads, head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def attn_decode(
    p: Dict,
    x: jnp.ndarray,
    cache: Dict,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: Optional[float],
    ring: bool = False,
    compute_dtype=jnp.bfloat16,
    n_heads_layout: Optional[int] = None,
    impl: str = "ref",
) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode: x (B,1,d) against the cache.

    ``ring=True`` treats the cache as a sliding-window ring buffer of size
    ``cache_len`` (keys stay rope'd at absolute positions, so relative
    geometry is preserved regardless of buffer rotation).
    """
    b = x.shape[0]
    cache_len = cache["k"].shape[1]
    pos = cache["pos"]
    positions = jnp.full((b, 1), pos, jnp.int32)

    hl = n_heads_layout or n_heads
    q = _split_heads(linear(p["wq"], x, compute_dtype), hl, head_dim)
    k = _split_heads(linear(p["wk"], x, compute_dtype), n_kv_heads, head_dim)
    v = _split_heads(linear(p["wv"], x, compute_dtype), n_kv_heads, head_dim)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    slot = pos % cache_len if ring else pos
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    n_valid = jnp.minimum(pos + 1, cache_len)
    if impl == "decode_kernel":
        # fused Pallas path: K/V stream through VMEM once (TPU target;
        # interpret mode on CPU). The kernel works on LOGICAL heads (its
        # GQA index_map needs n_heads % n_kv == 0); padded layout heads
        # are zero anyway, so slice in and pad back out.
        from repro.kernels.decode_attention import ops as dec_ops

        out = dec_ops.decode_attention(
            q[:, :, :n_heads],
            ck.astype(compute_dtype),
            cv.astype(compute_dtype),
            jnp.broadcast_to(n_valid, (b,)),
        )
        out = _pad_heads(out, hl)
    else:
        if ring:
            valid = jnp.broadcast_to(jnp.arange(cache_len)[None] < n_valid, (b, cache_len))
        else:
            valid = jnp.broadcast_to(jnp.arange(cache_len)[None] <= pos, (b, cache_len))
        out = _sdpa(
            q,
            _pad_heads(_repeat_kv(ck.astype(compute_dtype), n_heads), hl),
            _pad_heads(_repeat_kv(cv.astype(compute_dtype), n_heads), hl),
            causal=False,  # validity mask already encodes the horizon
            window=0,
            kv_valid=valid,
        )
    mask = _head_mask(n_heads, hl, out.dtype)
    if mask is not None:
        out = out * mask
    out = linear(p["wo"], out.reshape(b, 1, hl * head_dim), compute_dtype)
    return out, {"k": ck, "v": cv, "pos": pos + 1}


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)
# ---------------------------------------------------------------------------


def cross_attn_init(key, d_model: int, n_heads: int, head_dim: int, dtype) -> Dict:
    return attn_init(key, d_model, n_heads, n_heads, head_dim, dtype, qkv_bias=True)


def precompute_cross_kv(
    p: Dict, enc_out: jnp.ndarray, n_heads: int, head_dim: int, compute_dtype=jnp.bfloat16
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    k = _split_heads(linear(p["wk"], enc_out, compute_dtype), n_heads, head_dim)
    v = _split_heads(linear(p["wv"], enc_out, compute_dtype), n_heads, head_dim)
    return k, v


def cross_attn_apply(
    p: Dict,
    x: jnp.ndarray,
    kv: Tuple[jnp.ndarray, jnp.ndarray],
    *,
    n_heads: int,
    head_dim: int,
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Decoder queries attend (unmasked) over precomputed encoder K/V."""
    q = _split_heads(linear(p["wq"], x, compute_dtype), n_heads, head_dim)
    k, v = kv
    out = _sdpa(q, k, v, causal=False, window=0)
    return linear(p["wo"], out.reshape(*x.shape[:-1], n_heads * head_dim), compute_dtype)


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2/V3, without a query LoRA)
# ---------------------------------------------------------------------------
#
#   q            = W_q x                  per head: [q_nope (nope), q_pe (rope)]
#   [c_kv, k_pe] = W_kv_a x               c_kv (rank) through its own RMSNorm
#   [k_nope, v]  = W_kv_b c_kv            per head
#   k_pe: one rope-wide key shared by all heads; interleaved RoPE on it and
#   on q_pe; scores over 1/sqrt(nope + rope); out = W_o [v-weighted heads].
#
# Decode caches c_kv and k_pe (rank + rope values a token a layer) and
# absorbs W_kv_b into the query and the output.


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                           theta: float) -> jnp.ndarray:
    """RoPE on consecutive pairs (x[2i], x[2i+1]) of (..., seq, heads, dim),
    the ``rope_interleave`` layout."""
    inv = 1.0 / theta ** (jnp.arange(0, x.shape[-1], 2, dtype=jnp.float32) / x.shape[-1])
    ang = positions.astype(jnp.float32)[..., None, None] * inv  # (..., seq, 1, dim/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_init(key, d_model: int, n_heads: int, kv_lora_rank: int, qk_nope: int,
             qk_rope: int, v_dim: int, dtype) -> Dict:
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d_model, n_heads * (qk_nope + qk_rope), dtype),
        "wkv_a": dense_init(ka, d_model, kv_lora_rank + qk_rope, dtype),
        "kv_norm": rmsnorm_init(kv_lora_rank, dtype),
        "wkv_b": dense_init(kb, kv_lora_rank, n_heads * (qk_nope + v_dim), dtype),
        "wo": dense_init(ko, n_heads * v_dim, d_model, dtype),
    }


def _mla_latent(p, x, positions, *, n_heads, qk_nope, qk_rope, rope_theta, eps,
                compute_dtype):
    """Queries split into their no-RoPE and RoPE parts, the normed latent
    c_kv and the shared RoPE key: (q_nope, q_pe, c_kv, k_pe (.., 1, rope))."""
    q = _split_heads(linear(p["wq"], x, compute_dtype), n_heads, qk_nope + qk_rope)
    q_nope, q_pe = q[..., :qk_nope], q[..., qk_nope:]
    kv = linear(p["wkv_a"], x, compute_dtype)
    c_kv = rmsnorm(p["kv_norm"], kv[..., :-qk_rope], eps)
    k_pe = kv[..., None, -qk_rope:]
    q_pe = apply_rope_interleaved(q_pe, positions, rope_theta)
    k_pe = apply_rope_interleaved(k_pe, positions, rope_theta)
    return q_nope, q_pe, c_kv, k_pe


def mla_apply(
    p: Dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    n_heads: int,
    qk_nope: int,
    qk_rope: int,
    v_dim: int,
    rope_theta: float,
    eps: float,
    impl: str = "ref",
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Causal latent self-attention over a full sequence (training /
    prefill), in the ``mla`` named scope."""
    with jax.named_scope("mla"):
        q_nope, q_pe, c_kv, k_pe = _mla_latent(
            p, x, positions, n_heads=n_heads, qk_nope=qk_nope, qk_rope=qk_rope,
            rope_theta=rope_theta, eps=eps, compute_dtype=compute_dtype)
        kv = _split_heads(linear(p["wkv_b"], c_kv, compute_dtype), n_heads, qk_nope + v_dim)
        k_nope, v = kv[..., :qk_nope], kv[..., qk_nope:]
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, q_pe.shape)], axis=-1)
        if impl == "blocked":
            out = _sdpa_blocked(q, k, v, causal=True, window=0)
        else:
            out = _sdpa(q, k, v, causal=True, window=0)
        return linear(p["wo"], out.reshape(*x.shape[:-1], n_heads * v_dim), compute_dtype)


def init_mla_cache(batch: int, length: int, kv_lora_rank: int, qk_rope: int,
                   dtype=jnp.bfloat16) -> Dict:
    """The latent cache: the normed c_kv and the roped shared key, per
    position. ``pos`` is the absolute next-token position."""
    return {
        "ckv": jnp.zeros((batch, length, kv_lora_rank), dtype),
        "kpe": jnp.zeros((batch, length, qk_rope), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def mla_decode(
    p: Dict,
    x: jnp.ndarray,
    cache: Dict,
    *,
    n_heads: int,
    qk_nope: int,
    qk_rope: int,
    v_dim: int,
    rope_theta: float,
    eps: float,
    ring: bool = False,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode: x (B,1,d) against the latent cache, with W_kv_b
    absorbed: scores = (q_nope W_uk) . c_kv + q_pe . k_pe, and the output
    (probs . c_kv) W_uv per head. ``ring`` as in :func:`attn_decode`."""
    with jax.named_scope("mla"):
        b = x.shape[0]
        cache_len = cache["ckv"].shape[1]
        pos = cache["pos"]
        positions = jnp.full((b, 1), pos, jnp.int32)
        q_nope, q_pe, c_kv, k_pe = _mla_latent(
            p, x, positions, n_heads=n_heads, qk_nope=qk_nope, qk_rope=qk_rope,
            rope_theta=rope_theta, eps=eps, compute_dtype=compute_dtype)
        slot = pos % cache_len if ring else pos
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], c_kv.astype(cache["ckv"].dtype), slot, axis=1)
        kpe = jax.lax.dynamic_update_slice_in_dim(
            cache["kpe"], k_pe[:, :, 0].astype(cache["kpe"].dtype), slot, axis=1)
        n_valid = jnp.minimum(pos + 1, cache_len)
        valid = jnp.arange(cache_len) < n_valid  # (T,)
        wkv_b = p["wkv_b"]["w"].astype(compute_dtype).reshape(-1, n_heads, qk_nope + v_dim)
        w_uk, w_uv = wkv_b[..., :qk_nope], wkv_b[..., qk_nope:]
        q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_uk)
        cc, kk = ckv.astype(compute_dtype), kpe.astype(compute_dtype)
        scores = (jnp.einsum("bshc,btc->bhst", q_lat, cc).astype(jnp.float32)
                  + jnp.einsum("bshr,btr->bhst", q_pe, kk).astype(jnp.float32))
        scores = scores / jnp.sqrt(jnp.float32(qk_nope + qk_rope))
        scores = jnp.where(valid[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype)
        o_lat = jnp.einsum("bhst,btc->bshc", probs, cc)
        out = jnp.einsum("bshc,chd->bshd", o_lat, w_uv)
        out = linear(p["wo"], out.reshape(b, 1, n_heads * v_dim), compute_dtype)
        return out, {"ckv": ckv, "kpe": kpe, "pos": pos + 1}
