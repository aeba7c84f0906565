"""jit'd dispatch for the decode attention kernel from cache layout."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pallas_interpret
from repro.kernels.decode_attention.decode_attention import decode_attention_bhd

__all__ = ["decode_attention"]


@functools.partial(jax.jit, static_argnames=("block_c",))
def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    n_valid: jnp.ndarray,
    block_c: int = 256,
) -> jnp.ndarray:
    """Model layout: q (B, 1, H, hd); caches (B, C, K, hd); n_valid (B,).
    Returns (B, 1, H, hd)."""
    b, _, h, hd = q.shape
    c, n_kv = k_cache.shape[1], k_cache.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, 1, hd)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(b * n_kv, c, hd)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(b * n_kv, c, hd)
    out = decode_attention_bhd(
        qf, kf, vf, n_valid.astype(jnp.int32),
        n_q_heads=h, n_kv_heads=n_kv, block_c=block_c,
        interpret=pallas_interpret(),
    )
    return out.reshape(b, h, 1, hd).transpose(0, 2, 1, 3)
