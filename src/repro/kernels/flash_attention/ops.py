"""jit'd dispatch for flash attention from model-layout tensors.

Models call with (B, S, H, hd) activations; this wrapper folds to the
kernel's (B*H, S, hd) layout, picks MXU-aligned block sizes, and selects
interpret mode off-TPU through :func:`repro.kernels.pallas_interpret`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pallas_interpret
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd

__all__ = ["flash_attention"]


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """q: (B, S, H, hd); k, v: (B, S, K, hd) (K may equal H). -> (B,S,H,hd)."""
    b, s, h, hd = q.shape
    n_kv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * n_kv, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * n_kv, v.shape[1], hd)
    out = flash_attention_bhsd(
        qf,
        kf,
        vf,
        causal=causal,
        window=window,
        n_q_heads=h,
        n_kv_heads=n_kv,
        block_q=block_q,
        block_k=block_k,
        interpret=pallas_interpret(),
    )
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
