"""jit'd dispatch for the WKV-6 kernel from model-layout tensors."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import pallas_interpret
from repro.kernels.rwkv6_scan.rwkv6_scan import wkv6_chunked_pallas

__all__ = ["wkv6"]


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(
    r: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    log_w: jnp.ndarray,
    u: jnp.ndarray,
    s0: jnp.ndarray,
    chunk: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Model layout: r/k/v/log_w (B, S, H, hd); u (H, hd); s0 (B, H, hd, hd).

    Returns (y (B,S,H,hd), s_final (B,H,hd,hd)).
    """
    b, s, h, hd = r.shape
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    uu = jnp.broadcast_to(u[None], (b, h, hd)).reshape(b * h, hd)
    ss = s0.reshape(b * h, hd, hd).astype(jnp.float32)
    ck = chunk if s % chunk == 0 else 1
    y, s_fin = wkv6_chunked_pallas(
        fold(r), fold(k), fold(v), fold(log_w), uu, ss, chunk=ck,
        interpret=pallas_interpret(),
    )
    return (
        y.reshape(b, h, s, hd).transpose(0, 2, 1, 3),
        s_fin.reshape(b, h, hd, hd),
    )
