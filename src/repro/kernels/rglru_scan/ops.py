"""jit'd dispatch for the RG-LRU scan kernel."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import pallas_interpret
from repro.kernels.rglru_scan.rglru_scan import rglru_scan_pallas

__all__ = ["rglru_scan"]


@functools.partial(jax.jit, static_argnames=("block_d", "chunk"))
def rglru_scan(
    log_a: jnp.ndarray,
    b: jnp.ndarray,
    h0: jnp.ndarray,
    block_d: int = 128,
    chunk: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    s = log_a.shape[1]
    w = log_a.shape[2]
    ck = chunk if s % chunk == 0 else 1
    bd = block_d if w % block_d == 0 else w
    return rglru_scan_pallas(
        log_a, b, h0, block_d=bd, chunk=ck, interpret=pallas_interpret()
    )
