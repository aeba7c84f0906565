"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package ships: <name>.py (pl.pallas_call + BlockSpec tiling),
ops.py (jit'd model-layout wrapper that asks :func:`pallas_interpret`
whether to interpret), ref.py (pure-jnp oracle used by the allclose test
sweeps).
"""

from __future__ import annotations

import os

import jax

__all__ = ["pallas_interpret"]


def pallas_interpret() -> bool:
    """Whether a Pallas call runs in interpret mode (the kernel body as
    jnp ops on the host backend).

    On a TPU backend kernels always compile for the chip, and
    ``REPRO_PALLAS_INTERPRET=1`` is an error there: an interpreted kernel
    would hide the device path. Elsewhere interpret mode is the default;
    ``REPRO_PALLAS_INTERPRET=0`` turns it off, which is how a TPU compile
    is rehearsed on a host without the chip. Resolved per call, outside
    any jit, so the variable is never frozen into a compilation.
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    on_tpu = jax.default_backend() == "tpu"
    if env is None:
        return not on_tpu
    forced = env not in ("0", "false", "False")
    if forced and on_tpu:
        raise RuntimeError(
            f"REPRO_PALLAS_INTERPRET={env} on a TPU backend: Pallas kernels "
            "compile for the chip there; unset the variable"
        )
    return forced
