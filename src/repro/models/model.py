"""Model registry: ModelConfig -> ModelBundle (init / loss / serve fns).

The bundle is the single integration surface consumed by the FL trainer,
the serving engine, and the dry-run launcher. All functions are pure and
jit-able; ``init_fn`` is also ``jax.eval_shape``-able (the dry-run builds
parameter ShapeDtypeStructs without allocating 132B parameters).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec as encdec_mod
from repro.models import transformer as tfm
from repro.models.sharding import model_param_specs

PyTree = Any

__all__ = ["ModelBundle", "build_model"]


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init_fn: Callable[[jax.Array], PyTree]
    loss_fn: Callable[[PyTree, Dict], jnp.ndarray]
    prefill_fn: Callable[[PyTree, Dict], Tuple[jnp.ndarray, jnp.ndarray]]
    decode_fn: Callable[[PyTree, jnp.ndarray, PyTree], Tuple[jnp.ndarray, PyTree]]
    init_decode_state_fn: Callable[..., PyTree]
    param_specs_fn: Callable[[PyTree], PyTree]
    #: ``(params, batch) -> (loss, counters)``: the loss with the layers'
    #: counters (empty for a model without any), which ``make_fl_round``
    #: averages over sites into the round's metrics
    counted_loss_fn: Optional[Callable[[PyTree, Dict], Tuple[jnp.ndarray, Dict]]] = None

    def param_shapes(self) -> PyTree:
        return jax.eval_shape(self.init_fn, jax.random.key(0))


def build_model(cfg: ModelConfig, impl: str = "ref", remat: bool = True) -> ModelBundle:
    if cfg.family == "audio":
        return _build_encdec(cfg, impl, remat)
    return _build_decoder_only(cfg, impl, remat)


def _build_decoder_only(cfg: ModelConfig, impl: str, remat: bool) -> ModelBundle:
    def init_fn(key):
        return tfm.init_params(cfg, key)

    def loss_fn(params, batch):
        return tfm.lm_loss(params, cfg, batch, impl=impl, remat=remat)

    def counted_loss_fn(params, batch):
        return tfm.lm_loss(params, cfg, batch, impl=impl, remat=remat, counted=True)

    def prefill_fn(params, batch):
        return tfm.prefill(params, cfg, batch, impl=impl)

    def decode_fn(params, tokens, caches, sliding_override: bool = False):
        return tfm.decode_step(params, cfg, tokens, caches, sliding_override)

    def init_decode_state_fn(batch: int, max_seq: int, sliding_override: bool = False):
        return tfm.init_decode_state(cfg, batch, max_seq, sliding_override)

    return ModelBundle(
        cfg=cfg,
        init_fn=init_fn,
        loss_fn=loss_fn,
        prefill_fn=prefill_fn,
        decode_fn=decode_fn,
        init_decode_state_fn=init_decode_state_fn,
        param_specs_fn=model_param_specs,
        counted_loss_fn=counted_loss_fn,
    )


def _build_encdec(cfg: ModelConfig, impl: str, remat: bool) -> ModelBundle:
    def init_fn(key):
        return encdec_mod.encdec_init(cfg, key)

    def loss_fn(params, batch):
        return encdec_mod.encdec_loss(params, cfg, batch, impl=impl, remat=remat)

    def prefill_fn(params, batch):
        return encdec_mod.encdec_prefill(params, cfg, batch, impl=impl)

    def decode_fn(params, tokens, caches, sliding_override: bool = False):
        del sliding_override  # whisper decoder: contiguous self-cache only
        return encdec_mod.encdec_decode_step(params, cfg, tokens, caches)

    def init_decode_state_fn(batch: int, max_seq: int, sliding_override: bool = False):
        del sliding_override
        return encdec_mod.encdec_init_decode_state(cfg, batch, max_seq)

    return ModelBundle(
        cfg=cfg,
        init_fn=init_fn,
        loss_fn=loss_fn,
        prefill_fn=prefill_fn,
        decode_fn=decode_fn,
        init_decode_state_fn=init_decode_state_fn,
        param_specs_fn=model_param_specs,
    )
