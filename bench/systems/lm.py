"""The program's side of the ``lm`` configurations: a decoder from the
program's registry (``repro.configs``) with the sizes of the bench's
configuration file, built by ``repro.models.build_model``."""

import dataclasses

SIZES = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
         "vocab_size", "rope_theta", "norm_eps", "tie_embeddings")


def model_config(config):
    from repro.configs import get_config

    return dataclasses.replace(get_config(config["arch"]),
                               **{k: config[k] for k in SIZES})


def build(config):
    from repro.models import build_model

    bundle = build_model(model_config(config))
    return bundle.loss_fn, bundle.param_shapes()
