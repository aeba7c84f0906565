"""The program's side of the ``mlp`` configurations: the paper's shallow
network from ``repro.models.mlp``, with the cohort's inverse-frequency
class weights."""

import jax
import numpy as np


def build(config):
    from repro.models.mlp import make_mlp_loss, mlp_init

    counts = np.asarray([config["inputs"]["n_mci"], config["inputs"]["n_ad"]],
                        np.float64)
    loss_fn = make_mlp_loss(counts.sum() / (len(counts) * counts))
    shapes = jax.eval_shape(
        lambda k: mlp_init(k, config["d_in"], config["d_hidden"],
                           config["n_classes"]), jax.random.key(0))
    return loss_fn, shapes
