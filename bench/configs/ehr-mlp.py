"""Plain reference of ``ehr-mlp``: the paper's shallow network over 42
EHR features, in float32 with every matmul at ``Precision.HIGHEST``.

``rnd`` rounds each matmul operand (identity for the reference; a cast
through a narrower dtype for the control). Imports nothing of the program.
"""

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def init_params(config, key):
    """Fan-in scaled normal weights and zero biases, float32."""
    d, h, c = config["d_in"], config["d_hidden"], config["n_classes"]
    k1, k2 = jax.random.split(key)
    return {
        "fc1": {"b": jnp.zeros((h,), jnp.float32),
                "w": jax.random.normal(k1, (d, h), jnp.float32) * d ** -0.5},
        "fc2": {"b": jnp.zeros((c,), jnp.float32),
                "w": jax.random.normal(k2, (h, c), jnp.float32) * h ** -0.5},
    }


def class_weights(config):
    """Inverse-frequency weights n / (classes * n_c) from the cohort's
    published counts (label 0 = MCI, 1 = AD)."""
    counts = np.asarray([config["inputs"]["n_mci"], config["inputs"]["n_ad"]],
                        np.float64)
    return counts.sum() / (len(counts) * counts)


def loss(params, batch, config, rnd=lambda a: a):
    """Class-weighted mean cross-entropy ``sum w_y ce / sum w_y``."""
    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)

    x, y = batch["x"].astype(jnp.float32), batch["y"]
    hid = jnp.tanh(mm(x, params["fc1"]["w"]) + params["fc1"]["b"])
    logits = mm(hid, params["fc2"]["w"]) + params["fc2"]["b"]
    ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, y[:, None], axis=-1)[:, 0]
    w = jnp.asarray(class_weights(config), jnp.float32)[y]
    return jnp.sum(w * ce) / jnp.sum(w)


def train_flops(config, traffic):
    """Model FLOPs of one sample's forward and backward: 3 x (2 x the
    multiply-adds of both layers)."""
    d, h, c = config["d_in"], config["d_hidden"], config["n_classes"]
    return 3 * 2 * (d * h + h * c)
