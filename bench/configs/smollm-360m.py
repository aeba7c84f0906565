"""Plain reference of ``smollm-360m`` (HuggingFaceTB/SmolLM-360M): a
Llama-style decoder in float32 with every contraction at
``Precision.HIGHEST``.

Block: pre-RMSNorm, grouped-query attention with rotate-half RoPE and a
causal mask, residual; pre-RMSNorm, SwiGLU MLP, residual. Final RMSNorm,
logits from the tied embedding table, mean next-token cross-entropy over
the first ``vocab_size`` ids. Parameters are the nested dict the program
consumes (layer-stacked ``blocks`` leaves). ``rnd`` rounds each
contraction operand and each activation the program keeps in its compute
dtype (identity for the reference, a narrower format for the control).
Imports nothing of the program.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _shapes(c):
    d, f, L = c["d_model"], c["d_ff"], c["n_layers"]
    hq, hk = c["n_heads"] * c["head_dim"], c["n_kv_heads"] * c["head_dim"]
    vp = -(-c["vocab_size"] // 256) * 256
    return {
        "blocks": {
            "attn": {"wk": {"w": (L, d, hk)}, "wo": {"w": (L, hq, d)},
                     "wq": {"w": (L, d, hq)}, "wv": {"w": (L, d, hk)}},
            "ln1": {"scale": (L, d)},
            "ln2": {"scale": (L, d)},
            "mlp": {"down": {"w": (L, f, d)}, "gate": {"w": (L, d, f)},
                    "up": {"w": (L, d, f)}},
        },
        "embed": {"table": (vp, d)},
        "final_norm": {"scale": (d,)},
    }


def init_params(config, key):
    """Norm scales 1; the embedding N(0, 0.02); each projection
    N(0, 1/fan_in). float32."""
    shapes = _shapes(config)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    leaves = []
    for i, (path, shape) in enumerate(paths):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        std = 0.02 if "table" in name else shape[-2] ** -0.5
        leaves.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(params, batch, config, rnd=lambda a: a):
    c = config
    hd, nh, nk = c["head_dim"], c["n_heads"], c["n_kv_heads"]
    eps = c["norm_eps"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)

    toks = batch["tokens"]
    inp, labels = toks[:, :-1], toks[:, 1:]
    b, s = inp.shape
    table = params["embed"]["table"]
    x = rnd(table[inp])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        h = rnd(_rmsnorm(x, p["ln1"]["scale"], eps))
        q = _rope(mm(h, p["attn"]["wq"]["w"]).reshape(b, s, nh, hd), c["rope_theta"])
        k = _rope(mm(h, p["attn"]["wk"]["w"]).reshape(b, s, nk, hd), c["rope_theta"])
        v = mm(h, p["attn"]["wv"]["w"]).reshape(b, s, nk, hd)
        k = jnp.repeat(k, nh // nk, axis=2)
        v = jnp.repeat(v, nh // nk, axis=2)
        sc = jnp.einsum("bshd,bthd->bhst", rnd(q), rnd(k),
                        precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
        pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        o = jnp.einsum("bhst,bthd->bshd", rnd(pr), rnd(v), precision=HIGHEST)
        x = rnd(x + mm(o.reshape(b, s, nh * hd), p["attn"]["wo"]["w"]))
        h = rnd(_rmsnorm(x, p["ln2"]["scale"], eps))
        g = mm(h, p["mlp"]["gate"]["w"])
        u = mm(h, p["mlp"]["up"]["w"])
        return rnd(x + mm(jax.nn.silu(g) * u, p["mlp"]["down"]["w"])), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    h = rnd(_rmsnorm(x, params["final_norm"]["scale"], eps))
    logits = mm(h, table.T)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < c["vocab_size"],
                       logits, -1e30)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def train_flops(config, traffic):
    """Model FLOPs of one sequence's forward and backward, from shapes and
    with nothing recomputed: 3 x the forward's 2 x multiply-adds of every
    projection and the logits per token, plus causal attention's scores
    and weighted sum (each position attends to itself and those before)."""
    c, s = config, int(traffic["seq_len"])
    d, f, L = c["d_model"], c["d_ff"], c["n_layers"]
    hq, hk = c["n_heads"] * c["head_dim"], c["n_kv_heads"] * c["head_dim"]
    vp = -(-c["vocab_size"] // 256) * 256
    per_token = L * (2 * d * hq + 2 * d * hk + 3 * d * f) + d * vp
    attn = L * 2 * hq * s * (s + 1) // 2
    return 3 * 2 * (s * per_token + attn)
