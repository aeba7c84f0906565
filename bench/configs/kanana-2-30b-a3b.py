"""Plain reference of ``kanana-2-30b-a3b``
(kakaocorp/kanana-2-30b-a3b-instruct-2601, ``model_type`` deepseek_v3) at
the chip's share: a DeepSeek-V3 decoder in float32 with every contraction
at ``Precision.HIGHEST``.

Block: pre-RMSNorm, multi-head latent attention, residual; pre-RMSNorm,
feed-forward, residual. Attention (no query LoRA): ``q = W_q h`` per head
as a no-RoPE part and a RoPE part; ``[c, k_pe] = W_kv_a h`` with ``c``
through its own RMSNorm; ``[k_nope, v] = W_kv_b c`` per head; one RoPE key
``k_pe`` shared by the heads; RoPE on the interleaved layout (each pair
``(x[2i], x[2i+1])`` de-interleaved, then rotated as halves); causal
softmax over ``(q_nope.k_nope + q_pe.k_pe) / sqrt(qk_head_dim)``; ``W_o``.
The first ``first_k_dense_replace`` layers' feed-forward is a SwiGLU of
``intermediate_size``. Each later layer's: float32 router logits over all
the experts, sigmoid scores, the top ``num_experts_per_tok`` of score plus
``e_score_correction_bias`` (one group), weights the selected scores
renormalised and times ``routed_scaling_factor``; the held experts (the
chip's share, ``deployment.first_expert_held`` on) each applied to every
token and weighted by that token's weight for it (zero where it did not
select it), which is the dropless result; plus the shared experts, one
SwiGLU of ``n_shared_experts * moe_intermediate_size``. Final RMSNorm,
logits from the untied head, mean next-token cross-entropy over the first
``vocab_size`` ids.

Departures from the published model, as the configuration file says: the
held experts only (the chip's share), the vocabulary slice, no auxiliary
loss and no online bias update.

Attention runs in blocks of ``Q_BLOCK`` queries and each layer under a
checkpoint, so that the reference fits one chip beside its own state.
``rnd`` rounds each contraction operand and each activation the program
keeps in its compute dtype (identity for the reference, a narrower format
for the control). Parameters are the nested dict the program consumes.
Imports nothing of the program.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _dims(c):
    dep = c["deployment"]
    return {
        "d": c["hidden_size"], "h": c["num_attention_heads"],
        "r": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
        "rope": c["qk_rope_head_dim"], "v": c["v_head_dim"],
        "f": c["intermediate_size"], "fe": c["moe_intermediate_size"],
        "fs": c["n_shared_experts"] * c["moe_intermediate_size"],
        "e": dep["experts_published"], "g": c["n_routed_experts"],
        "first": dep["first_expert_held"], "k": c["num_experts_per_tok"],
        "lead": c["first_k_dense_replace"],
        "moe": c["num_hidden_layers"] - c["first_k_dense_replace"],
        "vp": -(-c["vocab_size"] // 256) * 256,
    }


def _attn_shapes(n, L=()):
    return {"wkv_a": {"w": L + (n["d"], n["r"] + n["rope"])},
            "wkv_b": {"w": L + (n["r"], n["h"] * (n["nope"] + n["v"]))},
            "kv_norm": {"scale": L + (n["r"],)},
            "wo": {"w": L + (n["h"] * n["v"], n["d"])},
            "wq": {"w": L + (n["d"], n["h"] * (n["nope"] + n["rope"]))}}


def _swiglu_shapes(d, f, L=()):
    return {"down": {"w": L + (f, d)}, "gate": {"w": L + (d, f)},
            "up": {"w": L + (d, f)}}


def _shapes(c):
    n = _dims(c)
    d, L = n["d"], (n["moe"],)
    lead = {"attn": _attn_shapes(n), "ln1": {"scale": (d,)},
            "ln2": {"scale": (d,)}, "mlp": _swiglu_shapes(d, n["f"])}
    blocks = {
        "attn": _attn_shapes(n, L), "ln1": {"scale": L + (d,)},
        "ln2": {"scale": L + (d,)},
        "moe": {"down": L + (n["g"], n["fe"], d), "gate": L + (n["g"], d, n["fe"]),
                "up": L + (n["g"], d, n["fe"]),
                "router": {"bias": L + (n["e"],), "w": L + (d, n["e"])},
                "shared": _swiglu_shapes(d, n["fs"], L)},
    }
    return {"blocks": blocks, "embed": {"table": (n["vp"], d)},
            "final_norm": {"scale": (d,)}, "head": {"table": (n["vp"], d)},
            "lead": [lead] * n["lead"]}


def init_params(config, key):
    """Norm scales 1; embedding and head N(0, 0.02); the selection bias
    N(0, 0.1^2); each projection N(0, 1/fan_in). float32."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(config), is_leaf=lambda s: isinstance(s, tuple))
    leaves = []
    for i, (path, shape) in enumerate(paths):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        if "table" in name:
            std = 0.02
        elif "bias" in name:
            std = 0.1
        else:
            std = shape[-2] ** -0.5
        leaves.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_interleaved(x, theta):
    """x (b, s, heads, dim): de-interleave each pair, then rotate halves."""
    s, dim = x.shape[1], x.shape[-1]
    x = x.reshape(*x.shape[:-1], dim // 2, 2)
    x = jnp.concatenate([x[..., 0], x[..., 1]], axis=-1)
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def expert_layer(xf, p, config, rnd=lambda a: a):
    """One expert layer's feed-forward of tokens (N, d): the held experts'
    part of the routed result, dropless, plus the shared experts."""
    c, n = config, _dims(config)

    def ein(spec, a, b_):
        return jnp.einsum(spec, rnd(a), rnd(b_), precision=HIGHEST)

    def mm(a, b_):
        return ein("...i,ij->...j", a, b_)

    scores = jax.nn.sigmoid(mm(xf, p["router"]["w"]))
    _, idx = jax.lax.top_k(scores + p["router"]["bias"], n["k"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
    # each held expert's weight for each token, zero where unselected
    held = n["first"] + jnp.arange(n["g"])
    wg = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0), 1)
    hid = (jax.nn.silu(ein("td,gdf->tgf", xf, p["gate"]))
           * ein("td,gdf->tgf", xf, p["up"]))
    sh = p["shared"]
    shared = mm(jax.nn.silu(mm(xf, sh["gate"]["w"])) * mm(xf, sh["up"]["w"]),
                sh["down"]["w"])
    return shared + ein("tgf,gfd->td", wg[:, :, None] * hid, p["down"])


def loss(params, batch, config, rnd=lambda a: a):
    c, n = config, _dims(config)
    h_, nope, rope, vd, r = n["h"], n["nope"], n["rope"], n["v"], n["r"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)

    def swiglu(x, p):
        return mm(jax.nn.silu(mm(x, p["gate"]["w"])) * mm(x, p["up"]["w"]),
                  p["down"]["w"])

    toks = batch["tokens"]
    inp, labels = toks[:, :-1], toks[:, 1:]
    b, s = inp.shape
    x = rnd(params["embed"]["table"][inp])
    qb = min(Q_BLOCK, s)

    def attention(x, p):
        q = mm(x, p["wq"]["w"]).reshape(b, s, h_, nope + rope)
        kv = mm(x, p["wkv_a"]["w"])
        lat = rnd(_rmsnorm(kv[..., :r], p["kv_norm"]["scale"], eps))
        k_pe = _rope_interleaved(kv[..., None, r:], theta)
        q_pe = _rope_interleaved(q[..., nope:], theta)
        kv = mm(lat, p["wkv_b"]["w"]).reshape(b, s, h_, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h_, rope))], -1)
        qq = jnp.concatenate([q[..., :nope], q_pe], -1)
        v = kv[..., nope:]
        kpos = jnp.arange(s)

        @jax.checkpoint
        def block(_, i):
            qi = jax.lax.dynamic_slice_in_dim(qq, i * qb, qb, 1)
            sc = jnp.einsum("bshd,bthd->bhst", rnd(qi), rnd(k), precision=HIGHEST)
            sc = sc / jnp.sqrt(jnp.float32(nope + rope))
            causal = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
            pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
            return None, jnp.einsum("bhst,bthd->bshd", rnd(pr), rnd(v),
                                    precision=HIGHEST)

        _, o = jax.lax.scan(block, None, jnp.arange(s // qb))
        o = o.transpose(1, 0, 2, 3, 4).reshape(b, s, h_ * vd)
        return mm(o, p["wo"]["w"])

    def experts(x, p):
        return expert_layer(x.reshape(b * s, -1), p, config, rnd).reshape(b, s, -1)

    def layer(x, p, ffn):
        x = rnd(x + attention(rnd(_rmsnorm(x, p["ln1"]["scale"], eps)), p["attn"]))
        return rnd(x + ffn(rnd(_rmsnorm(x, p["ln2"]["scale"], eps)), p))

    for p in params["lead"]:
        x = jax.checkpoint(lambda x, p: layer(x, p, lambda h, q: swiglu(h, q["mlp"])))(x, p)
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, p: (layer(x, p, lambda h, q: experts(h, q["moe"])), None)),
        x, params["blocks"])
    hid = rnd(_rmsnorm(x, params["final_norm"]["scale"], eps))
    logits = mm(hid, params["head"]["table"].T)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < c["vocab_size"], logits, -1e30)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def expert_layer_flops(config, tokens, held_assignments):
    """Forward and backward FLOPs of the expert layers for ``tokens``
    tokens, with nothing recomputed: the router and the shared SwiGLU from
    shapes, the held experts from ``held_assignments`` (token-expert
    assignments to them, summed over the expert layers)."""
    n = _dims(config)
    per_token = n["moe"] * (n["d"] * n["e"] + 3 * n["d"] * n["fs"])
    return 3 * 2 * (tokens * per_token + held_assignments * 3 * n["d"] * n["fe"])


def train_flops(config, traffic):
    """Model FLOPs of one sequence's forward and backward, from shapes and
    with nothing recomputed: 3 x the forward's 2 x multiply-adds of every
    projection, the feed-forwards and the logits per token, plus causal
    attention's scores and weighted sum (each position attends to itself
    and those before). The held experts count at the k x held / experts
    assignments a token expects."""
    n, s = _dims(config), int(traffic["seq_len"])
    d, h_ = n["d"], n["h"]
    layers = n["lead"] + n["moe"]
    mla = (d * h_ * (n["nope"] + n["rope"]) + d * (n["r"] + n["rope"])
           + n["r"] * h_ * (n["nope"] + n["v"]) + h_ * n["v"] * d)
    expected = n["k"] * n["g"] / n["e"] * s * n["moe"]
    per_token = layers * mla + n["lead"] * 3 * d * n["f"] + d * n["vp"]
    attn = layers * h_ * (n["nope"] + n["rope"] + n["v"]) * s * (s + 1) // 2
    return (3 * 2 * (s * per_token + attn)
            + expert_layer_flops(config, s, expected))
