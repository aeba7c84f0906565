"""Decoder-stack assembly for all decoder-only families.

Composition rules:
  * homogeneous stacks (dense / moe / ssm) scan over layer-stacked params
    (HLO size O(1) in depth -- essential for the 64-layer dry-runs) with an
    optional remat (activation-checkpoint) policy;
  * patterned stacks (hybrid: RecurrentGemma's recurrent/recurrent/local-
    attention) unroll with per-layer param trees.

Functions are pure; parameters are nested dicts. Each block kind implements
(train, decode) pairs and a decode-state initializer. ``impl`` routes the
attention / recurrence inner loops to "ref" (pure jnp) or Pallas kernels.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.layers import (
    chunked_softmax_xent,
    embed_init,
    embed_lookup,
    rmsnorm,
    rmsnorm_init,
    swiglu,
    swiglu_init,
    unembed_logits,
)

PyTree = Any

__all__ = [
    "init_params",
    "forward_hidden",
    "lm_loss",
    "prefill",
    "decode_step",
    "init_decode_state",
]


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# per-block init / apply
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, kind: str) -> Dict:
    dt = _pdtype(cfg)
    d = cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind in ("attention", "local_attention"):
        return {
            "ln1": rmsnorm_init(d, dt),
            "attn": attn.attn_init(
                k1, d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dt, cfg.qkv_bias,
                n_heads_layout=attn.layout_heads(cfg.n_heads, cfg.tp_head_pad),
            ),
            "ln2": rmsnorm_init(d, dt),
            "mlp": swiglu_init(k2, d, cfg.d_ff, dt),
        }
    if kind == "moe":
        return {
            "ln1": rmsnorm_init(d, dt),
            "attn": attn.attn_init(
                k1, d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dt, cfg.qkv_bias,
                n_heads_layout=attn.layout_heads(cfg.n_heads, cfg.tp_head_pad),
            ),
            "ln2": rmsnorm_init(d, dt),
            "moe": _moe_init(k2, cfg),
        }
    if kind in ("mla", "mla_moe"):
        blk = {
            "ln1": rmsnorm_init(d, dt),
            "attn": attn.mla_init(
                k1, d, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                cfg.qk_rope_head_dim, cfg.v_head_dim, dt,
            ),
            "ln2": rmsnorm_init(d, dt),
        }
        if kind == "mla":
            blk["mlp"] = swiglu_init(k2, d, cfg.d_ff, dt)
        else:
            blk["moe"] = _moe_init(k2, cfg)
        return blk
    if kind == "rwkv":
        return {
            "ln1": rmsnorm_init(d, dt),
            "ln2": rmsnorm_init(d, dt),
            "rwkv": rwkv_mod.rwkv_block_init(k1, d, cfg.d_ff, dt),
        }
    if kind == "recurrent":
        width = cfg.rnn_width or d
        return {
            "ln1": rmsnorm_init(d, dt),
            "rglru": rglru_mod.rglru_block_init(k1, d, width, cfg.conv_width, dt),
            "ln2": rmsnorm_init(d, dt),
            "mlp": swiglu_init(k2, d, cfg.d_ff, dt),
        }
    raise ValueError(f"unknown block kind {kind}")


def _moe_init(key, cfg: ModelConfig) -> Dict:
    return moe_mod.moe_init(
        key, cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts, _pdtype(cfg),
        cfg.shared_expert, n_held=cfg.n_experts_held, shared_d_ff=cfg.shared_d_ff,
        select_bias=cfg.score_func == "sigmoid",
    )


def _mla_kw(cfg: ModelConfig) -> Dict:
    return dict(
        n_heads=cfg.n_heads, qk_nope=cfg.qk_nope_head_dim,
        qk_rope=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, eps=cfg.norm_eps, compute_dtype=_cdtype(cfg),
    )


def _expert_layer(p: Dict, cfg: ModelConfig, h: jnp.ndarray):
    """The block's expert layer on normed h: (out, aux_loss, counters).
    A layer told which experts it holds runs dropless and counts its
    assignments; one that holds all runs the capacity dispatch."""
    if cfg.n_experts_held:
        out, counters = moe_mod.moe_held_apply(
            p, h, n_experts=cfg.n_experts, k=cfg.experts_per_token,
            first_held=cfg.first_expert_held, score_func=cfg.score_func,
            scaling=cfg.routed_scaling,
            compute_dtype=_cdtype(cfg),
        )
        return out, jnp.float32(0.0), counters
    out, aux = moe_mod.moe_apply(
        p, h, n_experts=cfg.n_experts, k=cfg.experts_per_token,
        capacity_factor=cfg.moe_capacity_factor, compute_dtype=_cdtype(cfg),
    )
    return out, aux, {}


def apply_block_train(
    p: Dict,
    kind: str,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    impl: str,
    carry_state: Optional[Dict] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, Dict]:
    """Full-sequence (train / prefill) block. Returns (x, aux_loss,
    counters); only a held-expert layer has counters."""
    cd = _cdtype(cfg)
    aux = jnp.float32(0.0)
    eps = cfg.norm_eps
    if kind in ("mla", "mla_moe"):
        x = x + attn.mla_apply(
            p["attn"], rmsnorm(p["ln1"], x, eps), positions, impl=impl, **_mla_kw(cfg)
        )
        h = rmsnorm(p["ln2"], x, eps)
        if kind == "mla":
            return x + swiglu(p["mlp"], h, cd), aux, {}
        m, aux, counters = _expert_layer(p["moe"], cfg, h)
        return x + m, aux, counters
    if kind in ("attention", "local_attention", "moe"):
        window = cfg.window if kind == "local_attention" else 0
        h = attn.attn_apply(
            p["attn"],
            rmsnorm(p["ln1"], x, eps),
            positions,
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta,
            causal=True,
            window=window,
            impl=impl,
            compute_dtype=cd,
            n_heads_layout=attn.layout_heads(cfg.n_heads, cfg.tp_head_pad),
        )
        x = x + h
        if kind == "moe":
            m, aux, counters = _expert_layer(p["moe"], cfg, rmsnorm(p["ln2"], x, eps))
            return x + m, aux, counters
        m = swiglu(p["mlp"], rmsnorm(p["ln2"], x, eps), cd)
        return x + m, aux, {}
    if kind == "rwkv":
        b, s, d = x.shape
        st = carry_state or rwkv_mod.rwkv_decode_states(b, d)
        h, _, _ = rwkv_mod.rwkv_time_mix(
            p["rwkv"]["time"], rmsnorm(p["ln1"], x, eps), st["tm_prev"], st["s"], cd, impl=impl
        )
        x = x + h
        c, _ = rwkv_mod.rwkv_channel_mix(
            p["rwkv"]["channel"], rmsnorm(p["ln2"], x, eps), st["cm_prev"], cd
        )
        return x + c, aux, {}
    if kind == "recurrent":
        b = x.shape[0]
        width = cfg.rnn_width or cfg.d_model
        st = carry_state or rglru_mod.rglru_decode_state(b, width, cfg.conv_width)
        h, _ = rglru_mod.rglru_block_apply(p["rglru"], rmsnorm(p["ln1"], x, eps), st, cd, impl=impl)
        x = x + h
        m = swiglu(p["mlp"], rmsnorm(p["ln2"], x, eps), cd)
        return x + m, aux, {}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# whole-stack init / forward
# ---------------------------------------------------------------------------


def _period_split(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(period, n_periods, n_tail) for patterned stacks. Periods are scanned
    when n_periods >= 2 (compile-time O(1) in depth); the tail unrolls."""
    period = len(cfg.block_pattern) or 1
    n_periods = cfg.n_layers // period
    if n_periods < 2:
        return period, 0, cfg.n_layers
    return period, n_periods, cfg.n_layers - n_periods * period


def init_params(cfg: ModelConfig, key) -> Dict:
    dt = _pdtype(cfg)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dt),
        "final_norm": rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = embed_init(k_head, cfg.padded_vocab, cfg.d_model, dt)
    pattern = cfg.effective_pattern
    keys = jax.random.split(k_blocks, cfg.n_layers)
    n_lead = cfg.first_dense_layers
    if n_lead:
        # leading dense layers as a list; the alike layers after them stacked
        params["lead"] = [init_block(keys[i], cfg, pattern[i]) for i in range(n_lead)]
        params["blocks"] = jax.vmap(lambda k: init_block(k, cfg, pattern[n_lead]))(
            keys[n_lead:]
        )
    elif cfg.is_homogeneous:
        params["blocks"] = jax.vmap(lambda k: init_block(k, cfg, pattern[0]))(keys)
    else:
        period, n_periods, n_tail = _period_split(cfg)
        if n_periods:
            # one layer-stacked tree per position in the repeating pattern
            params["pblocks"] = [
                jax.vmap(lambda k, pos=pos: init_block(k, cfg, pattern[pos]))(
                    jnp.stack([keys[p * period + pos] for p in range(n_periods)])
                )
                for pos in range(period)
            ]
            params["tail"] = [
                init_block(keys[n_periods * period + i], cfg, pattern[n_periods * period + i])
                for i in range(n_tail)
            ]
        else:
            params["blocks"] = [init_block(keys[i], cfg, pattern[i]) for i in range(cfg.n_layers)]
    return params


def _layer_params(params: Dict, cfg: ModelConfig, i: int) -> Dict:
    """Per-layer param tree regardless of storage layout (used by decode)."""
    if "lead" in params:
        n_lead = len(params["lead"])
        if i < n_lead:
            return params["lead"][i]
        return jax.tree_util.tree_map(lambda a: a[i - n_lead], params["blocks"])
    if "blocks" in params and cfg.is_homogeneous:
        return jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
    if "pblocks" in params:
        period, n_periods, _ = _period_split(cfg)
        if i < n_periods * period:
            p, pos = divmod(i, period)
            return jax.tree_util.tree_map(lambda a: a[p], params["pblocks"][pos])
        return params["tail"][i - n_periods * period]
    return params["blocks"][i]


def forward_hidden(
    params: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    impl: str = "ref",
    remat: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Embedded inputs (B,S,d) -> final hidden (B,S,d), total aux loss."""
    h, aux, _ = forward_counted(params, cfg, x, positions, impl, remat)
    return h, aux


def forward_counted(
    params: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    impl: str = "ref",
    remat: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, Dict]:
    """:func:`forward_hidden` with the layers' counters, combined over
    layers (``moe.merge_counters``); empty where no layer counts."""
    pattern = cfg.effective_pattern
    counters: Dict = {}
    if "lead" in params:
        for blk, kind in zip(params["lead"], pattern):

            def lead_layer(blk_, h, pos, kind=kind):
                return apply_block_train(blk_, kind, cfg, h, pos, impl)[0]

            fn = jax.checkpoint(lead_layer, prevent_cse=False) if remat else lead_layer
            x = fn(blk, x, positions)
        pattern = pattern[len(params["lead"]):]
    if cfg.is_homogeneous:
        kind = pattern[0]

        def body(carry, layer_params):
            h, aux, acc = carry
            h2, a, c = apply_block_train(layer_params, kind, cfg, h, positions, impl)
            return (h2, aux + a, moe_mod.merge_counters(acc, c)), None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        if kind in ("moe", "mla_moe") and cfg.n_experts_held:
            counters = moe_mod.zero_counters()
        (x, aux, counters), _ = jax.lax.scan(
            body, (x, jnp.float32(0.0), counters), params["blocks"]
        )
    else:
        aux = jnp.float32(0.0)
        period, n_periods, n_tail = _period_split(cfg)

        def one_layer(blk_, h, pos, kind):
            return apply_block_train(blk_, kind, cfg, h, pos, impl)[:2]

        if "pblocks" in params and n_periods:

            def period_body(carry, stacked_blks):
                h, a = carry
                for pos in range(period):
                    fn = functools.partial(one_layer, kind=pattern[pos])
                    if remat:
                        fn = jax.checkpoint(fn, prevent_cse=False)
                    h, ai = fn(stacked_blks[pos], h, positions)
                    a = a + ai
                return (h, a), None

            (x, aux), _ = jax.lax.scan(
                period_body, (x, aux), tuple(params["pblocks"])
            )
            tail_blocks = params.get("tail", [])
            tail_kinds = pattern[n_periods * period :]
        else:
            tail_blocks = params["blocks"]
            tail_kinds = pattern
        for blk, kind in zip(tail_blocks, tail_kinds):
            fn = functools.partial(one_layer, kind=kind)
            if remat:
                fn = jax.checkpoint(fn, prevent_cse=False)
            x, a = fn(blk, x, positions)
            aux = aux + a
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux, counters


def _embed_inputs(
    params: Dict, cfg: ModelConfig, batch: Dict
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (embedded (B,S,d), positions (B,S), labels (B,S))."""
    cd = _cdtype(cfg)
    tokens = batch["tokens"]  # (B, S+1): inputs + shifted labels
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    emb = embed_lookup(params["embed"], inputs, cd)
    if cfg.frontend != "none" and "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(cd)  # (B, P, d) stubbed frontend
        emb = jnp.concatenate([pre, emb], axis=1)
        labels = jnp.concatenate(
            [jnp.full(pre.shape[:2], -1, labels.dtype), labels], axis=1
        )
    b, s, _ = emb.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return emb, positions, labels


def lm_loss(
    params: Dict,
    cfg: ModelConfig,
    batch: Dict,
    impl: str = "ref",
    remat: bool = True,
    loss_chunk: int = 512,
    counted: bool = False,
):
    """Next-token cross-entropy (mean over valid tokens) + MoE aux; with
    ``counted``, ``(loss, counters)`` (the layers' counters, combined over
    layers)."""
    emb, positions, labels = _embed_inputs(params, cfg, batch)
    h, aux, counters = forward_counted(params, cfg, emb, positions, impl, remat)
    table = params["embed" if cfg.tie_embeddings else "head"]["table"]
    loss = chunked_softmax_xent(
        table, h, labels, cfg.vocab_size, chunk=loss_chunk, compute_dtype=_cdtype(cfg)
    )
    loss = loss + cfg.router_aux_coef * aux
    return (loss, counters) if counted else loss


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------


def _decode_kinds(cfg: ModelConfig, max_seq: int, sliding_override: bool) -> Tuple[Tuple[str, int], ...]:
    """(kind, cache_len) per layer. ``sliding_override`` replaces full
    attention with a window ring buffer (the long_500k policy for dense
    archs -- see DESIGN.md)."""
    out = []
    for kind in cfg.effective_pattern:
        if kind in ("attention", "moe", "mla", "mla_moe"):
            if sliding_override:
                out.append((kind, min(cfg.window or 4096, max_seq)))
            else:
                out.append((kind, max_seq))
        elif kind == "local_attention":
            out.append((kind, min(cfg.window or max_seq, max_seq)))
        else:
            out.append((kind, 0))
    return tuple(out)


def init_decode_state(
    cfg: ModelConfig,
    batch: int,
    max_seq: int,
    sliding_override: bool = False,
    cache_dtype=jnp.bfloat16,
) -> Any:
    """Per-layer decode caches. Homogeneous stacks get layer-stacked caches
    (scanned decode); with leading dense layers ``{"lead": [...], "blocks":
    stacked}``; patterned stacks get a list. Latent attention caches its
    latent (:func:`attention.init_mla_cache`)."""
    kinds = _decode_kinds(cfg, max_seq, sliding_override)

    def one(kind: str, cache_len: int):
        if kind in ("mla", "mla_moe"):
            return attn.init_mla_cache(
                batch, cache_len, cfg.kv_lora_rank, cfg.qk_rope_head_dim, cache_dtype
            )
        if kind in ("attention", "moe", "local_attention"):
            return attn.init_kv_cache(batch, cache_len, cfg.n_kv_heads, cfg.head_dim, cache_dtype)
        if kind == "rwkv":
            return rwkv_mod.rwkv_decode_states(batch, cfg.d_model)
        if kind == "recurrent":
            return rglru_mod.rglru_decode_state(batch, cfg.rnn_width or cfg.d_model, cfg.conv_width)
        raise ValueError(kind)

    n_lead = cfg.first_dense_layers
    if cfg.is_homogeneous:
        single = one(*kinds[n_lead])
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_layers - n_lead,) + a.shape).copy(),
            single,
        )
        if n_lead:
            return {"lead": [one(k, c) for k, c in kinds[:n_lead]], "blocks": stacked}
        return stacked
    return [one(k, c) for k, c in kinds]


def apply_block_decode(
    p: Dict, kind: str, cfg: ModelConfig, x: jnp.ndarray, state: Any, ring: bool
) -> Tuple[jnp.ndarray, Any]:
    cd = _cdtype(cfg)
    eps = cfg.norm_eps
    if kind in ("mla", "mla_moe"):
        h, state = attn.mla_decode(
            p["attn"], rmsnorm(p["ln1"], x, eps), state, ring=ring, **_mla_kw(cfg)
        )
        x = x + h
        m = rmsnorm(p["ln2"], x, eps)
        if kind == "mla":
            return x + swiglu(p["mlp"], m, cd), state
        return x + _expert_layer(p["moe"], cfg, m)[0], state
    if kind in ("attention", "local_attention", "moe"):
        h, state = attn.attn_decode(
            p["attn"],
            rmsnorm(p["ln1"], x, eps),
            state,
            n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta,
            ring=ring or kind == "local_attention",
            compute_dtype=cd,
            n_heads_layout=attn.layout_heads(cfg.n_heads, cfg.tp_head_pad),
        )
        x = x + h
        if kind == "moe":
            m = _expert_layer(p["moe"], cfg, rmsnorm(p["ln2"], x, eps))[0]
        else:
            m = swiglu(p["mlp"], rmsnorm(p["ln2"], x, eps), cd)
        return x + m, state
    if kind == "rwkv":
        h, tm_prev, s_new = rwkv_mod.rwkv_time_mix(
            p["rwkv"]["time"], rmsnorm(p["ln1"], x, eps), state["tm_prev"], state["s"], cd, chunk=1
        )
        x = x + h
        c, cm_prev = rwkv_mod.rwkv_channel_mix(
            p["rwkv"]["channel"], rmsnorm(p["ln2"], x, eps), state["cm_prev"], cd
        )
        return x + c, {"tm_prev": tm_prev, "cm_prev": cm_prev, "s": s_new}
    if kind == "recurrent":
        h, state2 = rglru_mod.rglru_block_apply(p["rglru"], rmsnorm(p["ln1"], x, eps), state, cd)
        x = x + h
        m = swiglu(p["mlp"], rmsnorm(p["ln2"], x, eps), cd)
        return x + m, state2
    raise ValueError(kind)


def decode_step(
    params: Dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    caches: Any,
    sliding_override: bool = False,
) -> Tuple[jnp.ndarray, Any]:
    """One decode step: tokens (B,) -> (logits (B, padded_vocab), caches)."""
    cd = _cdtype(cfg)
    x = embed_lookup(params["embed"], tokens[:, None], cd)  # (B,1,d)
    pattern = cfg.effective_pattern
    if cfg.is_homogeneous:
        lead = []
        if "lead" in params:
            for i, blk in enumerate(params["lead"]):
                x, c = apply_block_decode(
                    blk, pattern[i], cfg, x, caches["lead"][i], ring=sliding_override
                )
                lead.append(c)
        kind = pattern[len(lead)]

        def body(h, xs):
            layer_params, layer_cache = xs
            h2, new_cache = apply_block_decode(
                layer_params, kind, cfg, h, layer_cache, ring=sliding_override
            )
            return h2, new_cache

        body_caches = caches["blocks"] if lead else caches
        x, body_caches = jax.lax.scan(body, x, (params["blocks"], body_caches))
        caches = {"lead": lead, "blocks": body_caches} if lead else body_caches
    else:
        new_caches = []
        for i, kind in enumerate(pattern):
            x, c = apply_block_decode(
                _layer_params(params, cfg, i), kind, cfg, x, caches[i], ring=sliding_override
            )
            new_caches.append(c)
        caches = new_caches
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    table = params["embed" if cfg.tie_embeddings else "head"]["table"]
    logits = unembed_logits(table, x[:, 0], cd)
    return logits, caches


def prefill(
    params: Dict,
    cfg: ModelConfig,
    batch: Dict,
    impl: str = "ref",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward returning last-position logits (B, padded_vocab).

    (The production engine would also materialize KV caches; for the
    dry-run roofline the compute/collective profile of prefill is what
    matters, and cache writes are pure stores.)
    """
    cd = _cdtype(cfg)
    tokens = batch["tokens"]
    emb = embed_lookup(params["embed"], tokens, cd)
    if cfg.frontend != "none" and "prefix_embeds" in batch:
        emb = jnp.concatenate([batch["prefix_embeds"].astype(cd), emb], axis=1)
    b, s, _ = emb.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    h, _ = forward_hidden(params, cfg, emb, positions, impl, remat=False)
    table = params["embed" if cfg.tie_embeddings else "head"]["table"]
    return unembed_logits(table, h[:, -1], cd), h[:, -1]
