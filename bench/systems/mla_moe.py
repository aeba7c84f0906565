"""The program's side of the ``mla_moe`` configurations: a DeepSeek-V3
block (latent attention, a dense first layer, then the dropless expert
layer that holds its share of the experts) from the program's registry,
with the keys of the bench's configuration file (the model's own
``config.json`` names) and its deployment share, built by
``repro.models.build_model``. The loss counts the held experts'
assignments, which the round reports in its metrics."""

import dataclasses

#: what the file must state for the program to run what it says
FIXED = {"model_type": "deepseek_v3", "hidden_act": "silu", "attention_bias": False,
         "q_lora_rank": None, "rope_interleave": True, "rope_scaling": None,
         "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
         "moe_layer_freq": 1, "scoring_func": "sigmoid", "norm_topk_prob": True}


def model_config(config):
    from repro.configs import get_config

    c, dep = config, config["deployment"]
    wrong = {k: c.get(k) for k, v in FIXED.items() if c.get(k) != v}
    if wrong:
        raise ValueError(f"the program runs {FIXED}, the file states {wrong}")
    if c["n_routed_experts"] * dep["expert_parallel"] != dep["experts_published"]:
        raise ValueError("experts held x expert parallelism != the router's experts")
    if c["qk_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim != qk_nope_head_dim + qk_rope_head_dim")
    return dataclasses.replace(
        get_config(c["arch"]),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        n_experts=dep["experts_published"], experts_per_token=c["num_experts_per_tok"],
        n_experts_held=c["n_routed_experts"], first_expert_held=dep["first_expert_held"],
        moe_d_ff=c["moe_intermediate_size"],
        shared_d_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
        score_func=c["scoring_func"], routed_scaling=c["routed_scaling_factor"],
        router_aux_coef=0.0,
        first_dense_layers=c["first_k_dense_replace"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], compute_dtype=c["compute_dtype"],
    )


def build(config):
    from repro.models import build_model

    bundle = build_model(model_config(config))
    return bundle.counted_loss_fn, bundle.param_shapes()
