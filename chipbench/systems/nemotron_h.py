"""The system's model configuration for a Nemotron-H language model given as
in ``chipbench/reference/nemotron_h.py``: its keys mapped onto
``ModelConfig`` (the system's ``nemotron_h`` architecture). A key the
system does not implement, or a setting of one it implements otherwise, is
refused."""
from __future__ import annotations

# settings the system's nemotron_h blocks have, and no other
FIXED = {"model_type": "nemotron_h", "mamba_hidden_act": "silu",
         "mlp_hidden_act": "relu2", "attention_bias": False,
         "mlp_bias": False, "use_bias": False, "mamba_proj_bias": False,
         "use_conv_bias": True, "conv_kernel": 4, "n_group": 1,
         "topk_group": 1, "n_shared_experts": 1, "norm_topk_prob": True,
         "tie_word_embeddings": False, "sliding_window": None,
         "residual_in_fp32": False}
# keys mapped onto ModelConfig
MAPPED = {"name", "source", "hidden_size", "num_hidden_layers",
          "hybrid_override_pattern", "num_attention_heads",
          "num_key_value_heads", "head_dim", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "vocab_size",
          "layer_norm_epsilon", "norm_eps", "router_experts",
          "n_routed_experts", "held_experts_from", "num_experts_per_tok",
          "routed_scaling_factor", "ssm_state_size", "chunk_size",
          "mamba_head_dim", "mamba_num_heads", "n_groups"}
# keys that change nothing the system computes in training: the trainer's
# own (arch, seq_len, optimizer, precision), the draws of the initial
# weights, serving, and sizes the explicit ones above replace
INERT = {"arch", "seq_len", "optimizer", "precision", "initializer_range",
         "rescale_prenorm_residual", "time_step_floor", "time_step_min",
         "time_step_max", "max_position_embeddings", "num_logits_to_keep",
         "use_mamba_kernels", "expand", "intermediate_size", "rope_theta",
         "partial_rotary_factor"}


def model_config(t: dict):
    import jax.numpy as jnp
    from repro.models.model import ModelConfig
    unknown = set(t) - set(FIXED) - MAPPED - INERT
    if unknown:
        raise ValueError(f"the system does not implement {sorted(unknown)}")
    wrong = {k: t[k] for k, v in FIXED.items() if t.get(k, v) != v}
    if wrong or t["layer_norm_epsilon"] != t["norm_eps"]:
        raise ValueError(f"the system's nemotron_h blocks have {FIXED} and "
                         f"one RMSNorm epsilon; the configuration gives "
                         f"{wrong or t['norm_eps']}")
    pattern = t["hybrid_override_pattern"]
    if len(pattern) != t["num_hidden_layers"]:
        raise ValueError(f"{len(pattern)} blocks in the pattern, "
                         f"{t['num_hidden_layers']} layers")
    return ModelConfig(
        name=t["name"], arch_type="nemotron_h", num_layers=len(pattern),
        layer_pattern=pattern, d_model=t["hidden_size"],
        n_heads=t["num_attention_heads"],
        n_kv_heads=t["num_key_value_heads"], head_dim=t["head_dim"],
        rotary=False, d_ff=t["moe_intermediate_size"], activation="relu2",
        n_experts=t["router_experts"], top_k=t["num_experts_per_tok"],
        experts_held=t["n_routed_experts"],
        experts_held_lo=t["held_experts_from"],
        moe_shared_ff=t["moe_shared_expert_intermediate_size"],
        routed_scaling=t["routed_scaling_factor"],
        ssm_state=t["ssm_state_size"], ssm_chunk=t["chunk_size"],
        ssm_head_dim=t["mamba_head_dim"], ssm_heads=t["mamba_num_heads"],
        ssm_groups=t["n_groups"], norm_eps=t["layer_norm_epsilon"],
        vocab_size=t["vocab_size"], vocab_pad_multiple=1,
        tie_embeddings=False, param_dtype=jnp.float32,
        compute_dtype=jnp.bfloat16, source=t["source"])
