"""The system's model configuration for a Qwen2-style decoder with an
image prefix, given as in ``chipbench/reference/dense_lm.py``: its keys
mapped onto ``ModelConfig`` (the system's ``vlm`` architecture)."""
from __future__ import annotations


def model_config(s: dict):
    import jax.numpy as jnp
    from repro.models.model import ModelConfig
    cfg = ModelConfig(
        name=s["name"], arch_type="vlm", num_layers=s["num_hidden_layers"],
        d_model=s["hidden_size"], n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], d_ff=s["intermediate_size"],
        vocab_size=s["vocab_size"], qkv_bias=True,
        rope_theta=float(s["rope_theta"]), n_patches=s["vision_tokens"],
        d_vision=s["vision_width"],
        vocab_pad_multiple=s["pad_vocab_size_multiple"],
        param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
        source=s["source"])
    if s["rms_norm_eps"] != 1e-6:
        raise ValueError("the system's RMSNorm epsilon is 1e-6")
    return cfg
