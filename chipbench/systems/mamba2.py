"""The system's model configuration for a Mamba-2 language model given as
in ``chipbench/reference/mamba2.py``: its keys mapped onto ``ModelConfig``."""
from __future__ import annotations


def model_config(t: dict):
    import jax.numpy as jnp
    from repro.models.model import ModelConfig
    cfg = ModelConfig(
        name=t["name"], arch_type="ssm", num_layers=t["n_layer"],
        d_model=t["d_model"], n_heads=0, n_kv_heads=0, d_ff=0,
        vocab_size=t["vocab_size"], ssm_state=t["d_state"],
        ssm_expand=t["expand"], ssm_head_dim=t["headdim"],
        ssm_groups=t["ngroups"], ssm_chunk=t["chunk_size"],
        vocab_pad_multiple=t["pad_vocab_size_multiple"],
        param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
        source=t["source"])
    if cfg.ssm_spec.d_conv != t["d_conv"] or t["norm_epsilon"] != 1e-6:
        raise ValueError("the system's Mamba-2 block has d_conv 4 and "
                         "RMSNorm epsilon 1e-6; the configuration differs")
    return cfg
