"""Perf-program features: int8 KV cache, bf16 params + fp32 master,
gradient-accumulation microbatching, TP-only serving shardings."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, make_batch, reduced
from repro.launch.steps import make_train_step
from repro.models import decode_step, forward, init_params
from repro.models import sharding as S
from repro.models.model import prefill
from repro.optim.adamw import init_opt_state


def test_int8_kv_cache_decode_close_to_forward():
    cfg = dataclasses.replace(reduced(get_config("qwen2.5-14b")),
                              compute_dtype=jnp.float32, kv_cache_quant=True)
    params = init_params(jax.random.key(0), cfg)
    T = 64
    batch = make_batch(cfg, T, 2, "prefill")
    logits_full, _ = forward(params, batch, cfg)
    _, cache = prefill(params, {"tokens": batch["tokens"][:, :T - 1]}, cfg, T)
    assert cache["kv"]["k"].dtype == jnp.int8
    assert cache["kv"]["k_scale"].dtype == jnp.bfloat16
    logits_dec, _ = decode_step(params, cache,
                                {"tokens": batch["tokens"][:, T - 1:T]},
                                jnp.full((2,), T - 1, jnp.int32), cfg)
    a = np.asarray(logits_full[:, -1], np.float32)
    b = np.asarray(logits_dec[:, 0], np.float32)
    rel = np.max(np.abs(a - b)) / np.abs(a).max()
    assert rel < 0.05, rel                      # ~1% quantization error


def test_int8_cache_is_half_the_bytes():
    cfg = reduced(get_config("stablelm-1.6b"))
    qcfg = dataclasses.replace(cfg, kv_cache_quant=True)
    from repro.models.model import init_cache
    base = init_cache(cfg, 2, 64)
    quant = init_cache(qcfg, 2, 64)
    nbytes = lambda c: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(c))
    assert nbytes(quant) < 0.6 * nbytes(base)


def test_bf16_params_master_restores_precision():
    cfg = dataclasses.replace(reduced(get_config("stablelm-1.6b")),
                              param_dtype=jnp.bfloat16)
    params = init_params(jax.random.key(0), cfg)
    opt = init_opt_state(params)
    assert "master" in opt
    # master mirrors params in fp32
    for p, m in zip(jax.tree.leaves(params), jax.tree.leaves(opt["master"])):
        assert m.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(p, np.float32), np.asarray(m),
                                   rtol=1e-2, atol=1e-2)
    batch = make_batch(cfg, 64, 2, "train")
    step = jax.jit(make_train_step(cfg))
    p2, o2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    # master moved and params track it
    moved = any(float(jnp.max(jnp.abs(a - b))) > 0 for a, b in
                zip(jax.tree.leaves(opt["master"]), jax.tree.leaves(o2["master"])))
    assert moved


@pytest.mark.parametrize("mb", [2, 4])
def test_microbatched_step_matches_single(mb):
    cfg = reduced(get_config("minitron-4b"))
    params = init_params(jax.random.key(1), cfg)
    opt = init_opt_state(params)
    batch = make_batch(cfg, 32, 4, "train")
    p1, _, m1 = jax.jit(make_train_step(cfg))(params, opt, batch)
    p2, _, m2 = jax.jit(make_train_step(cfg, microbatches=mb))(params, opt, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)


def test_tp_only_param_specs_drop_fsdp():
    cfg = get_config("stablelm-1.6b")
    mesh = AbstractMesh((16, 16), ("data", "model"))
    fsdp = S.param_specs(cfg, mesh, fsdp_on=True)
    tponly = S.param_specs(cfg, mesh, fsdp_on=False)
    flat_f = jax.tree.leaves(fsdp, is_leaf=lambda x: isinstance(x, P))
    flat_t = jax.tree.leaves(tponly, is_leaf=lambda x: isinstance(x, P))
    assert any("data" in str(s) for s in flat_f)
    assert not any("data" in str(s) for s in flat_t)
    assert any("model" in str(s) for s in flat_t)   # TP survives


def test_head_padding_variant_lowers_shapes():
    cfg = get_config("qwen2.5-14b")
    padded = dataclasses.replace(cfg, n_heads=48, head_dim=cfg.resolved_head_dim)
    assert padded.resolved_head_dim == 128
    assert padded.n_heads % 16 == 0
    shapes = jax.eval_shape(lambda k: init_params(k, reduced(padded)),
                            jax.random.key(0))
    assert shapes is not None
