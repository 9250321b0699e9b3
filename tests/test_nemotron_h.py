"""The nemotron_h stack (Mamba-2, held experts and attention laid out by a
pattern) against its plain float32 reference at a tiny size, its expert
layer's share of the whole layer, its routing counts, and the shared blocks'
new defaults pinned to the arithmetic they had before."""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h as ref
from chipbench.reference.numerics import rmsnorm as ref_rmsnorm
from chipbench.systems.nemotron_h import model_config
from repro import obs
from repro.configs import get_config, make_batch, reduced
from repro.models import layers as L
from repro.models import model as M

TINY = dict(
    name="nemotron-h-tiny", arch="nemotron_h", source="test",
    model_type="nemotron_h", hidden_size=48, num_hidden_layers=7,
    hybrid_override_pattern="MEMEM*E", mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=8, chunk_size=8, conv_kernel=4,
    use_conv_bias=True, mamba_proj_bias=False, mamba_hidden_act="silu",
    num_attention_heads=4, num_key_value_heads=2, head_dim=12,
    attention_bias=False, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, router_experts=32,
    n_routed_experts=8, held_experts_from=8, num_experts_per_tok=6,
    routed_scaling_factor=2.5, norm_topk_prob=True, n_group=1, topk_group=1,
    n_shared_experts=1, mlp_hidden_act="relu2", mlp_bias=False,
    layer_norm_epsilon=1e-5, norm_eps=1e-5, tie_word_embeddings=False,
    vocab_size=256, time_step_min=0.001, time_step_max=0.1)
SEQ = 32                                   # four chunks of 8


def _cfg(**kw):
    c = {**TINY, **kw}
    return c, dataclasses.replace(model_config(c), compute_dtype=jnp.float32)


def _batch(c, b=3, key=2):
    toks = jax.random.randint(jax.random.key(key), (b, SEQ + 1), 0,
                              c["vocab_size"])
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def test_loss_and_every_gradient_match_the_reference():
    c, cfg = _cfg()
    params = ref.init(jax.random.key(1), c)
    batch = _batch(c)
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(lambda p: ref.loss(p, batch, c))(params)
        (ls, _), gs = jax.value_and_grad(
            lambda p: M.train_loss(p, batch, cfg), has_aux=True)(params)
    assert float(ls) == pytest.approx(float(lr), rel=1e-5)
    flat_r = jax.tree_util.tree_flatten_with_path(gr)[0]
    flat_s = dict(jax.tree_util.tree_flatten_with_path(gs)[0])
    assert len(flat_r) == len(flat_s) == 24
    for path, g in flat_r:
        if jnp.any(g != 0):
            assert _gap(flat_s[path], g) < 1e-4, jax.tree_util.keystr(path)
        else:     # a share's router and selection bias: no gradient
            assert path[-1].key in ("router", "router_bias")
            assert not jnp.any(flat_s[path])


def test_router_learns_only_where_the_layer_holds_every_expert():
    c, _ = _cfg()
    whole, p = _moe_params(c)
    x = jax.random.normal(jax.random.key(9), (2, SEQ, c["hidden_size"]))

    def router_grad(held, lo, q):
        loss = lambda r: jnp.sum(L.held_moe_apply(
            {**q, "router": r}, x, _moe_spec(c, held, lo))[0] ** 2)
        return jax.grad(loss)(q["router"])

    total = c["router_experts"]
    assert jnp.any(router_grad(total, 0, p) != 0)
    share = {**p, "up": p["up"][:8], "down": p["down"][:8]}
    assert not jnp.any(router_grad(8, 0, share))


def test_param_count_is_the_reference_leaves_and_active_share():
    c, cfg = _cfg()
    sizes = {path: math.prod(shape) for path, shape, _ in ref.leaves(c)}
    assert cfg.param_count() == sum(sizes.values())
    params = M.init_params(jax.random.key(0), cfg)
    assert sum(x.size for x in jax.tree.leaves(params)) == sum(sizes.values())
    routed = sizes[("moe_layers", "moe", "up")] \
        + sizes[("moe_layers", "moe", "down")]
    # 6 of 32 routed experts a token, 8 held here: 6 x 8 / 32 = 1.5 of them
    assert cfg.active_param_count() == cfg.param_count() - routed \
        + round(routed * 6 / 32)


def _moe_spec(c, held, lo):
    return L.HeldMoeSpec(
        d_model=c["hidden_size"], d_ff=c["moe_intermediate_size"],
        d_shared=c["moe_shared_expert_intermediate_size"],
        n_experts=c["router_experts"], top_k=c["num_experts_per_tok"],
        held=held, held_lo=lo, scaling=c["routed_scaling_factor"])


def _moe_params(c, key=4):
    """One expert layer holding all of the router's experts."""
    whole = {**c, "n_routed_experts": c["router_experts"],
             "held_experts_from": 0}
    p = ref.init(jax.random.key(key), whole)["moe_layers"]["moe"]
    return whole, jax.tree.map(lambda a: a[0], p)


def test_shares_of_the_experts_add_up_to_the_whole_layer():
    c, _ = _cfg()
    whole, p = _moe_params(c)
    x = jax.random.normal(jax.random.key(5), (2, SEQ, c["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        uncut = ref._experts(p, x, whole, ref.dims(whole), "f32")
        shared = L.mlp_apply(p["shared"], x, "relu2")
        held, total = c["n_routed_experts"], c["router_experts"]
        parts, n_held = [], 0
        for lo in range(0, total, held):       # E / 8 shares of 8 experts
            share = {**p, "up": p["up"][lo:lo + held],
                     "down": p["down"][lo:lo + held]}
            y, counts = L.held_moe_apply(share, x, _moe_spec(c, held, lo))
            parts.append(y - shared)
            n_held += int(counts["moe_held"])
    assert len(parts) == total // held == 4
    assert _gap(sum(parts) + shared, uncut) < 1e-5
    assert n_held == 2 * SEQ * c["num_experts_per_tok"]


def test_dropless_when_every_token_picks_the_same_experts():
    c, _ = _cfg()
    whole, p = _moe_params(c)
    k, held = c["num_experts_per_tok"], c["n_routed_experts"]
    bias = jnp.zeros((c["router_experts"],)).at[:k].set(10.0)
    p = {**p, "router_bias": bias}           # experts 0..5 for every token
    x = jax.random.normal(jax.random.key(6), (2, SEQ, c["hidden_size"]))
    sub = {**p, "up": p["up"][:held], "down": p["down"][:held]}
    mine = {**c, "held_experts_from": 0}
    with jax.default_matmul_precision("highest"):
        y, counts = L.held_moe_apply(sub, x, _moe_spec(c, held, 0))
        want = ref._experts(sub, x, mine, ref.dims(mine), "f32")
    t = 2 * SEQ
    assert int(counts["moe_held"]) == int(counts["moe_choices"]) == t * k
    assert int(counts["moe_load_max"]) == t
    assert int(counts["moe_rows"]) == t * k
    assert _gap(y, want) < 1e-5


def _unwritten_past_the_groups(real):
    """``lax.ragged_dot`` whose result rows past the last group, and the
    same rows of its rows operand's gradient, hold NaN: what the TPU's
    grouped product leaves there is whatever the memory held."""
    def tail(a, sizes):
        past = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, a)

    @jax.custom_vjp
    def dot(lhs, rhs, sizes):
        return tail(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return dot(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, ct):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)[1](
            jnp.nan_to_num(ct))
        return tail(d_lhs, sizes), d_rhs, None

    dot.defvjp(fwd, bwd)
    return dot


def test_rows_past_the_held_choices_reach_no_gradient(monkeypatch):
    c, _ = _cfg()
    whole, p = _moe_params(c)
    held = c["n_routed_experts"]
    share = {**p, "up": p["up"][:held], "down": p["down"][:held]}
    x = jax.random.normal(jax.random.key(9), (2, SEQ, c["hidden_size"]))
    spec = _moe_spec(c, held, 0)

    def grads():
        return jax.grad(lambda q, x: jnp.sum(jnp.square(
            L.held_moe_apply(q, x, spec)[0])), argnums=(0, 1))(share, x)

    clean = grads()
    monkeypatch.setattr(L.lax, "ragged_dot",
                        _unwritten_past_the_groups(jax.lax.ragged_dot))
    poisoned = grads()
    for a, b in zip(jax.tree.leaves(poisoned), jax.tree.leaves(clean)):
        np.testing.assert_array_equal(a, b)


def test_grouped_norm_at_one_group_is_todays_norm_bit_for_bit():
    x = jax.random.normal(jax.random.key(0), (2, 5, 64), jnp.float32)
    p = {"scale": 1 + 0.1 * jax.random.normal(jax.random.key(1), (64,))}

    def today(p, x, eps=1e-6):
        dtype = x.dtype
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(var + eps)
        return (x * p["scale"]).astype(dtype)

    for eps in (1e-6, 1e-5):
        np.testing.assert_array_equal(L.rmsnorm_apply(p, x, eps, groups=1),
                                      today(p, x, eps))
    np.testing.assert_array_equal(L.norm_apply("rmsnorm", p, x),
                                  today(p, x))


def test_grouped_norm_relu2_epsilon_and_rotary_off_match_the_reference():
    x = jax.random.normal(jax.random.key(0), (2, 5, 64), jnp.float32)
    scale = 1 + 0.1 * jax.random.normal(jax.random.key(1), (64,))
    got = L.rmsnorm_apply({"scale": scale}, x, 1e-5, groups=8)
    want = ref_rmsnorm(x.reshape(2, 5, 8, 8), scale.reshape(8, 8),
                       1e-5).reshape(2, 5, 64)
    assert _gap(got, want) < 1e-6
    assert _gap(L.rmsnorm_apply({"scale": scale}, x, 1e-5),
                ref_rmsnorm(x, scale, 1e-5)) < 1e-6
    mlp = {"w1": {"w": jax.random.normal(jax.random.key(2), (64, 16))},
           "w2": {"w": jax.random.normal(jax.random.key(3), (16, 64))}}
    with jax.default_matmul_precision("highest"):
        assert _gap(L.mlp_apply(mlp, x, "relu2"), ref._relu2(
            x @ mlp["w1"]["w"]) @ mlp["w2"]["w"]) < 1e-6
    c, cfg = _cfg()
    att = jax.tree.map(lambda a: a[0], ref.init(jax.random.key(7), c)
                       ["attn_layers"]["attn"])
    h = jax.random.normal(jax.random.key(8), (2, SEQ, c["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(SEQ)[None], (2, SEQ))
    with jax.default_matmul_precision("highest"):
        got, _ = L.attention_apply(att, h, pos, cfg.attn_spec)
        want = ref._attention(att, h, c, ref.dims(c), "f32")
        rotated, _ = L.attention_apply(
            att, h, pos, dataclasses.replace(cfg.attn_spec, rotary=True))
    assert not cfg.attn_spec.rotary
    assert _gap(got, want) < 1e-5
    assert _gap(rotated, want) > 1e-2


# -- today's formulas, kept here: the defaults must reproduce them ----------

def _today_rmsnorm(p, x, eps=1e-6, groups=1):
    assert (eps, groups) == (1e-6, 1)
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"]).astype(dtype)


def _today_attention(p, x, positions, spec, cache=None, cache_positions=None,
                     return_kv=False):
    assert cache is None and spec.sliding_window is None
    b, s, _ = x.shape
    q = L.dense_apply(p["wq"], x).reshape(b, s, spec.n_heads, spec.head_dim)
    k = L.dense_apply(p["wk"], x).reshape(b, s, spec.n_kv_heads, spec.head_dim)
    v = L.dense_apply(p["wv"], x).reshape(b, s, spec.n_kv_heads, spec.head_dim)
    q = L.apply_rope(q, positions, spec.rope_theta)
    k = L.apply_rope(k, positions, spec.rope_theta)
    scale = 1.0 / math.sqrt(spec.head_dim)
    scores = L._gqa_scores(q, k.transpose(0, 2, 1, 3)).astype(jnp.float32) \
        * scale
    mask = positions[:, None, None, :] <= positions[:, None, :, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30),
                           axis=-1).astype(x.dtype)
    out = L._gqa_values(probs, v.transpose(0, 2, 1, 3))
    y = L.dense_apply(p["wo"], out.reshape(b, s, spec.n_heads * spec.head_dim))
    return y, ((k, v) if return_kv else None)


def _today_ssm(p, x, spec, cache=None, return_state=False):
    assert cache is None
    b, s, _ = x.shape
    din = spec.expand * spec.d_model
    heads = din // spec.head_dim
    gn = spec.n_groups * spec.d_state
    proj = L.dense_apply({"w": p["in_proj"]}, x)
    z, xbc, dt = (proj[..., :din], proj[..., din:2 * din + 2 * gn],
                  proj[..., 2 * din + 2 * gn:])
    pad = jnp.zeros((b, spec.d_conv - 1, xbc.shape[-1]), xbc.dtype)
    xin = jnp.concatenate([pad, xbc], axis=1)
    idx = jnp.arange(s)[:, None] + jnp.arange(spec.d_conv)[None, :]
    xbc = jax.nn.silu(jnp.einsum("bskc,kc->bsc", xin[:, idx, :],
                                 p["conv_w"].astype(x.dtype))
                      + p["conv_b"].astype(x.dtype))
    xi = xbc[..., :din].reshape(b, s, heads, spec.head_dim)
    Bm = xbc[..., din:din + gn].reshape(b, s, spec.n_groups, spec.d_state)
    Cm = xbc[..., din + gn:].reshape(b, s, spec.n_groups, spec.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    pad_s = (-s) % spec.chunk
    padf = lambda a: jnp.pad(a, [(0, 0), (0, pad_s)] + [(0, 0)] * (a.ndim - 2))
    y, _ = L.ssd_chunked(padf(xi).astype(jnp.float32), padf(dt),
                         -jnp.exp(p["A_log"]), padf(Bm).astype(jnp.float32),
                         padf(Cm).astype(jnp.float32), spec.chunk)
    y = y[:, :s] + xi.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(b, s, din).astype(x.dtype)
    y = _today_rmsnorm(p["norm"], y * jax.nn.silu(z))
    return L.dense_apply({"w": p["out_proj"]}, y), None


@pytest.mark.parametrize("arch", ["mamba2-780m", "internvl2-1b"])
def test_defaults_reproduce_todays_forward_bit_for_bit(arch, monkeypatch):
    cfg = dataclasses.replace(reduced(get_config(arch)),
                              compute_dtype=jnp.float32)
    assert cfg.norm_eps == 1e-6 and cfg.rotary and cfg.ssm_heads == 0
    assert cfg.ssm_spec.norm_eps == 1e-6 and cfg.ssm_spec.n_groups == 1
    assert cfg.ssm_spec.d_inner == cfg.ssm_expand * cfg.d_model
    params = M.init_params(jax.random.key(0), cfg)
    batch = make_batch(cfg, 64, 2, "train")
    now = M.forward(params, batch, cfg)[0]
    monkeypatch.setattr(L, "rmsnorm_apply", _today_rmsnorm)
    monkeypatch.setattr(L, "attention_apply", _today_attention)
    monkeypatch.setattr(L, "ssm_apply", _today_ssm)
    np.testing.assert_array_equal(now, M.forward(params, batch, cfg)[0])


def test_serving_entry_points_refuse_the_pattern_stack():
    c, cfg = _cfg()
    params = M.init_params(jax.random.key(0), cfg)
    batch = _batch(c)
    for call in (lambda: M.prefill(params, batch, cfg, SEQ),
                 lambda: M.init_cache(cfg, 2, SEQ),
                 lambda: M.decode_step(params, {}, batch,
                                       jnp.zeros((3,), jnp.int32), cfg)):
        with pytest.raises(NotImplementedError):
            call()


def _hlo_ops(pattern):
    c, cfg = _cfg(hybrid_override_pattern=pattern,
                  num_hidden_layers=len(pattern))
    params = jax.eval_shape(lambda k: ref.init(k, c), jax.random.key(0))
    batch = jax.eval_shape(lambda: _batch(c))
    text = jax.jit(lambda p, b: M.train_loss(p, b, cfg)[0]).lower(
        params, batch).as_text()
    return len(re.findall(r"^\s*%\S+ = ", text, re.M))


def test_program_size_does_not_grow_with_depth():
    assert _hlo_ops("MEMEM*E") == _hlo_ops("MEMEM*EMEMEM*EMEMEM*E")


def test_routing_counts_reach_the_train_step_span_only_while_traced(tmp_path):
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.train_loop import Trainer
    c, cfg = _cfg()
    before = obs.counted("trace.moe")
    trainer = Trainer(cfg, 2, SEQ, AdamWConfig(warmup_steps=1))
    trainer.data = iter([_batch(c, b=2)] * 4)
    trainer.step_minibatch()
    assert obs.counted("trace.moe") > before           # traced, not per call
    traced = obs.counted("trace.moe")
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer.step_minibatch()
    finally:
        jax.profiler.stop_trace()
    assert obs.counted("trace.moe") == traced
    (sp,) = obs.spans("train.step")
    assert sp.attrs["moe_choices"] == 3 * 2 * SEQ * 6   # 3 expert layers
    assert 0 < sp.attrs["moe_held"] <= sp.attrs["moe_rows"] \
        == sp.attrs["moe_choices"]
    assert sp.attrs["moe_experts"] == 8
    assert sp.attrs["moe_held"] <= 8 * sp.attrs["moe_load_max"]
