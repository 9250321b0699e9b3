"""Per-arch smoke tests (reduced configs) + serving-cache consistency."""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, make_batch, reduced
from repro.models import (decode_step, forward, init_cache, init_params,
                          train_loss)
from repro.models.model import cache_len_for, prefill
from repro.launch.steps import make_train_step
from repro.optim.adamw import init_opt_state


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_train_step(arch):
    """Reduced variant of each assigned architecture: one forward + one full
    train step on CPU; asserts output shapes and finiteness."""
    cfg = reduced(get_config(arch))
    params = init_params(jax.random.key(0), cfg)
    batch = make_batch(cfg, 64, 2, "train")

    logits, aux = forward(params, batch, cfg)
    s_expected = 64 if cfg.arch_type != "vlm" else 64
    if cfg.arch_type == "audio":
        assert logits.shape == (2, 64, cfg.n_codebooks, cfg.padded_vocab)
    else:
        assert logits.shape == (2, s_expected, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    step = jax.jit(make_train_step(cfg))
    opt = init_opt_state(params)
    new_params, new_opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_opt["step"]) == 1
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda x, y: float(jnp.sum(jnp.abs(x - y))), params, new_params))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_decode_step(arch):
    cfg = reduced(get_config(arch))
    params = init_params(jax.random.key(0), cfg)
    cache = init_cache(cfg, 2, 64)
    db = make_batch(cfg, 1, 2, "decode")
    logits, new_cache = decode_step(params, cache, db, jnp.zeros((2,), jnp.int32), cfg)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", [
    "stablelm-1.6b", "qwen2.5-14b",
    pytest.param("mixtral-8x22b", marks=pytest.mark.xfail(
        strict=True, reason=(
            "capacity-factor MoE dispatch cannot give exact prefill/decode "
            "parity: a token forward() drops (expert queue full over the "
            "whole sequence) is kept by decode_step's fresh one-token queue. "
            "Per-row dispatch groups (layers.moe_apply) removed the cross-"
            "row leakage; exact parity would need expert-occupancy carried "
            "in the decode cache. Seed-era debt, tracked in ROADMAP.md."))),
    "mamba2-780m", "zamba2-1.2b", "internvl2-1b", "musicgen-medium"])
def test_prefill_decode_matches_forward(arch):
    """prefill(T-1) + decode(1) must reproduce forward(T)'s last logits."""
    cfg = dataclasses.replace(reduced(get_config(arch)), compute_dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    T = 64
    batch = make_batch(cfg, T, 2, "prefill")
    logits_full, _ = forward(params, batch, cfg)
    if cfg.arch_type == "vlm":
        pre = {"tokens": batch["tokens"][:, :-1], "vision": batch["vision"]}
        db = {"tokens": batch["tokens"][:, -1:]}
    else:
        pre = {"tokens": batch["tokens"][:, :T - 1]}
        db = {"tokens": batch["tokens"][:, T - 1:T]}
    _, cache = prefill(params, pre, cfg, T, cache_dtype=jnp.float32)
    pos = jnp.full((2,), logits_full.shape[1] - 1, jnp.int32)
    logits_dec, _ = decode_step(params, cache, db, pos, cfg)
    a = np.asarray(logits_full[:, -1], np.float32)
    b = np.asarray(logits_dec[:, 0], np.float32)
    np.testing.assert_allclose(a, b, atol=2e-3 * max(1.0, np.abs(a).max()))


def test_ring_buffer_equals_full_cache_within_window():
    """With window >= seq, the ring buffer must be exact; decode with a
    window w must equal full attention restricted to the last w tokens."""
    cfg = dataclasses.replace(reduced(get_config("stablelm-1.6b")),
                              compute_dtype=jnp.float32,
                              long_context_mode="swa", serve_window=32,
                              swa_activation_len=16)
    params = init_params(jax.random.key(0), cfg)
    T = 64
    assert cache_len_for(cfg, T) == 32
    batch = make_batch(cfg, T, 1, "prefill")
    _, cache = prefill(params, {"tokens": batch["tokens"][:, :T - 1]}, cfg, T,
                       cache_dtype=jnp.float32)
    # every live slot holds one of the last 32 positions
    kv_pos = np.asarray(cache["kv_pos"][0, 0])
    live = kv_pos[kv_pos >= 0]
    assert live.min() >= T - 1 - 32 and live.max() == T - 2
    db = {"tokens": batch["tokens"][:, T - 1:T]}
    logits, _ = decode_step(params, cache, db,
                            jnp.full((1,), T - 1, jnp.int32), cfg)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_ssd_chunked_matches_stepwise_recurrence():
    """The chunked SSD scan must equal the naive per-token recurrence."""
    from repro.models.layers import ssd_chunked
    key = jax.random.key(3)
    b, s, h, p, n, chunk = 1, 32, 2, 8, 4, 8
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, 1, n))
    C = jax.random.normal(ks[4], (b, s, 1, n))
    y_chunk, final = ssd_chunked(x, dt, A, B, C, chunk)

    # naive recurrence
    hstate = np.zeros((b, h, p, n))
    ys = []
    xn, dtn, Bn, Cn = map(np.asarray, (x, dt, B[:, :, 0], C[:, :, 0]))
    An = np.asarray(A)
    for t in range(s):
        decay = np.exp(dtn[:, t] * An[None, :])                     # (b,h)
        upd = dtn[:, t, :, None, None] * xn[:, t, :, :, None] * Bn[:, t, None, None, :]
        hstate = hstate * decay[:, :, None, None] + upd
        ys.append(np.einsum("bhpn,bn->bhp", hstate, Cn[:, t]))
    y_naive = np.stack(ys, axis=1)                                   # (b,s,h,p)
    np.testing.assert_allclose(np.asarray(y_chunk), y_naive, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), hstate, rtol=1e-4, atol=1e-4)


def _ssd_stepwise(x, dt, A, B, C, initial_state):
    """Per-token SSD recurrence in jnp, differentiable; head i reads group
    i // (h // g) of B and C."""
    rep = x.shape[2] // B.shape[2]
    Bh = jnp.repeat(B, rep, axis=2)                                  # (b,s,h,n)
    Ch = jnp.repeat(C, rep, axis=2)

    def step(hstate, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt * A[None, :])[:, :, None, None]         # (b,h,1,1)
        hstate = hstate * decay + (dtt[:, :, None, None] * xt[..., None]
                                   * bt[:, :, None, :])
        return hstate, jnp.einsum("bhpn,bhn->bhp", hstate, ct)

    seq = [a.swapaxes(0, 1) for a in (x, dt, Bh, Ch)]
    final, ys = jax.lax.scan(step, initial_state, seq)
    return ys.swapaxes(0, 1), final


def _ssd_inputs(key, b, s, h, p, g, n):
    ks = jax.random.split(key, 6)
    return (jax.random.normal(ks[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))),
            -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3),
            jax.random.normal(ks[3], (b, s, g, n)),
            jax.random.normal(ks[4], (b, s, g, n)),
            jax.random.normal(ks[5], (b, h, p, n)))


@pytest.mark.parametrize("n_groups", [1, 2])
@pytest.mark.parametrize("n_chunks", [1, 3])
def test_ssd_chunked_grad_matches_stepwise_recurrence(n_groups, n_chunks):
    """Gradients of the chunked SSD scan, for x, dt, A, B, C and the initial
    state, equal those of the per-token recurrence in float32."""
    from repro.models.layers import ssd_chunked
    b, h, p, n, chunk = 2, 4, 3, 5, 8
    args = _ssd_inputs(jax.random.key(7), b, n_chunks * chunk, h, p,
                       n_groups, n)
    wy, wf = (jax.random.normal(k, shape) for k, shape in zip(
        jax.random.split(jax.random.key(8)),
        [(b, n_chunks * chunk, h, p), (b, h, p, n)]))

    def loss(fn):
        def f(*a):
            y, final = fn(*a)
            return jnp.sum(y * wy) + jnp.sum(final * wf)
        return f

    argnums = tuple(range(6))
    got = jax.jit(jax.grad(loss(lambda x, dt, A, B, C, h0: ssd_chunked(
        x, dt, A, B, C, chunk, initial_state=h0)), argnums))(*args)
    want = jax.jit(jax.grad(loss(_ssd_stepwise), argnums))(*args)
    for name, g_got, g_want in zip(["x", "dt", "A", "B", "C", "h0"], got, want):
        scale = float(jnp.max(jnp.abs(g_want)))
        np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                                   rtol=1e-4, atol=1e-5 * scale, err_msg=name)


@pytest.mark.parametrize("n_groups", [1, 3])
def test_ssd_chunked_grad_lowers_no_state_outer_product(n_groups):
    """The lowered gradient of the chunked SSD holds no tensor as large as
    the (b, s, h, p, n) outer product of B and x: every contraction stays a
    matmul. The head repeat of B and C, (b, nc, l, g, rep, n), is smaller."""
    from repro.models.layers import ssd_chunked
    b, chunk, nc, h, p, n = 2, 8, 2, 3, 5, 7
    s = nc * chunk
    args = _ssd_inputs(jax.random.key(9), b, s, h, p, n_groups, n)[:5]

    def loss(*a):
        y, final = ssd_chunked(*a, chunk)
        return jnp.sum(y) + jnp.sum(final)

    text = jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(*args).as_text()
    shapes = re.findall(r"tensor<((?:\d+x)+)[a-z]", text)
    assert shapes, "no ranked tensor found in the lowered text"
    sizes = {shape: math.prod(int(d) for d in shape.rstrip("x").split("x"))
             for shape in shapes}
    too_large = {k: v for k, v in sizes.items() if v >= b * s * h * p * n}
    assert not too_large, too_large


def test_moe_aux_loss_and_capacity():
    """MoE: balanced routing gives aux ~1; capacity drops are bounded."""
    from repro.models.layers import MoeSpec, moe_apply, moe_init
    spec = MoeSpec(d_model=32, d_ff=64, n_experts=4, top_k=2, group_size=64)
    p = moe_init(jax.random.key(0), spec)
    x = jax.random.normal(jax.random.key(1), (2, 64, 32), jnp.float32)
    y, aux = moe_apply(p, x, spec)
    assert y.shape == x.shape
    assert 0.9 < float(aux) < 4.0    # ~1 when balanced; n_experts if collapsed
