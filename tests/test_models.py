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


def _ssm_block(n_groups, chunk=32):
    """A small Mamba-2 block with no dimension equal to ``chunk``."""
    from repro.models.layers import SSMSpec, ssm_init
    spec = SSMSpec(d_model=12, d_state=8, head_dim=4, n_groups=n_groups,
                   chunk=chunk)
    return spec, ssm_init(jax.random.key(11), spec)


@pytest.mark.parametrize("n_groups", [1, 2])
@pytest.mark.parametrize("s", [12, 24, 40])
def test_ssm_apply_short_chunk_matches_padding_to_the_chunk(s, n_groups,
                                                            monkeypatch):
    """``ssm_apply`` scans a row shorter than, or not a multiple of,
    ``spec.chunk`` in chunks cut to the row (40 rows: two of 24). Its output,
    final state and the gradients of the parameters and the input equal
    those of padding the row to a multiple of ``spec.chunk``."""
    from repro.models import layers as L
    spec, params = _ssm_block(n_groups)
    x = jax.random.normal(jax.random.key(12), (2, s, spec.d_model))
    wy, wc, ws = (jax.random.normal(k, shape) for k, shape in zip(
        jax.random.split(jax.random.key(13), 3),
        [(2, s, spec.d_model), (2, spec.d_conv - 1, spec.d_inner
                                + 2 * n_groups * spec.d_state),
         (2, spec.n_heads, spec.head_dim, spec.d_state)]))

    def run(p, x):
        y, cache = L.ssm_apply(p, x, spec, return_state=True)
        loss = (jnp.sum(y * wy) + jnp.sum(cache["conv"] * wc)
                + jnp.sum(cache["ssm"] * ws))
        return loss, (y, cache["ssm"])

    def call():
        return jax.jit(jax.value_and_grad(run, argnums=(0, 1),
                                          has_aux=True))(params, x)

    assert L.ssd_chunk_len(s, spec.chunk) < spec.chunk
    (_, got), got_grads = call()
    monkeypatch.setattr(L, "ssd_chunk_len", lambda s, longest: longest)
    (_, want), want_grads = call()
    leaves = jax.tree_util.tree_leaves_with_path(
        (got, got_grads, want, want_grads))
    n = len(leaves) // 2
    for (path, g), (_, w) in zip(leaves[:n], leaves[n:]):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_ssm_apply_grad_at_a_short_row_lowers_no_full_chunk():
    """At 16 rows the gradient of ``ssm_apply`` holds no (..., 32, 32)
    tensor of the 32-row chunk; at 32 rows it does. The chunk is
    ``spec.chunk`` whenever the row is a multiple of it."""
    from repro.models.layers import ssd_chunk_len, ssm_apply
    spec, params = _ssm_block(1)

    def lowered(s):
        x = jnp.ones((2, s, spec.d_model))
        grad = jax.jit(jax.grad(lambda p, x: jnp.sum(ssm_apply(p, x, spec)[0]),
                                argnums=(0, 1)))
        text = grad.lower(params, x).as_text()
        return {tuple(int(d) for d in m.rstrip("x").split("x"))
                for m in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)}

    full = lambda shapes: {t for t in shapes if t[-2:] == (32, 32)}
    assert full(lowered(32))
    assert not full(lowered(16)), full(lowered(16))
    for longest in (8, 32, 128, 256):
        for s in range(longest, 4 * longest + 1, longest):
            assert ssd_chunk_len(s, longest) == longest


def test_ssd_chunk_cut_counts_each_trace_of_a_shortened_chunk():
    from repro import obs
    from repro.models.layers import ssm_apply
    spec, params = _ssm_block(1)

    def traces(s):
        f = jax.jit(lambda p, x: ssm_apply(p, x, spec)[0])
        before = obs.counted("trace.ssd_chunk_cut")
        for _ in range(2):
            f(params, jnp.ones((2, s, spec.d_model))).block_until_ready()
        return obs.counted("trace.ssd_chunk_cut") - before

    assert traces(16) == 1
    assert traces(32) == 0
    assert traces(64) == 0


def test_moe_aux_loss_and_capacity():
    """MoE: balanced routing gives aux ~1; capacity drops are bounded."""
    from repro.models.layers import MoeSpec, moe_apply, moe_init
    spec = MoeSpec(d_model=32, d_ff=64, n_experts=4, top_k=2, group_size=64)
    p = moe_init(jax.random.key(0), spec)
    x = jax.random.normal(jax.random.key(1), (2, 64, 32), jnp.float32)
    y, aux = moe_apply(p, x, spec)
    assert y.shape == x.shape
    assert 0.9 < float(aux) < 4.0    # ~1 when balanced; n_experts if collapsed
