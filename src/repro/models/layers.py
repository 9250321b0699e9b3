"""Neural-net building blocks for the repro model zoo.

Pure-functional JAX: every layer is an ``init(key, cfg) -> params`` plus an
``apply(params, x, ...) -> y`` pair, params are plain nested dicts so they
pjit/shard_map cleanly and checkpoint as flat npz.

Blocks provided: RMS/LayerNorm, rotary embeddings, GQA attention (optional
QKV bias, sliding window, KV cache with ring buffer), SwiGLU/GELU MLP,
top-k MoE with capacity-factor dispatch (einsum form so GSPMD shards the
expert axis), and the Mamba2 SSD mixer (chunked scan for train/prefill,
O(1) recurrence for decode).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

Params = dict
DEFAULT_ROPE_THETA = 10_000.0


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm_apply(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * p["scale"]).astype(dtype)


def layernorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm_apply(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * lax.rsqrt(var + eps)
    return (x * p["scale"] + p["bias"]).astype(dtype)


def norm_init(kind: str, d: int) -> Params:
    return layernorm_init(d) if kind == "layernorm" else rmsnorm_init(d)


def norm_apply(kind: str, p: Params, x: jax.Array) -> jax.Array:
    return layernorm_apply(p, x) if kind == "layernorm" else rmsnorm_apply(p, x)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = DEFAULT_ROPE_THETA) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = DEFAULT_ROPE_THETA) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense projections
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False, scale: float | None = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), jnp.float32) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense_apply(p: Params, x: jax.Array) -> jax.Array:
    y = jnp.einsum("...i,io->...o", x, p["w"].astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window, KV cache)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    sliding_window: Optional[int] = None
    rope_theta: float = DEFAULT_ROPE_THETA
    unroll: bool = False


def attention_init(key, spec: AttnSpec) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, spec.d_model, spec.n_heads * spec.head_dim, bias=spec.qkv_bias),
        "wk": dense_init(kk, spec.d_model, spec.n_kv_heads * spec.head_dim, bias=spec.qkv_bias),
        "wv": dense_init(kv, spec.d_model, spec.n_kv_heads * spec.head_dim, bias=spec.qkv_bias),
        "wo": dense_init(ko, spec.n_heads * spec.head_dim, spec.d_model),
    }


def init_kv_cache(batch: int, spec: AttnSpec, cache_len: int,
                  dtype=jnp.bfloat16) -> Params:
    """Ring-buffer KV cache, laid out (B, cache_len, Hkv, D): the ring slot is
    the leading in-cache axis so the per-token scatter is contiguous and
    layout-transpose-free (#Perf hillclimb A, iteration 2).

    dtype=jnp.int8 selects the quantized cache (#Perf A, iteration 3):
    per-(slot, head) symmetric scales in bf16, halving cache HBM."""
    shape = (batch, cache_len, spec.n_kv_heads, spec.head_dim)
    cache = {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }
    if dtype == jnp.int8:
        cache["k_scale"] = jnp.zeros(shape[:3] + (1,), jnp.bfloat16)
        cache["v_scale"] = jnp.zeros(shape[:3] + (1,), jnp.bfloat16)
    return cache


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (..., D) -> (int8 values, bf16 scale (..., 1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,Sq,Hq,D), k: (B,Hkv,Sk,D) -> (B,Hq,Sq,Sk) with grouped heads."""
    b, sq, hq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    scores = jnp.einsum("bskgd,bktd->bkgst", qg, k)
    return scores.reshape(b, hq, sq, k.shape[2])


def _gqa_values(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (B,Hq,Sq,Sk), v: (B,Hkv,Sk,D) -> (B,Sq,Hq,D)."""
    b, hq, sq, sk = probs.shape
    hkv = v.shape[1]
    group = hq // hkv
    pg = probs.reshape(b, hkv, group, sq, sk)
    out = jnp.einsum("bkgst,bktd->bskgd", pg, v)
    return out.reshape(b, sq, hq, v.shape[3])


_Q_CHUNK = 1024  # flash-style query blocking beyond this sequence length


def _chunked_causal_attention(q, kt, vt, positions, scale, window, unroll=False):
    """Flash-style attention: scan over query blocks so live score memory is
    O(block x S) instead of O(S x S). Each block is rematerialized in the
    backward pass (same trade the Pallas kernel makes in VMEM)."""
    b, s, hq, d = q.shape
    nb = s // _Q_CHUNK
    assert s % _Q_CHUNK == 0, f"seq {s} not divisible by q-chunk {_Q_CHUNK}"
    qb = q.reshape(b, nb, _Q_CHUNK, hq, d).transpose(1, 0, 2, 3, 4)
    pb = positions.reshape(b, nb, _Q_CHUNK).transpose(1, 0, 2)

    @jax.checkpoint
    def block(q_blk, pos_blk):
        scores = _gqa_scores(q_blk, kt).astype(jnp.float32) * scale
        qpos = pos_blk[:, None, :, None]
        kpos = positions[:, None, None, :]
        mask = kpos <= qpos
        if window is not None:
            mask = mask & (kpos > qpos - window)
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_blk.dtype)
        return _gqa_values(probs, vt)

    def body(_, xs):
        q_blk, pos_blk = xs
        return None, block(q_blk, pos_blk)

    _, out = lax.scan(body, None, (qb, pb), unroll=unroll)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, hq, d)


def attention_apply(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    spec: AttnSpec,
    cache: Optional[Params] = None,
    cache_positions: Optional[jax.Array] = None,
    return_kv: bool = False,
) -> tuple[jax.Array, Any]:
    """Causal (optionally sliding-window) self-attention.

    Prefill/train path (cache None): full-sequence causal attention.
    Decode path (cache given): x is (B, 1, d); ``positions`` (B,1) is the
    absolute position of the new token; ``cache_positions`` (B, cache_len)
    holds the absolute position stored in each ring-buffer slot (-1 = empty).
    Returns (y, new_cache) where new_cache includes updated k/v/positions.
    """
    from repro.models.model import constrain   # activation-sharding hook
    b, s, _ = x.shape
    q = constrain(dense_apply(p["wq"], x).reshape(b, s, spec.n_heads, spec.head_dim))
    k = constrain(dense_apply(p["wk"], x).reshape(b, s, spec.n_kv_heads, spec.head_dim))
    v = constrain(dense_apply(p["wv"], x).reshape(b, s, spec.n_kv_heads, spec.head_dim))
    q = apply_rope(q, positions, spec.rope_theta)
    k = apply_rope(k, positions, spec.rope_theta)
    scale = 1.0 / math.sqrt(spec.head_dim)

    if cache is None:
        kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, S, D)
        vt = v.transpose(0, 2, 1, 3)
        if s > _Q_CHUNK:
            out = _chunked_causal_attention(q, kt, vt, positions, scale,
                                            spec.sliding_window, spec.unroll)
        else:
            scores = _gqa_scores(q, kt).astype(jnp.float32) * scale
            qpos = positions[:, None, :, None]   # (B,1,Sq,1)
            kpos = positions[:, None, None, :]   # (B,1,1,Sk)
            mask = kpos <= qpos
            if spec.sliding_window is not None:
                mask = mask & (kpos > qpos - spec.sliding_window)
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            out = _gqa_values(probs, vt)
        y = dense_apply(p["wo"], out.reshape(b, s, spec.n_heads * spec.head_dim))
        return y, ((k, v) if return_kv else None)   # (B, S, Hkv, D) layout

    # --- decode: single new token against ring-buffer cache -------------
    # Scatter-based update in the cache's native (B, slot, H, D) layout:
    # touches O(B*Hkv*D) entries, no layout transposes. (The naive one-hot
    # masked arithmetic update rewrote the ENTIRE cache every token and
    # dominated the decode memory roofline; see EXPERIMENTS.md #Perf A.)
    cache_len = cache["k"].shape[1]
    quantized = cache["k"].dtype == jnp.int8
    b_idx = jnp.arange(b)
    pos = positions[:, 0]                                   # (B,)
    slot = (pos % cache_len).astype(jnp.int32)              # ring-buffer slot
    new_cache = {}
    if quantized:
        kq, ks = quantize_kv(k[:, 0])
        vq, vs = quantize_kv(v[:, 0])
        k_cache = cache["k"].at[b_idx, slot].set(kq)
        v_cache = cache["v"].at[b_idx, slot].set(vq)
        k_scale = cache["k_scale"].at[b_idx, slot].set(ks)
        v_scale = cache["v_scale"].at[b_idx, slot].set(vs)
        new_cache.update(k_scale=k_scale, v_scale=v_scale)
    else:
        knew = k[:, 0].astype(cache["k"].dtype)             # (B, Hkv, D)
        vnew = v[:, 0].astype(cache["v"].dtype)
        k_cache = cache["k"].at[b_idx, slot].set(knew)
        v_cache = cache["v"].at[b_idx, slot].set(vnew)
    new_cpos = cache_positions.at[b_idx, slot].set(
        pos.astype(cache_positions.dtype))

    # scores directly against the (B, T, Hkv, D) layout
    hkv = spec.n_kv_heads
    group = spec.n_heads // hkv
    qg = q.reshape(b, s, hkv, group, spec.head_dim)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg,
                        k_cache.astype(q.dtype)).astype(jnp.float32) * scale
    if quantized:   # fold the per-(slot, head) scale into the logits
        scores = scores * k_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    scores = scores.reshape(b, spec.n_heads, s, cache_len)
    valid = new_cpos >= 0
    visible = new_cpos <= pos[:, None]
    if spec.sliding_window is not None:
        visible = visible & (new_cpos > (pos[:, None] - spec.sliding_window))
    mask = (valid & visible)[:, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    pg = probs.reshape(b, hkv, group, s, cache_len)
    if quantized:   # fold the v scale into the probabilities
        pg = pg * v_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :].astype(pg.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", pg, v_cache.astype(x.dtype))
    out = out.reshape(b, s, spec.n_heads, spec.head_dim)
    y = dense_apply(p["wo"], out.reshape(b, s, spec.n_heads * spec.head_dim))
    new_cache.update(k=k_cache, v=v_cache)
    return y, (new_cache, new_cpos)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, *, activation: str = "swiglu") -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w1": dense_init(k1, d_model, d_ff),
        "w2": dense_init(k2, d_ff, d_model),
    }
    if activation == "swiglu":
        p["w3"] = dense_init(k3, d_model, d_ff)
    return p


def mlp_apply(p: Params, x: jax.Array, activation: str = "swiglu") -> jax.Array:
    h = dense_apply(p["w1"], x)
    if activation == "swiglu":
        h = jax.nn.silu(h) * dense_apply(p["w3"], x)
    else:
        h = jax.nn.gelu(h)
    return dense_apply(p["w2"], h)


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k, capacity-factor dispatch)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoeSpec:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    group_size: int = 1024        # tokens per dispatch group (memory control)
    dense_residual: bool = False  # Arctic-style always-on dense branch
    dense_residual_ff: int = 0


def moe_init(key, spec: MoeSpec) -> Params:
    kr, ke1, ke2, ke3, kd = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(spec.d_model)
    p = {
        "router": jax.random.normal(kr, (spec.d_model, spec.n_experts), jnp.float32) * scale,
        "w1": jax.random.normal(ke1, (spec.n_experts, spec.d_model, spec.d_ff), jnp.float32) * scale,
        "w3": jax.random.normal(ke3, (spec.n_experts, spec.d_model, spec.d_ff), jnp.float32) * scale,
        "w2": jax.random.normal(ke2, (spec.n_experts, spec.d_ff, spec.d_model), jnp.float32)
        * (1.0 / math.sqrt(spec.d_ff)),
    }
    if spec.dense_residual:
        p["dense"] = mlp_init(kd, spec.d_model, spec.dense_residual_ff or spec.d_ff)
    return p


def moe_apply(p: Params, x: jax.Array, spec: MoeSpec) -> tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss). x: (B, S, d)."""
    b, s, d = x.shape
    # Dispatch groups never span batch rows: capacity-queue positions come
    # from a cumsum over the group, so mixing rows would make one sequence's
    # drops depend on another's tokens (and break prefill/decode parity,
    # where row lengths shift between calls).
    g_row = max(1, s // spec.group_size) if s >= spec.group_size else 1
    while s % g_row:                  # largest divisor of s, so the reshape
        g_row -= 1                    # is exact for any sequence length
    g = b * g_row
    t = s // g_row
    xg = x.reshape(g, t, d)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # (G, T, E)

    # load-balance aux loss (Switch-style)
    density = jnp.mean(probs, axis=1)                                   # (G, E)
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), spec.n_experts)
    usage = jnp.mean(top1, axis=1)                                      # (G, E)
    aux = jnp.mean(jnp.sum(density * usage, axis=-1)) * spec.n_experts

    capacity = int(math.ceil(t * spec.top_k / spec.n_experts * spec.capacity_factor))
    capacity = max(capacity, spec.top_k)

    gate_vals, gate_idx = lax.top_k(probs, spec.top_k)                  # (G, T, K)
    gate_vals = gate_vals / jnp.clip(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # position of each (token, k) routing choice within its expert queue
    sel = jax.nn.one_hot(gate_idx, spec.n_experts, dtype=jnp.float32)   # (G,T,K,E)
    flat = sel.reshape(g, t * spec.top_k, spec.n_experts)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat                     # (G,T*K,E)
    pos_in_expert = jnp.sum(pos_in_expert * flat, axis=-1).reshape(g, t, spec.top_k)
    keep = pos_in_expert < capacity
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    # dispatch / combine tensors: (G, T, E, C)
    slot = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity, dtype=jnp.float32)
    combine = jnp.einsum("gtk,gtke,gtkc->gtec", gate_vals, sel, slot)
    dispatch = (combine > 0).astype(xg.dtype)

    xe = jnp.einsum("gtec,gtd->gecd", dispatch, xg)                     # (G,E,C,d)
    h = jnp.einsum("gecd,edf->gecf", xe, p["w1"].astype(xg.dtype))
    h = jax.nn.silu(h) * jnp.einsum("gecd,edf->gecf", xe, p["w3"].astype(xg.dtype))
    ye = jnp.einsum("gecf,efd->gecd", h, p["w2"].astype(xg.dtype))
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(xg.dtype), ye)

    if spec.dense_residual:
        y = y + mlp_apply(p["dense"], xg)
    return y.reshape(b, s, d), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Mamba2 / SSD mixer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SSMSpec:
    d_model: int
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim


def ssm_init(key, spec: SSMSpec) -> Params:
    ki, ko, kc, ka, kdt = jax.random.split(key, 5)
    din = spec.d_inner
    d_in_proj = 2 * din + 2 * spec.n_groups * spec.d_state + spec.n_heads
    conv_dim = din + 2 * spec.n_groups * spec.d_state
    scale = 1.0 / math.sqrt(spec.d_model)
    a = jax.random.uniform(ka, (spec.n_heads,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(kdt, (spec.n_heads,), jnp.float32) * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "in_proj": jax.random.normal(ki, (spec.d_model, d_in_proj), jnp.float32) * scale,
        "conv_w": jax.random.normal(kc, (spec.d_conv, conv_dim), jnp.float32) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(a),
        "D": jnp.ones((spec.n_heads,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.clip(dt, 1e-4))),
        "norm": rmsnorm_init(din),
        "out_proj": jax.random.normal(ko, (din, spec.d_model), jnp.float32) * (1.0 / math.sqrt(din)),
    }


def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < k <= i} x[..., k]."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


@jax.named_scope("ssd_chunked")
def ssd_chunked(x, dt, A, B, C, chunk: int, initial_state=None):
    """Chunked SSD scan (Mamba2, state-space duality).

    x: (b, s, h, p); dt: (b, s, h); A: (h,) (negative); B, C: (b, s, g, n).
    Returns (y: (b, s, h, p), final_state: (b, h, p, n)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    nc = s // chunk
    rep = h // g

    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, g, n)
    Cc = C.reshape(b, nc, chunk, g, n)
    Bh = jnp.repeat(Bc, rep, axis=3)  # (b,nc,l,h,n)
    Ch = jnp.repeat(Cc, rep, axis=3)

    dA = dtc * A[None, None, None, :]            # (b,nc,l,h)
    dA_cs = jnp.cumsum(dA, axis=2)               # within-chunk cumsum

    # intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))               # (b,nc,h,l,l)
    scores = jnp.einsum("bclhn,bcshn->bchls", Ch, Bh)            # (b,nc,h,l,l)
    y_diag = jnp.einsum("bchls,bchls,bcshp,bcsh->bclhp",
                        scores, L, xc, dtc)

    # chunk-final states
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)          # (b,nc,l,h)
    states = jnp.einsum("bclhn,bclh,bclh,bclhp->bchpn",
                        Bh, decay_states, dtc, xc)               # (b,nc,h,p,n)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])                    # (b,nc,h)
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), x.dtype)

    def step(carry, inp):
        st, dec = inp
        new = carry * dec[:, :, None, None] + st
        return new, carry  # emit state *entering* the chunk

    final, prev_states = lax.scan(
        step,
        initial_state,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)           # (b,nc,h,p,n)

    state_decay = jnp.exp(dA_cs)                                 # (b,nc,l,h)
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp", Ch, prev_states, state_decay)

    y = (y_diag + y_off).reshape(b, s, h, p)
    return y, final


def ssm_apply(p: Params, x: jax.Array, spec: SSMSpec,
              cache: Optional[Params] = None,
              return_state: bool = False) -> tuple[jax.Array, Optional[Params]]:
    """Mamba2 block. Train/prefill when cache is None; else one-token decode.

    cache = {"conv": (B, d_conv-1, conv_dim), "ssm": (B, H, P, N)}.
    """
    b, s, _ = x.shape
    din = spec.d_inner
    gn = spec.n_groups * spec.d_state
    proj = dense_apply({"w": p["in_proj"]}, x)
    # split: z (din) | xbc (din + 2*gn) | dt (n_heads)
    z = proj[..., :din]
    xbc = proj[..., din:2 * din + 2 * gn]
    dt = proj[..., 2 * din + 2 * gn:]

    conv_w = p["conv_w"].astype(x.dtype)  # (d_conv, conv_dim)
    if cache is None:
        pad = jnp.zeros((b, spec.d_conv - 1, xbc.shape[-1]), xbc.dtype)
        xin = jnp.concatenate([pad, xbc], axis=1)
        new_conv = xin[:, -(spec.d_conv - 1):, :] if return_state else None
    else:
        xin = jnp.concatenate([cache["conv"].astype(xbc.dtype), xbc], axis=1)
        new_conv = xin[:, 1:, :]
    # depthwise causal conv1d
    idx = jnp.arange(s)[:, None] + jnp.arange(spec.d_conv)[None, :]
    windows = xin[:, idx, :]                                  # (B, S, d_conv, C)
    xbc = jax.nn.silu(jnp.einsum("bskc,kc->bsc", windows, conv_w) + p["conv_b"].astype(x.dtype))

    xi = xbc[..., :din].reshape(b, s, spec.n_heads, spec.head_dim)
    Bm = xbc[..., din:din + gn].reshape(b, s, spec.n_groups, spec.d_state)
    Cm = xbc[..., din + gn:].reshape(b, s, spec.n_groups, spec.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"])                                     # (H,)

    if cache is None:
        # pad seq to a chunk multiple; dt=0 on pad => state unaffected
        pad_s = (-s) % spec.chunk
        if pad_s:
            padf = lambda a: jnp.pad(a, [(0, 0), (0, pad_s)] + [(0, 0)] * (a.ndim - 2))
            xi_p, dt_p, B_p, C_p = padf(xi), padf(dt), padf(Bm), padf(Cm)
        else:
            xi_p, dt_p, B_p, C_p = xi, dt, Bm, Cm
        y, final_state = ssd_chunked(
            xi_p.astype(jnp.float32), dt_p, A,
            B_p.astype(jnp.float32), C_p.astype(jnp.float32), spec.chunk)
        y = y[:, :s]
        new_cache = ({"conv": new_conv, "ssm": final_state} if return_state else None)
    else:
        # one-step recurrence: h' = h * exp(dt A) + dt * B x ; y = C h'
        rep = spec.n_heads // spec.n_groups
        B1 = jnp.repeat(Bm[:, 0], rep, axis=1)                   # (B,H,N)
        C1 = jnp.repeat(Cm[:, 0], rep, axis=1)
        dt1 = dt[:, 0]                                           # (B,H)
        xv = xi[:, 0].astype(jnp.float32)                        # (B,H,P)
        decay = jnp.exp(dt1 * A[None, :])[..., None, None]       # (B,H,1,1)
        upd = dt1[..., None, None] * xv[..., None] * B1[:, :, None, :].astype(jnp.float32)
        h_new = cache["ssm"].astype(jnp.float32) * decay + upd   # (B,H,P,N)
        y = jnp.einsum("bhpn,bhn->bhp", h_new, C1.astype(jnp.float32))[:, None]
        final_state = h_new
        new_cache = {"conv": new_conv, "ssm": h_new.astype(cache["ssm"].dtype)}

    y = y + xi.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(b, s, din).astype(x.dtype)
    y = rmsnorm_apply(p["norm"], y * jax.nn.silu(z))
    out = dense_apply({"w": p["out_proj"]}, y)
    return out, new_cache


def init_ssm_cache(batch: int, spec: SSMSpec, dtype=jnp.float32) -> Params:
    conv_dim = spec.d_inner + 2 * spec.n_groups * spec.d_state
    return {
        "conv": jnp.zeros((batch, spec.d_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, spec.n_heads, spec.head_dim, spec.d_state), dtype),
    }


# ---------------------------------------------------------------------------
# Embeddings / output head
# ---------------------------------------------------------------------------

def embedding_init(key, vocab: int, d_model: int) -> Params:
    return {"table": jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02}


def embedding_apply(p: Params, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return jnp.take(p["table"].astype(dtype), tokens, axis=0)


def unembed_apply(p: Params, x: jax.Array) -> jax.Array:
    return jnp.einsum("...d,vd->...v", x, p["table"].astype(x.dtype))
