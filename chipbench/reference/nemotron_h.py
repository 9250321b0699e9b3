"""Plain Nemotron-H language model (``model_type`` ``nemotron_h``; the
family of arXiv:2504.03624, here NVIDIA-Nemotron-3-Nano-30B-A3B), float32,
for training.

Blocks follow ``hybrid_override_pattern``, one character each, and every
block is ``x + mixer(RMSNorm(x))`` (epsilon ``layer_norm_epsilon``):

- ``M``, Mamba-2: ``in_proj`` to [z | x B C | dt] with ``mamba_num_heads``
  heads of ``mamba_head_dim`` (the inner width is their product) and
  ``n_groups`` groups of B and C of ``ssm_state_size``; a depthwise causal
  conv of width ``conv_kernel`` with bias and SiLU over x B C; dt =
  softplus(dt + dt_bias); the SSD recurrence ``h_t = exp(dt_t A) h_{t-1} +
  dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``, each head reading its group's B
  and C, written in its quadratic form over the whole sequence; the gated
  RMSNorm ``norm(y * silu(z))`` taken over each group's slice of the inner
  width (mamba_ssm's ``RMSNormGated(group_size=d_ssm / ngroups)``);
  ``out_proj``. No bias on the projections.
- ``E``, experts: the router scores every one of ``router_experts`` with
  ``sigmoid(x W)``; the top ``num_experts_per_tok`` by score plus
  ``e_score_correction_bias`` are chosen; their unbiased scores, normalised
  to sum to one (``norm_topk_prob``), times ``routed_scaling_factor``, weigh
  them. ``n_group`` = ``topk_group`` = 1, so no group is masked. Each
  expert is ``down(relu(up(x))^2)``, ``moe_intermediate_size`` wide; the
  shared expert is the same, ``moe_shared_expert_intermediate_size`` wide.
  Only the ``n_routed_experts`` experts held here, ids ``held_experts_from``
  onward, are computed, densely: every held expert on every token, weighted
  by its routing weight, which is 0 where it was not chosen. What the
  experts held elsewhere would add is left out. Output: routed + shared.
- ``*``, attention: grouped-query causal attention, ``num_attention_heads``
  query and ``num_key_value_heads`` key/value heads of ``head_dim``, scale
  1/sqrt(``head_dim``), no bias, no position embedding.

A final RMSNorm and an output head of its own (``tie_word_embeddings``
false). Parameters are laid out as the system under test holds them, each
kind's blocks stacked on a leading axis, which is only a naming of the same
numbers.

Departures from the published description, besides the cut the
configuration states: weights are random (``leaves``), not trained, so
``rescale_prenorm_residual`` and the ``time_step_*`` settings shape only
their draws (dt_bias from log-uniform steps in [``time_step_min``,
``time_step_max``], no floor); the selection bias is a weight drawn once,
never updated by the router's load (the published model adjusts it while it
trains), so no gradient reaches it and only weight decay moves it; the
loss is the mean next-token cross-entropy alone, with no load-balancing
term. Where only a share of the experts is held, no gradient passes
through the routing weights (``routing``): in the deployment the gradient
of a chosen expert's weight needs that expert's output, from whichever
chip holds it, and the share alone would turn the router toward the
experts it holds (on one TPU v5e, the held share of the token-choices
grew from 1/16 to 79% within 50 steps). The router and the selection bias then
move only by weight decay, as in fine-tuning with the router frozen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.numerics import einsum, rmsnorm, tree_of, xent

KINDS = {"M": "mamba_layers", "E": "moe_layers", "*": "attn_layers"}


def dims(c: dict) -> dict:
    h, g = c["mamba_num_heads"], c["n_groups"]
    din = h * c["mamba_head_dim"]
    gn = g * c["ssm_state_size"]
    pat = c["hybrid_override_pattern"]
    return {"d": c["hidden_size"], "din": din, "gn": gn, "h": h,
            "conv": din + 2 * gn, "proj": 2 * din + 2 * gn + h,
            "hq": c["num_attention_heads"] * c["head_dim"],
            "hkv": c["num_key_value_heads"] * c["head_dim"],
            "vocab": c["vocab_size"],
            "count": {k: pat.count(k) for k in KINDS}}


def leaves(c: dict) -> list:
    """Every weight as (path, shape, how it is drawn)."""
    z = dims(c)
    d, n = z["d"], z["count"]
    held, f, fs = (c["n_routed_experts"], c["moe_intermediate_size"],
                   c["moe_shared_expert_intermediate_size"])
    out = [(("embed", "table"), (z["vocab"], d), ("normal", 0.02)),
           (("head", "table"), (z["vocab"], d), ("normal", 0.02)),
           (("final_norm", "scale"), (d,), ("ones",))]
    for kind, key in KINDS.items():
        if n[kind]:
            out.append(((key, "ln", "scale"), (n[kind], d), ("one_plus", 0.1)))
    M, E, A = n["M"], n["E"], n["*"]
    if M:
        out += [
            (("mamba_layers", "ssm", "in_proj"), (M, d, z["proj"]),
             ("normal", d ** -0.5)),
            (("mamba_layers", "ssm", "conv_w"), (M, c["conv_kernel"], z["conv"]),
             ("normal", 0.1)),
            (("mamba_layers", "ssm", "conv_b"), (M, z["conv"]), ("normal", 0.02)),
            (("mamba_layers", "ssm", "A_log"), (M, z["h"]),
             ("log_uniform", 1.0, 16.0)),
            (("mamba_layers", "ssm", "D"), (M, z["h"]), ("ones",)),
            (("mamba_layers", "ssm", "dt_bias"), (M, z["h"]),
             ("dt_bias", c["time_step_min"], c["time_step_max"])),
            (("mamba_layers", "ssm", "norm", "scale"), (M, z["din"]),
             ("one_plus", 0.1)),
            (("mamba_layers", "ssm", "out_proj"), (M, z["din"], d),
             ("normal", z["din"] ** -0.5))]
    if E:
        out += [
            (("moe_layers", "moe", "router"), (E, d, c["router_experts"]),
             ("normal", d ** -0.5)),
            (("moe_layers", "moe", "router_bias"), (E, c["router_experts"]),
             ("normal", 0.01)),
            (("moe_layers", "moe", "up"), (E, held, d, f), ("normal", d ** -0.5)),
            (("moe_layers", "moe", "down"), (E, held, f, d), ("normal", f ** -0.5)),
            (("moe_layers", "moe", "shared", "w1", "w"), (E, d, fs),
             ("normal", d ** -0.5)),
            (("moe_layers", "moe", "shared", "w2", "w"), (E, fs, d),
             ("normal", fs ** -0.5))]
    if A:
        for name, i, o in (("wq", d, z["hq"]), ("wk", d, z["hkv"]),
                           ("wv", d, z["hkv"]), ("wo", z["hq"], d)):
            out.append((("attn_layers", "attn", name, "w"), (A, i, o),
                        ("normal", i ** -0.5)))
    return out


def init(key, c: dict) -> dict:
    """Seeded float32 weights, in one traced program."""
    return tree_of(key, leaves(c))


def _ssd_row(xs, dt, Bm, Cm, A, prec: str):
    """One row's SSD in quadratic form: xs (s, h, p), dt (s, h), Bm and Cm
    (s, h, n), each head with its group's B and C, A (h,)."""
    s = xs.shape[0]
    cs = jnp.cumsum(dt * A, axis=0)                              # (s, h)
    seg = cs[:, None, :] - cs[None, :, :]                        # (t, u, h)
    causal = jnp.tril(jnp.ones((s, s), bool))[:, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    w = einsum("thn,uhn->tuh", Cm, Bm, prec) * decay * dt[None, :, :]
    return einsum("tuh,uhp->thp", w, xs, prec)


def _mamba(p: dict, x, c: dict, z: dict, prec: str):
    b, s, _ = x.shape
    din, gn, h, hd = z["din"], z["gn"], z["h"], c["mamba_head_dim"]
    g = c["n_groups"]
    proj = einsum("bsd,de->bse", x, p["in_proj"], prec)
    zg, xbc, dt = (proj[..., :din], proj[..., din:din + z["conv"]],
                   proj[..., din + z["conv"]:])
    k = c["conv_kernel"]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + s] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :din].reshape(b, s, h, hd)
    Bm = jnp.repeat(xbc[..., din:din + gn].reshape(b, s, g, -1), h // g, 2)
    Cm = jnp.repeat(xbc[..., din + gn:].reshape(b, s, g, -1), h // g, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                      # (b, s, h)
    A = -jnp.exp(p["A_log"])
    # one row at a time, so the (s, s, h) decay of every row is never held
    row = jax.checkpoint(lambda r: _ssd_row(*r, A, prec))
    y = jax.lax.map(row, (xs, dt, Bm, Cm))
    y = y + xs * p["D"][:, None]
    y = (y.reshape(b, s, din) * jax.nn.silu(zg)).reshape(b, s, g, din // g)
    scale = p["norm"]["scale"].reshape(g, din // g)
    y = rmsnorm(y, scale, c["layer_norm_epsilon"]).reshape(b, s, din)
    return einsum("bse,ed->bsd", y, p["out_proj"], prec)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def routing(p: dict, x, c: dict, prec: str):
    """The routing weight of every held expert for every token (b, s,
    held): the chosen ones' weights, 0 elsewhere; constants to the
    gradient where only a share of the experts is held."""
    scores = jax.nn.sigmoid(einsum("bsd,de->bse", x, p["router"], prec))
    _, idx = jax.lax.top_k(scores + p["router_bias"],
                           c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * c["routed_scaling_factor"]
    if c["n_routed_experts"] < c["router_experts"]:
        w = jax.lax.stop_gradient(w)
    held = jax.nn.one_hot(idx - c["held_experts_from"], c["n_routed_experts"])
    return jnp.einsum("bske,bsk->bse", held, w)


def _experts(p: dict, x, c: dict, z: dict, prec: str):
    gate = routing(p, x, c, prec)
    h = _relu2(einsum("bsd,edf->bsef", x, p["up"], prec)) * gate[..., None]
    routed = einsum("bsef,efd->bsd", h, p["down"], prec)
    sh = p["shared"]
    shared = einsum("bsf,fd->bsd", _relu2(einsum(
        "bsd,df->bsf", x, sh["w1"]["w"], prec)), sh["w2"]["w"], prec)
    return routed + shared


def _attention(p: dict, x, c: dict, z: dict, prec: str):
    b, s, _ = x.shape
    nq, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    proj = lambda name: einsum("bsd,de->bse", x, p[name]["w"], prec)
    q = proj("wq").reshape(b, s, nq, hd)
    k = jnp.repeat(proj("wk").reshape(b, s, nkv, hd), nq // nkv, axis=2)
    v = jnp.repeat(proj("wv").reshape(b, s, nkv, hd), nq // nkv, axis=2)
    sc = einsum("bthd,buhd->bhtu", q, k, prec) / jnp.sqrt(float(hd))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = einsum("bhtu,buhd->bthd", jax.nn.softmax(sc, axis=-1), v, prec)
    return einsum("bse,ed->bsd", o.reshape(b, s, nq * hd), p["wo"]["w"], prec)


MIXERS = {"M": ("ssm", _mamba), "E": ("moe", _experts), "*": ("attn", _attention)}


def logits(params: dict, tokens, c: dict, prec: str = "f32"):
    z = dims(c)
    eps = c["layer_norm_epsilon"]
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    seen = {k: 0 for k in KINDS}
    for kind in c["hybrid_override_pattern"]:
        lp = jax.tree.map(lambda a: a[seen[kind]], params[KINDS[kind]])
        seen[kind] += 1
        name, mixer = MIXERS[kind]

        @jax.checkpoint
        def block(x, lp, mixer=mixer, name=name):
            return x + mixer(lp[name], rmsnorm(x, lp["ln"]["scale"], eps),
                             c, z, prec)
        x = block(x, lp)
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    return einsum("bsd,vd->bsv", x, params["head"]["table"], prec)


def loss(params: dict, batch: dict, c: dict, prec: str = "f32"):
    return xent(logits(params, batch["tokens"], c, prec), batch["labels"])


def train_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: 3x the forward's products
    (forward, and the backward's two products per forward product);
    recomputation is not counted. Per token: for each ``M``, in_proj,
    out_proj, the conv, and the SSD's quadratic form over the sequence (C.B
    once per group over the state, then the weighted sum over the heads'
    values); for each ``E``, the router, the shared expert, and the
    ``num_experts_per_tok`` x held / ``router_experts`` routed experts a
    token places here on average; for each ``*``, the four projections and
    the scores and values over the whole (masked) square; the output head
    once."""
    z = dims(c)
    d, n = z["d"], z["count"]
    mamba = (2 * d * z["proj"] + 2 * z["din"] * d
             + 2 * c["conv_kernel"] * z["conv"]
             + 2 * seq * (z["gn"] + z["din"]))
    expert = 2 * 2 * d * c["moe_intermediate_size"]
    per_tok = (c["num_experts_per_tok"] * c["n_routed_experts"]
               / c["router_experts"])
    moe = (2 * d * c["router_experts"]
           + 2 * 2 * d * c["moe_shared_expert_intermediate_size"]
           + per_tok * expert)
    attn = 2 * d * (2 * z["hq"] + 2 * z["hkv"]) + 2 * 2 * seq * z["hq"]
    fwd = batch * seq * (n["M"] * mamba + n["E"] * moe + n["*"] * attn
                         + 2 * d * z["vocab"])
    return 3.0 * fwd
