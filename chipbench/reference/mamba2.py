"""Plain Mamba-2 language model (arXiv:2405.21060), float32, for training.

Per layer: pre-norm RMSNorm; ``in_proj`` to [z | x B C | dt]; a depthwise
causal conv (width ``d_conv``, with bias) and SiLU over x B C; dt =
softplus(dt + dt_bias); the SSD recurrence
``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``
written in its quadratic (attention-like) form over the whole sequence;
gated RMSNorm ``norm(y * silu(z))``; ``out_proj``; residual add. Final
RMSNorm and an output head tied to the embedding table, over the padded
vocabulary. The parameters are laid out as the system under test holds them
(layers stacked on a leading axis), which is only a naming of the same
numbers.

Sizes come from the configuration file: ``d_model``, ``n_layer``,
``d_state``, ``d_conv``, ``expand``, ``headdim``, ``ngroups``,
``vocab_size``, ``pad_vocab_size_multiple``, ``norm_epsilon``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.numerics import einsum, rmsnorm, tree_of, xent


def dims(c: dict) -> dict:
    d = c["d_model"]
    din = c["expand"] * d
    gn = c["ngroups"] * c["d_state"]
    h = din // c["headdim"]
    m = c["pad_vocab_size_multiple"]
    return {"d": d, "din": din, "gn": gn, "h": h,
            "proj": 2 * din + 2 * gn + h, "conv": din + 2 * gn,
            "vocab": -(-c["vocab_size"] // m) * m}


def leaves(c: dict) -> list:
    """Every weight as (path, shape, how it is drawn)."""
    z = dims(c)
    L, d, h = c["n_layer"], z["d"], z["h"]
    return [
        (("embed", "table"), (z["vocab"], d), ("normal", 0.02)),
        (("layers", "ln", "scale"), (L, d), ("one_plus", 0.1)),
        (("layers", "ssm", "in_proj"), (L, d, z["proj"]), ("normal", d ** -0.5)),
        (("layers", "ssm", "conv_w"), (L, c["d_conv"], z["conv"]),
         ("normal", 0.1)),
        (("layers", "ssm", "conv_b"), (L, z["conv"]), ("normal", 0.02)),
        (("layers", "ssm", "A_log"), (L, h), ("log_uniform", 1.0, 16.0)),
        (("layers", "ssm", "D"), (L, h), ("ones",)),
        (("layers", "ssm", "dt_bias"), (L, h), ("dt_bias", 1e-3, 0.1)),
        (("layers", "ssm", "norm", "scale"), (L, z["din"]), ("one_plus", 0.1)),
        (("layers", "ssm", "out_proj"), (L, z["din"], d),
         ("normal", z["din"] ** -0.5)),
        (("final_norm", "scale"), (d,), ("ones",)),
    ]


def init(key, c: dict) -> dict:
    """Seeded float32 weights, in one traced program."""
    return tree_of(key, leaves(c))


def _mixer(p: dict, x, c: dict, z: dict, prec: str):
    b, s, _ = x.shape
    din, gn, h, hd = z["din"], z["gn"], z["h"], c["headdim"]
    proj = einsum("bsd,de->bse", x, p["in_proj"], prec)
    zg, xbc, dt = (proj[..., :din], proj[..., din:din + z["conv"]],
                   proj[..., din + z["conv"]:])
    k = c["d_conv"]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + s] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :din].reshape(b, s, h, hd)
    g = c["ngroups"]
    Bm = jnp.repeat(xbc[..., din:din + gn].reshape(b, s, g, -1), h // g, 2)
    Cm = jnp.repeat(xbc[..., din + gn:].reshape(b, s, g, -1), h // g, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                      # (b,s,h)
    dA = dt * -jnp.exp(p["A_log"])
    cs = jnp.cumsum(dA, axis=1)                                  # (b,s,h)
    seg = cs[:, :, None, :] - cs[:, None, :, :]                  # (b,t,u,h)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = einsum("bthn,buhn->btuh", Cm, Bm, prec)
    w = cb * decay * dt[:, None, :, :]
    y = einsum("btuh,buhp->bthp", w, xs, prec)
    y = y + xs * p["D"][:, None]
    y = y.reshape(b, s, din)
    y = rmsnorm(y * jax.nn.silu(zg), p["norm"]["scale"], c["norm_epsilon"])
    return einsum("bse,ed->bsd", y, p["out_proj"], prec)


def logits(params: dict, tokens, c: dict, prec: str = "f32"):
    z = dims(c)
    eps = c["norm_epsilon"]
    x = jnp.take(params["embed"]["table"], tokens, axis=0)

    @jax.checkpoint
    def layer(x, lp):
        h = rmsnorm(x, lp["ln"]["scale"], eps)
        return x + _mixer(lp["ssm"], h, c, z, prec), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    return einsum("bsd,vd->bsv", x, params["embed"]["table"], prec)


def loss(params: dict, batch: dict, c: dict, prec: str = "f32"):
    return xent(logits(params, batch["tokens"], c, prec), batch["labels"])


def train_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: 3x the forward's products
    (forward, and the backward's two products per forward product);
    recomputation is not counted. Per token and layer: in_proj, out_proj,
    the conv, and the SSD's quadratic form over the sequence (C.B once per
    group over the state, then the weighted sum over the heads' values);
    the output head once per token."""
    z = dims(c)
    d = z["d"]
    per_token_layer = (2 * d * z["proj"] + 2 * z["din"] * d
                       + 2 * c["d_conv"] * z["conv"]
                       + 2 * seq * (z["gn"] + z["din"]))
    fwd = batch * seq * (c["n_layer"] * per_token_layer + 2 * d * z["vocab"])
    return 3.0 * fwd
