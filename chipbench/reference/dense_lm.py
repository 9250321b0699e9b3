"""Plain decoder-only language model of the Qwen2 kind (arXiv:2407.10671),
float32, with an image prefix of precomputed features.

Per layer: pre-norm RMSNorm; grouped-query causal attention with biases on
q, k and v and rotary embeddings (rotate-half form, base ``rope_theta``);
residual; RMSNorm; SwiGLU MLP ``w2(silu(w1 x) * w3 x)``; residual. Final
RMSNorm and an output head tied to the embedding table, over the padded
vocabulary. A request is ``vision_tokens`` embeddings of width
``vision_width``, mapped by one dense projection, followed by its text
tokens. Parameters are laid out as the system under test holds them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.numerics import einsum, rmsnorm, tree_of


def dims(c: dict) -> dict:
    m = c["pad_vocab_size_multiple"]
    return {"hd": c["hidden_size"] // c["num_attention_heads"],
            "vocab": -(-c["vocab_size"] // m) * m}


def leaves(c: dict) -> list:
    """Every weight as (path, shape, how it is drawn)."""
    z = dims(c)
    L, d, f = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    hq = c["num_attention_heads"] * z["hd"]
    hkv = c["num_key_value_heads"] * z["hd"]
    out = [(("embed", "table"), (z["vocab"], d), ("normal", 0.02)),
           (("layers", "ln1", "scale"), (L, d), ("one_plus", 0.1)),
           (("layers", "ln2", "scale"), (L, d), ("one_plus", 0.1)),
           (("final_norm", "scale"), (d,), ("ones",)),
           (("vision_proj", "w"), (c["vision_width"], d),
            ("normal", c["vision_width"] ** -0.5))]
    for name, i, o, bias in (("wq", d, hq, True), ("wk", d, hkv, True),
                             ("wv", d, hkv, True), ("wo", hq, d, False)):
        out.append((("layers", "attn", name, "w"), (L, i, o),
                    ("normal", i ** -0.5)))
        if bias:
            out.append((("layers", "attn", name, "b"), (L, o),
                        ("normal", 0.02)))
    for name, i, o in (("w1", d, f), ("w2", f, d), ("w3", d, f)):
        out.append((("layers", "mlp", name, "w"), (L, i, o),
                    ("normal", i ** -0.5)))
    return out


def init(key, c: dict) -> dict:
    """Seeded float32 weights, in one traced program."""
    return tree_of(key, leaves(c))


def _rope(x, theta: float):
    """x: (b, s, h, hd); rotate-half rotary embedding at positions 0..s-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(lp: dict, x, c: dict, z: dict, prec: str):
    b, s, d = x.shape
    eps, hd = c["rms_norm_eps"], z["hd"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    h = rmsnorm(x, lp["ln1"]["scale"], eps)
    a = lp["attn"]
    proj = lambda p: einsum("bsd,de->bse", h, p["w"], prec) + p["b"]
    q = _rope(proj(a["wq"]).reshape(b, s, nq, hd), c["rope_theta"])
    k = _rope(proj(a["wk"]).reshape(b, s, nkv, hd), c["rope_theta"])
    v = proj(a["wv"]).reshape(b, s, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    sc = einsum("bthd,buhd->bhtu", q, k, prec) / jnp.sqrt(float(hd))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = einsum("bhtu,buhd->bthd", jax.nn.softmax(sc, axis=-1), v, prec)
    x = x + einsum("bse,ed->bsd", o.reshape(b, s, nq * hd), a["wo"]["w"], prec)
    h = rmsnorm(x, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    g = jax.nn.silu(einsum("bsd,df->bsf", h, m["w1"]["w"], prec)) \
        * einsum("bsd,df->bsf", h, m["w3"]["w"], prec)
    return x + einsum("bsf,fd->bsd", g, m["w2"]["w"], prec)


def logits(params: dict, batch: dict, c: dict, prec: str = "f32"):
    """batch: ``vision`` (b, vision_tokens, vision_width), ``tokens``
    (b, text_tokens). Returns float32 logits (b, s, padded vocab)."""
    z = dims(c)
    vis = einsum("bpv,vd->bpd", batch["vision"], params["vision_proj"]["w"],
                 prec)
    txt = jnp.take(params["embed"]["table"], batch["tokens"], axis=0)
    x = jnp.concatenate([vis, txt], axis=1)

    def layer(x, lp):
        return _layer(lp, x, c, z, prec), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rmsnorm(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    return einsum("bsd,vd->bsv", x, params["embed"]["table"], prec)


def forward_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one forward over ``batch`` requests of ``seq``
    positions: every projection and MLP product, the attention scores and
    values over the whole (masked) square, the output head at every
    position, and the vision projection at the vision positions."""
    z = dims(c)
    d, f = c["hidden_size"], c["intermediate_size"]
    hq = c["num_attention_heads"] * z["hd"]
    hkv = c["num_key_value_heads"] * z["hd"]
    per_token_layer = (2 * d * (2 * hq + 2 * hkv) + 3 * 2 * d * f
                       + 2 * 2 * seq * hq)
    per_token = c["num_hidden_layers"] * per_token_layer + 2 * d * z["vocab"]
    vision = 2 * c["vision_width"] * d * c["vision_tokens"]
    return float(batch * (seq * per_token + vision))
