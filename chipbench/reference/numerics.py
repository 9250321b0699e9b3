"""Matrix products of the plain references at a stated precision.

``"f32"`` is float32 at ``Precision.HIGHEST``: on a TPU a float32 product
otherwise runs in bfloat16 passes. ``"fp8"`` is the control: both operands
of every product are rounded to float8 (e4m3, one scale per tensor so the
values use its range), then multiplied with float32 accumulation; the
gradient passes the rounding straight through.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "fp8")
_E4M3_MAX = 448.0


@jax.custom_jvp
def _fake_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


@_fake_fp8.defjvp
def _fake_fp8_jvp(primals, tangents):
    return _fake_fp8(primals[0]), tangents[0]


def einsum(spec: str, a, b, precision: str = "f32"):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}; use {PRECISIONS}")
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _fake_fp8(a), _fake_fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def xent(logits, labels):
    """Mean cross-entropy of float32 logits against integer labels."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def draw(key, shape, how):
    """One weight, from its own key: ``normal`` (scale), ``one_plus``
    (1 + normal x scale), ``ones``, ``log_uniform`` (log of a uniform draw
    in [lo, hi]) or ``dt_bias`` (inverse softplus of a log-uniform step in
    [lo, hi], Mamba's initialisation of dt)."""
    kind = how[0]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * how[1]
    if kind == "one_plus":
        return 1.0 + jax.random.normal(key, shape, jnp.float32) * how[1]
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind == "log_uniform":
        return jnp.log(how[1] + u * (how[2] - how[1]))
    if kind == "dt_bias":
        lo, hi = math.log(how[1]), math.log(how[2])
        return jnp.log(jnp.expm1(jnp.exp(lo + u * (hi - lo))))
    raise ValueError(kind)


def leaf_key(key, path):
    """A weight's key depends on its name alone, so one weight can be drawn
    again without the others."""
    for part in path:
        key = jax.random.fold_in(key, sum(ord(ch) * 131 ** i
                                          for i, ch in enumerate(part))
                                 % (2 ** 31))
    return key


def tree_of(key, leaves):
    tree: dict = {}
    for path, shape, how in leaves:
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = draw(leaf_key(key, path), shape, how)
    return tree
