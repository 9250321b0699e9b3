"""Plain AdamW as the configuration states it: linear warm-up then cosine
decay of the learning rate, clipping by the global gradient norm, bias
correction, and decoupled weight decay on every stored leaf of rank two or
more (with layers stacked on a leading axis that includes the per-layer
norm scales and biases)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def lr_at(o: dict, step: int) -> float:
    if step < o["warmup_steps"]:
        return o["lr"] * step / max(o["warmup_steps"], 1)
    frac = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    r = o["min_lr_ratio"]
    return o["lr"] * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * frac)))


def clipped(grads, o: dict):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["grad_clip"] / (norm + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def update(params, m, v, grads, o: dict, step: int, lr: float):
    """One step from 1-based ``step`` with clipped ``grads``; returns
    (params, m, v)."""
    b1, b2 = o["b1"], o["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def step_leaf(p, mm, vv):
        delta = (mm / (1 - b1 ** step)) / (jnp.sqrt(vv / (1 - b2 ** step))
                                          + o["eps"])
        if p.ndim >= 2:
            delta = delta + o["weight_decay"] * p
        return p - lr * delta

    return jax.tree.map(step_leaf, params, m, v), m, v
