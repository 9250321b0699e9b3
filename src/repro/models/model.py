"""Decoder-LM skeleton covering the assigned architecture families.

One config-driven model: dense / MoE / SSM (Mamba2-SSD) / hybrid (Zamba2) /
VLM backbone / audio backbone / nemotron_h (a stack laid out by a pattern of
Mamba-2, expert and attention blocks). Homogeneous layer stacks are
parameterised as leading-axis-stacked pytrees and executed with
``jax.lax.scan`` so HLO size is O(1) in depth (essential for 56-layer
full-size dry-run compiles); a pattern stack keeps one stack per block kind
and scans the pattern, switching on each layer's kind.

Entry points:
  init_params(key, cfg)                      -> params
  forward(params, batch, cfg)                -> logits (train / prefill)
  train_loss(params, batch, cfg)             -> (loss, metrics)
  init_cache(cfg, batch, cache_len)          -> decode cache
  decode_step(params, cache, batch, pos, cfg)-> (logits, new_cache)
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# nemotron_h block kinds: Mamba-2, experts, attention; each kind's params
# are stacked under its own key
PATTERN_KINDS = ("M", "E", "*")
PATTERN_STACKS = {"M": "mamba_layers", "E": "moe_layers", "*": "attn_layers"}


# ---------------------------------------------------------------------------
# activation-sharding hook
#
# With FSDP/ZeRO param sharding, GSPMD would otherwise propagate the *weight*
# sharding into activations (replicating the batch on every device). The
# launcher installs a with_sharding_constraint here that re-pins (B, S, d)
# activations to (batch->data axes, None, None) at every layer boundary, so
# the compiler all-gathers weights (small, per layer) instead of activations.
# ---------------------------------------------------------------------------

_ACT_CONSTRAINT = None


@contextlib.contextmanager
def activation_sharding(fn):
    """fn: jax.Array -> jax.Array (typically a with_sharding_constraint).
    Applied to rank-3 (B, S, d) tensors at layer boundaries and, when heads
    don't divide the model axis (e.g. Qwen's 40 heads on 16-way TP), to
    rank-4 attention internals so the unavoidable reshard happens once, in
    bf16, at an explicit point (#Perf hillclimb B)."""
    global _ACT_CONSTRAINT
    prev = _ACT_CONSTRAINT
    _ACT_CONSTRAINT = fn
    try:
        yield
    finally:
        _ACT_CONSTRAINT = prev


def constrain(x: jax.Array) -> jax.Array:
    if _ACT_CONSTRAINT is not None:
        return _ACT_CONSTRAINT(x)
    return x


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                      # dense | moe | ssm | hybrid | vlm | audio | nemotron_h
    num_layers: int
    d_model: int
    n_heads: int                        # 0 for attention-free (ssm)
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                   # 0 -> d_model // n_heads
    norm: str = "rmsnorm"
    norm_eps: float = 1e-6              # RMSNorm's, in nemotron_h and the SSM's gated norm
    activation: str = "swiglu"
    qkv_bias: bool = False
    sliding_window: Optional[int] = None      # training-time SWA (Mixtral)
    rope_theta: float = 10_000.0
    rotary: bool = True
    tie_embeddings: bool = True         # False: an output head of its own
    # MoE
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_group_size: int = 1024
    moe_dense_residual: bool = False
    moe_aux_weight: float = 0.01
    # nemotron_h experts: routed over n_experts, ids [experts_held_lo,
    # experts_held_lo + experts_held) held here (0: all), d_ff wide, plus a
    # shared expert moe_shared_ff wide
    experts_held: int = 0
    experts_held_lo: int = 0
    moe_shared_ff: int = 0
    routed_scaling: float = 1.0
    # SSM
    ssm_state: int = 0
    ssm_chunk: int = 256
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_heads: int = 0                  # 0: ssm_expand * d_model // ssm_head_dim
    # nemotron_h: one character per layer, M (Mamba-2), E (experts), * (attention)
    layer_pattern: str = ""
    # hybrid (Zamba2): shared attention block every `attn_every` SSM layers
    attn_every: int = 6
    # VLM stub frontend
    n_patches: int = 256
    d_vision: int = 1024
    # audio stub frontend (EnCodec codebooks)
    n_codebooks: int = 4
    # serving
    kv_cache_quant: bool = False        # int8 KV cache with bf16 scales
    long_context_mode: str = "native"   # native | swa (ring-buffer window)
    serve_window: int = 8192
    swa_activation_len: int = 65536     # swa mode kicks in beyond this context
    # numerics / memory
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    unroll: bool = False               # unroll layer scans (dry-run cost analysis)
    vocab_pad_multiple: int = 2048
    # provenance
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def padded_vocab(self) -> int:
        return round_up(self.vocab_size, self.vocab_pad_multiple)

    @property
    def attn_spec(self) -> L.AttnSpec:
        return L.AttnSpec(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.resolved_head_dim,
            qkv_bias=self.qkv_bias, sliding_window=self.sliding_window,
            rope_theta=self.rope_theta, unroll=self.unroll, rotary=self.rotary)

    @property
    def moe_spec(self) -> L.MoeSpec:
        return L.MoeSpec(
            d_model=self.d_model, d_ff=self.d_ff, n_experts=self.n_experts,
            top_k=self.top_k, capacity_factor=self.capacity_factor,
            group_size=self.moe_group_size,
            dense_residual=self.moe_dense_residual,
            dense_residual_ff=self.d_ff)

    @property
    def held_moe_spec(self) -> L.HeldMoeSpec:
        return L.HeldMoeSpec(
            d_model=self.d_model, d_ff=self.d_ff, d_shared=self.moe_shared_ff,
            n_experts=self.n_experts, top_k=self.top_k,
            held=self.experts_held or self.n_experts,
            held_lo=self.experts_held_lo, scaling=self.routed_scaling)

    @property
    def ssm_spec(self) -> L.SSMSpec:
        return L.SSMSpec(
            d_model=self.d_model, d_state=self.ssm_state,
            expand=self.ssm_expand, head_dim=self.ssm_head_dim,
            n_groups=self.ssm_groups, chunk=self.ssm_chunk,
            heads=self.ssm_heads, norm_eps=self.norm_eps)

    @property
    def pattern_kinds(self) -> tuple[str, ...]:
        """The block kinds of a nemotron_h pattern, in a fixed order."""
        return tuple(k for k in PATTERN_KINDS if k in self.layer_pattern)

    @property
    def n_attn_sites(self) -> int:
        """Number of shared-attention applications in a hybrid stack."""
        if self.arch_type != "hybrid":
            return 0
        return len([i for i in range(self.num_layers) if i % self.attn_every == 0])

    def _pattern_layer_params(self) -> dict:
        """Parameters of one block of each nemotron_h kind, its norm included."""
        d, hd, m, s = (self.d_model, self.resolved_head_dim,
                       self.held_moe_spec, self.ssm_spec)
        conv = s.d_inner + 2 * s.n_groups * s.d_state
        return {
            "M": d + d * (s.d_inner + conv + s.n_heads) + (s.d_conv + 1) * conv
                 + 3 * s.n_heads + s.d_inner + s.d_inner * d,
            "E": d + (d + 1) * m.n_experts + m.held * 2 * d * m.d_ff
                 + 2 * d * m.d_shared,
            "*": d + d * hd * (self.n_heads + 2 * self.n_kv_heads)
                 + self.n_heads * hd * d,
        }

    def param_count(self) -> int:
        """Analytic parameter count (embedding + stack + head)."""
        d, f, v = self.d_model, self.d_ff, self.padded_vocab
        hd = self.resolved_head_dim
        if self.arch_type == "nemotron_h":
            per = self._pattern_layer_params()
            heads = 1 if self.tie_embeddings else 2
            return sum(per[k] for k in self.layer_pattern) + heads * v * d + d
        per_layer = 0
        if self.arch_type in ("dense", "vlm", "audio"):
            attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            mlp = d * f * (3 if self.activation == "swiglu" else 2)
            per_layer = attn + mlp + 2 * d
        elif self.arch_type == "moe":
            attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            moe = self.n_experts * 3 * d * f + d * self.n_experts
            if self.moe_dense_residual:
                moe += 3 * d * f
            per_layer = attn + moe + 2 * d
        elif self.arch_type in ("ssm", "hybrid"):
            s = self.ssm_spec
            din = s.d_inner
            gn = s.n_groups * s.d_state
            per_layer = d * (2 * din + 2 * gn + s.n_heads) + din * d + s.d_conv * (din + 2 * gn) + 2 * din
        total = self.num_layers * per_layer + v * d
        if self.arch_type == "hybrid":
            attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            total += attn + 3 * d * f + 4 * d   # one shared block
        if self.arch_type == "vlm":
            total += self.d_vision * d
        if self.arch_type == "audio":
            total += (self.n_codebooks - 1) * v * d
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only; nemotron_h:
        of the routed experts held, the top_k x held / n_experts a token
        places on them on average)."""
        if self.arch_type == "nemotron_h":
            m = self.held_moe_spec
            expert = 2 * self.d_model * m.d_ff
            n_e = self.layer_pattern.count("E")
            routed = n_e * m.held * expert
            active = n_e * m.top_k * m.held * expert / m.n_experts
            return self.param_count() - routed + round(active)
        if self.arch_type != "moe":
            return self.param_count()
        d, f = self.d_model, self.d_ff
        dense_like = self.param_count() - self.num_layers * self.n_experts * 3 * d * f
        active = self.num_layers * (self.top_k + (1 if self.moe_dense_residual else 0)) * 3 * d * f
        return dense_like + active


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """A nemotron_h RMSNorm, at the configured epsilon."""
    return L.rmsnorm_apply(p, x, cfg.norm_eps)


def _pattern_block_init(key, cfg: ModelConfig, kind: str) -> dict:
    """Params of ONE nemotron_h block of ``kind`` (unstacked)."""
    ln = L.norm_init(cfg.norm, cfg.d_model)
    if kind == "M":
        return {"ln": ln, "ssm": L.ssm_init(key, cfg.ssm_spec)}
    if kind == "E":
        return {"ln": ln, "moe": L.held_moe_init(key, cfg.held_moe_spec)}
    return {"ln": ln, "attn": L.attention_init(key, cfg.attn_spec)}


def _layer_init(key, cfg: ModelConfig) -> dict:
    """Params of ONE layer (unstacked)."""
    d = cfg.d_model
    if cfg.arch_type in ("dense", "vlm", "audio"):
        k1, k2 = jax.random.split(key)
        return {
            "ln1": L.norm_init(cfg.norm, d),
            "attn": L.attention_init(k1, cfg.attn_spec),
            "ln2": L.norm_init(cfg.norm, d),
            "mlp": L.mlp_init(k2, d, cfg.d_ff, activation=cfg.activation),
        }
    if cfg.arch_type == "moe":
        k1, k2 = jax.random.split(key)
        return {
            "ln1": L.norm_init(cfg.norm, d),
            "attn": L.attention_init(k1, cfg.attn_spec),
            "ln2": L.norm_init(cfg.norm, d),
            "moe": L.moe_init(k2, cfg.moe_spec),
        }
    if cfg.arch_type in ("ssm", "hybrid"):
        return {
            "ln": L.norm_init(cfg.norm, d),
            "ssm": L.ssm_init(key, cfg.ssm_spec),
        }
    raise ValueError(cfg.arch_type)


def _apply_param_dtype(params: dict, cfg: ModelConfig) -> dict:
    """Cast weight matrices to cfg.param_dtype; keep 1-D params (norms,
    biases, A_log/D/dt_bias) in fp32 for stability."""
    if cfg.param_dtype == jnp.float32:
        return params
    return jax.tree.map(
        lambda x: x.astype(cfg.param_dtype) if x.ndim >= 2 else x, params)


def init_params(key, cfg: ModelConfig) -> dict:
    ke, kl, kx = jax.random.split(key, 3)
    params = {
        "embed": L.embedding_init(ke, cfg.padded_vocab, cfg.d_model),
        "final_norm": L.norm_init(cfg.norm, cfg.d_model),
    }
    if cfg.arch_type == "nemotron_h":
        if len(cfg.layer_pattern) != cfg.num_layers:
            raise ValueError(f"pattern {cfg.layer_pattern!r} has "
                             f"{len(cfg.layer_pattern)} layers, not "
                             f"{cfg.num_layers}")
        for i, kind in enumerate(cfg.pattern_kinds):
            keys = jax.random.split(jax.random.fold_in(kl, i),
                                    cfg.layer_pattern.count(kind))
            params[PATTERN_STACKS[kind]] = jax.vmap(
                lambda k: _pattern_block_init(k, cfg, kind))(keys)
    else:
        layer_keys = jax.random.split(kl, cfg.num_layers)
        params["layers"] = jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys)
    if not cfg.tie_embeddings:
        params["head"] = L.embedding_init(jax.random.fold_in(ke, 1),
                                          cfg.padded_vocab, cfg.d_model)
    if cfg.arch_type == "hybrid":
        k1, k2 = jax.random.split(kx)
        params["shared_attn"] = {
            "ln1": L.norm_init(cfg.norm, cfg.d_model),
            "attn": L.attention_init(k1, cfg.attn_spec),
            "ln2": L.norm_init(cfg.norm, cfg.d_model),
            "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, activation=cfg.activation),
        }
    if cfg.arch_type == "vlm":
        params["vision_proj"] = L.dense_init(kx, cfg.d_vision, cfg.d_model)
    if cfg.arch_type == "audio":
        keys = jax.random.split(kx, cfg.n_codebooks - 1)
        params["embed_cb"] = jax.vmap(
            lambda k: L.embedding_init(k, cfg.padded_vocab, cfg.d_model))(keys)
    return _apply_param_dtype(params, cfg)


# ---------------------------------------------------------------------------
# embedding frontends
# ---------------------------------------------------------------------------

def embed_inputs(params: dict, batch: dict, cfg: ModelConfig) -> jax.Array:
    """Map a batch to (B, S, d_model) in compute dtype."""
    dt = cfg.compute_dtype
    if cfg.arch_type == "audio":
        toks = batch["tokens"]                                     # (B, S, CB)
        x = L.embedding_apply(params["embed"], toks[..., 0], dt)
        for i in range(cfg.n_codebooks - 1):
            tab = jax.tree.map(lambda t: t[i], params["embed_cb"])
            x = x + L.embedding_apply(tab, toks[..., i + 1], dt)
        return x
    if cfg.arch_type == "vlm":
        txt = L.embedding_apply(params["embed"], batch["tokens"], dt)   # (B, St, d)
        if "vision" not in batch:          # decode: text tokens only
            return txt
        vis = L.dense_apply(params["vision_proj"], batch["vision"].astype(dt))
        return jnp.concatenate([vis, txt], axis=1)
    return L.embedding_apply(params["embed"], batch["tokens"], dt)


def output_logits(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.arch_type == "nemotron_h":
        return L.unembed_apply(params["head"], _norm(cfg, params["final_norm"], x))
    x = L.norm_apply(cfg.norm, params["final_norm"], x)
    if cfg.arch_type == "audio":
        outs = [L.unembed_apply(params["embed"], x)]
        for i in range(cfg.n_codebooks - 1):
            tab = jax.tree.map(lambda t: t[i], params["embed_cb"])
            outs.append(L.unembed_apply(tab, x))
        return jnp.stack(outs, axis=-2)                            # (B,S,CB,V)
    return L.unembed_apply(params["embed"], x)


# ---------------------------------------------------------------------------
# transformer stack (train / prefill)
# ---------------------------------------------------------------------------

def _dense_block(lp, x, positions, cfg: ModelConfig, cache=None, cpos=None):
    h, kv = L.attention_apply(lp["attn"], L.norm_apply(cfg.norm, lp["ln1"], x),
                              positions, cfg.attn_spec, cache, cpos)
    x = x + h
    mixer = lp.get("moe")
    aux = jnp.zeros((), jnp.float32)
    if mixer is not None:
        h, aux = L.moe_apply(mixer, L.norm_apply(cfg.norm, lp["ln2"], x), cfg.moe_spec)
    else:
        h = L.mlp_apply(lp["mlp"], L.norm_apply(cfg.norm, lp["ln2"], x), cfg.activation)
    return x + h, aux, kv


def _hybrid_shared(params, x, positions, cfg: ModelConfig, cache=None, cpos=None):
    sp = params["shared_attn"]
    spec = cfg.attn_spec
    h, kv = L.attention_apply(sp["attn"], L.norm_apply(cfg.norm, sp["ln1"], x),
                              positions, spec, cache, cpos)
    x = x + h
    x = x + L.mlp_apply(sp["mlp"], L.norm_apply(cfg.norm, sp["ln2"], x), cfg.activation)
    return x, kv


MOE_COUNTS = ("moe_choices", "moe_held", "moe_load_max", "moe_rows",
              "moe_experts")


def _pattern_stack(params, x, positions, cfg: ModelConfig):
    """The nemotron_h stack: ``x + mixer(norm(x))`` for each layer of the
    pattern, scanned over the layers. Each step takes one block of every
    kind's stack at that kind's rank so far and switches on the layer's
    kind, so the program holds one block of each kind whatever the depth.
    Returns (x, counts): the expert layers' routing counts, summed over
    them (``moe_experts``, the experts each holds, is their most)."""
    kinds = cfg.pattern_kinds
    stacks = [params[PATTERN_STACKS[k]] for k in kinds]
    pat = cfg.layer_pattern
    kind_id = jnp.array([kinds.index(c) for c in pat], jnp.int32)
    rank = jnp.array([[min(pat[:i].count(k), pat.count(k) - 1) for k in kinds]
                      for i in range(len(pat))], jnp.int32)
    zero = {k: jnp.zeros((), jnp.int32) for k in MOE_COUNTS}

    def mamba(x, lp):
        h, _ = L.ssm_apply(lp["ssm"], _norm(cfg, lp["ln"], x), cfg.ssm_spec)
        return x + h, zero

    def experts(x, lp):
        h, counts = L.held_moe_apply(lp["moe"], _norm(cfg, lp["ln"], x),
                                     cfg.held_moe_spec)
        return x + h, counts

    def attention(x, lp):
        h, _ = L.attention_apply(lp["attn"], _norm(cfg, lp["ln"], x),
                                 positions, cfg.attn_spec)
        return x + h, zero

    fns = {"M": mamba, "E": experts, "*": attention}
    if cfg.remat:
        # a switch's linearisation keeps every branch's residuals, zeros for
        # the branches not taken: rematerialised, each keeps only its inputs
        fns = {k: jax.checkpoint(f, prevent_cse=False) for k, f in fns.items()}

    def body(x, inp):
        x = constrain(x)
        kid, r = inp
        blocks = [jax.tree.map(lambda a: lax.dynamic_index_in_dim(
            a, r[i], keepdims=False), st) for i, st in enumerate(stacks)]
        branches = [lambda x, bl, i=i, k=k: fns[k](x, bl[i])
                    for i, k in enumerate(kinds)]
        return lax.switch(kid, branches, x, blocks)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, counts = lax.scan(body, x, (kind_id, rank), unroll=cfg.unroll)
    return x, {k: jnp.max(v) if k == "moe_experts" else jnp.sum(v)
               for k, v in counts.items()}


def _pattern_forward(params: dict, batch: dict, cfg: ModelConfig):
    x = constrain(embed_inputs(params, batch, cfg))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, counts = _pattern_stack(params, x, positions, cfg)
    return output_logits(params, x, cfg), counts


def forward(params: dict, batch: dict, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward (train / prefill). Returns (logits, moe_aux)."""
    if cfg.arch_type == "nemotron_h":
        return _pattern_forward(params, batch, cfg)[0], jnp.zeros((), jnp.float32)
    x = constrain(embed_inputs(params, batch, cfg))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        def body(x, lp):
            x = constrain(x)
            x, aux, _ = _dense_block(lp, x, positions, cfg)
            return x, aux
        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, auxs = lax.scan(body, x, params["layers"], unroll=cfg.unroll)
        return output_logits(params, x, cfg), jnp.mean(auxs)

    if cfg.arch_type == "ssm":
        def body(x, lp):
            x = constrain(x)
            h, _ = L.ssm_apply(lp["ssm"], L.norm_apply(cfg.norm, lp["ln"], x), cfg.ssm_spec)
            return x + h, jnp.zeros((), jnp.float32)
        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = lax.scan(body, x, params["layers"], unroll=cfg.unroll)
        return output_logits(params, x, cfg), jnp.zeros((), jnp.float32)

    if cfg.arch_type == "hybrid":
        shared = params["shared_attn"]
        is_attn = jnp.array([i % cfg.attn_every == 0 for i in range(cfg.num_layers)])

        def body(x, inp):
            x = constrain(x)
            lp, attn_here = inp

            def with_attn(x):
                h, _ = L.attention_apply(
                    shared["attn"], L.norm_apply(cfg.norm, shared["ln1"], x),
                    positions, cfg.attn_spec)
                x = x + h
                return x + L.mlp_apply(shared["mlp"],
                                       L.norm_apply(cfg.norm, shared["ln2"], x),
                                       cfg.activation)

            x = lax.cond(attn_here, with_attn, lambda x: x, x)
            h, _ = L.ssm_apply(lp["ssm"], L.norm_apply(cfg.norm, lp["ln"], x), cfg.ssm_spec)
            return x + h, jnp.zeros((), jnp.float32)

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = lax.scan(body, x, (params["layers"], is_attn), unroll=cfg.unroll)
        return output_logits(params, x, cfg), jnp.zeros((), jnp.float32)

    raise ValueError(cfg.arch_type)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    # one-hot contraction (not take_along_axis) so a vocab-sharded logits
    # tensor reduces to partial sums + a tiny all-reduce under GSPMD instead
    # of an all-gather of the full logits.
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.sum(logits * onehot, axis=-1)
    return logz - gold


def train_loss(params: dict, batch: dict, cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """Mean next-token cross-entropy (plus the MoE auxiliary loss), and its
    metrics; a nemotron_h stack adds its routing counts (``MOE_COUNTS``)."""
    if cfg.arch_type == "nemotron_h":
        logits, counts = _pattern_forward(params, batch, cfg)
        xent = jnp.mean(softmax_xent(logits, batch["labels"]))
        return xent, {"loss": xent, "xent": xent,
                      "moe_aux": jnp.zeros((), jnp.float32), **counts}
    logits, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    if cfg.arch_type == "vlm":
        logits = logits[:, cfg.n_patches:]          # loss over text positions only
    xent = softmax_xent(logits, labels)
    loss = jnp.mean(xent) + cfg.moe_aux_weight * aux
    return loss, {"loss": loss, "xent": jnp.mean(xent), "moe_aux": aux}


# ---------------------------------------------------------------------------
# prefill (serving: process prompt, fill cache, emit last-token logits)
# ---------------------------------------------------------------------------

def _ring_fill(k_full: jax.Array, v_full: jax.Array, clen: int):
    """Scatter full-sequence KV (L,B,S,H,hd) into a ring buffer of length
    clen laid out (L,B,clen,H,hd). Slot i holds the *latest* position p < S
    with p % clen == i. Returns (k_cache, v_cache, slot_positions (clen,)),
    -1 for never-written slots."""
    s = k_full.shape[2]
    i = jnp.arange(clen)
    src = (s - 1) - ((s - 1 - i) % clen)
    valid = src >= 0
    srcc = jnp.clip(src, 0)
    k_cache = jnp.take(k_full, srcc, axis=2)
    v_cache = jnp.take(v_full, srcc, axis=2)
    slot_pos = jnp.where(valid, src, -1).astype(jnp.int32)
    zero = jnp.zeros((), k_cache.dtype)
    k_cache = jnp.where(valid[None, None, :, None, None], k_cache, zero)
    v_cache = jnp.where(valid[None, None, :, None, None], v_cache, zero)
    return k_cache, v_cache, slot_pos


def prefill(params: dict, batch: dict, cfg: ModelConfig,
            max_seq_len: int, cache_dtype=jnp.bfloat16) -> tuple[jax.Array, dict]:
    """Process a full prompt; return (last-token logits, decode cache sized
    for a total context of max_seq_len)."""
    _serves(cfg)
    if cfg.kv_cache_quant:
        cache_dtype = jnp.int8
    x = constrain(embed_inputs(params, batch, cfg))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    clen = cache_len_for(cfg, max_seq_len)

    if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        def body(x, lp):
            x = constrain(x)
            h, kv = L.attention_apply(lp["attn"], L.norm_apply(cfg.norm, lp["ln1"], x),
                                      positions, cfg.attn_spec, return_kv=True)
            x = x + h
            mixer = lp.get("moe")
            if mixer is not None:
                h, _ = L.moe_apply(mixer, L.norm_apply(cfg.norm, lp["ln2"], x), cfg.moe_spec)
            else:
                h = L.mlp_apply(lp["mlp"], L.norm_apply(cfg.norm, lp["ln2"], x), cfg.activation)
            return x + h, kv
        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, (k_full, v_full) = lax.scan(body, x, params["layers"], unroll=cfg.unroll)
        if cache_dtype == jnp.int8:
            from repro.models.layers import quantize_kv
            kq, ks = quantize_kv(k_full)
            vq, vs = quantize_kv(v_full)
            kc, vc, slot_pos = _ring_fill(kq, vq, clen)
            ksc, vsc, _ = _ring_fill(ks, vs, clen)
            kv = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
        else:
            kc, vc, slot_pos = _ring_fill(k_full.astype(cache_dtype),
                                          v_full.astype(cache_dtype), clen)
            kv = {"k": kc, "v": vc}
        cache = {
            "kv": kv,
            "kv_pos": jnp.broadcast_to(slot_pos[None, None], (cfg.num_layers, b, clen)),
        }
        logits = output_logits(params, x[:, -1:], cfg)
        return logits, cache

    if cfg.arch_type == "ssm":
        def body(x, lp):
            x = constrain(x)
            h, st = L.ssm_apply(lp["ssm"], L.norm_apply(cfg.norm, lp["ln"], x),
                                cfg.ssm_spec, return_state=True)
            return x + h, st
        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, states = lax.scan(body, x, params["layers"], unroll=cfg.unroll)
        ssm_cache = {"conv": states["conv"],
                     "ssm": states["ssm"].astype(jnp.float32)}
        logits = output_logits(params, x[:, -1:], cfg)
        return logits, {"ssm": ssm_cache}

    if cfg.arch_type == "hybrid":
        shared = params["shared_attn"]
        is_attn = jnp.array([i % cfg.attn_every == 0 for i in range(cfg.num_layers)])
        spec = cfg.attn_spec
        hd = cfg.resolved_head_dim

        def body(x, inp):
            x = constrain(x)
            lp, attn_here = inp

            def with_attn(x):
                h, (kt, vt) = L.attention_apply(
                    shared["attn"], L.norm_apply(cfg.norm, shared["ln1"], x),
                    positions, spec, return_kv=True)
                x = x + h
                x = x + L.mlp_apply(shared["mlp"],
                                    L.norm_apply(cfg.norm, shared["ln2"], x),
                                    cfg.activation)
                return x, (kt, vt)

            def without(x):
                z = jnp.zeros((b, s, cfg.n_kv_heads, hd), x.dtype)
                return x, (z, z)

            x, kv = lax.cond(attn_here, with_attn, without, x)
            h, st = L.ssm_apply(lp["ssm"], L.norm_apply(cfg.norm, lp["ln"], x),
                                cfg.ssm_spec, return_state=True)
            return x + h, (kv, st)
        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, ((k_full, v_full), states) = lax.scan(body, x, (params["layers"], is_attn), unroll=cfg.unroll)
        sites = [i for i in range(cfg.num_layers) if i % cfg.attn_every == 0]
        k_sites = k_full[jnp.array(sites)].astype(cache_dtype)
        v_sites = v_full[jnp.array(sites)].astype(cache_dtype)
        kc, vc, slot_pos = _ring_fill(k_sites, v_sites, clen)
        cache = {
            "ssm": {"conv": states["conv"], "ssm": states["ssm"].astype(jnp.float32)},
            "kv": {"k": kc, "v": vc},
            "kv_pos": jnp.broadcast_to(slot_pos[None, None], (len(sites), b, clen)),
        }
        logits = output_logits(params, x[:, -1:], cfg)
        return logits, cache

    raise ValueError(cfg.arch_type)


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

def _serves(cfg: ModelConfig) -> None:
    if cfg.arch_type == "nemotron_h":
        raise NotImplementedError(
            "nemotron_h trains only: no prefill, cache or decode step")


def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    """KV ring-buffer length for a max context of seq_len.

    The ring buffer *is* the sliding window: when cache_len < seq_len old
    entries are overwritten, which enforces the window without extra masking.
    """
    if cfg.arch_type in ("ssm",):
        return 0
    if cfg.sliding_window is not None:                  # native SWA (Mixtral)
        return min(seq_len, cfg.sliding_window)
    if cfg.long_context_mode == "swa" and seq_len > cfg.swa_activation_len:
        return min(seq_len, cfg.serve_window)           # serving-only window
    return seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16) -> dict:
    """Decode cache for a maximum context of `seq_len` tokens."""
    _serves(cfg)
    if cfg.kv_cache_quant:
        dtype = jnp.int8
    clen = cache_len_for(cfg, seq_len)
    spec = cfg.attn_spec
    cache: dict = {}
    if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        kv = jax.vmap(lambda _: L.init_kv_cache(batch, spec, clen, dtype))(
            jnp.arange(cfg.num_layers))
        cache["kv"] = kv
        cache["kv_pos"] = -jnp.ones((cfg.num_layers, batch, clen), jnp.int32)
    elif cfg.arch_type == "ssm":
        cache["ssm"] = jax.vmap(lambda _: L.init_ssm_cache(batch, cfg.ssm_spec))(
            jnp.arange(cfg.num_layers))
    elif cfg.arch_type == "hybrid":
        cache["ssm"] = jax.vmap(lambda _: L.init_ssm_cache(batch, cfg.ssm_spec))(
            jnp.arange(cfg.num_layers))
        n_sites = cfg.n_attn_sites
        cache["kv"] = jax.vmap(lambda _: L.init_kv_cache(batch, spec, clen, dtype))(
            jnp.arange(n_sites))
        cache["kv_pos"] = -jnp.ones((n_sites, batch, clen), jnp.int32)
    return cache


def _effective_decode_spec(cfg: ModelConfig) -> L.AttnSpec:
    # Ring-buffer overwrite already enforces the window during decode
    # (cache_len == window), so the decode mask needs no window term.
    return dataclasses.replace(cfg.attn_spec, sliding_window=None)


def decode_step(params: dict, cache: dict, batch: dict, pos: jax.Array,
                cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """One-token decode. batch['tokens']: (B,1) (or (B,1,CB) audio);
    pos: (B,) absolute positions. Returns (logits, new_cache)."""
    _serves(cfg)
    x = constrain(embed_inputs(params, batch, cfg))    # (B, 1, d)
    positions = pos[:, None].astype(jnp.int32)
    spec = _effective_decode_spec(cfg)

    if cfg.arch_type in ("dense", "moe", "vlm", "audio"):
        def body(x, inp):
            x = constrain(x)
            lp, kv, cpos = inp
            x, _, kvout = _dense_block_decode(lp, x, positions, cfg, spec, kv, cpos)
            return x, kvout
        x, kvs = lax.scan(body, x, (params["layers"], cache["kv"], cache["kv_pos"]), unroll=cfg.unroll)
        new_cache = {"kv": kvs[0], "kv_pos": kvs[1]}
        return output_logits(params, x, cfg), new_cache

    if cfg.arch_type == "ssm":
        def body(x, inp):
            x = constrain(x)
            lp, sc = inp
            h, new_sc = L.ssm_apply(lp["ssm"], L.norm_apply(cfg.norm, lp["ln"], x),
                                    cfg.ssm_spec, sc)
            return x + h, new_sc
        x, new_ssm = lax.scan(body, x, (params["layers"], cache["ssm"]), unroll=cfg.unroll)
        return output_logits(params, x, cfg), {"ssm": new_ssm}

    if cfg.arch_type == "hybrid":
        shared = params["shared_attn"]
        is_attn = jnp.array([i % cfg.attn_every == 0 for i in range(cfg.num_layers)])
        site_idx = jnp.cumsum(is_attn.astype(jnp.int32)) - is_attn.astype(jnp.int32)

        # scan over layers; ssm caches are xs/ys, shared kv cache is carry
        def body2(carry, inp):
            x, kv, kv_pos = carry
            x = constrain(x)
            lp, sc, attn_here, site = inp

            def with_attn(operand):
                x, kv, kv_pos = operand
                kv_l = jax.tree.map(lambda t: t[site], kv)
                cpos_l = kv_pos[site]
                h, upd = L.attention_apply(
                    shared["attn"], L.norm_apply(cfg.norm, shared["ln1"], x),
                    positions, spec, kv_l, cpos_l)
                new_kv_l, new_cpos = upd
                x = x + h
                x = x + L.mlp_apply(shared["mlp"],
                                    L.norm_apply(cfg.norm, shared["ln2"], x),
                                    cfg.activation)
                kv = jax.tree.map(
                    lambda full, new: lax.dynamic_update_index_in_dim(
                        full, new.astype(full.dtype), site, 0), kv, new_kv_l)
                kv_pos = lax.dynamic_update_index_in_dim(kv_pos, new_cpos, site, 0)
                return x, kv, kv_pos

            x, kv, kv_pos = lax.cond(attn_here, with_attn, lambda o: o, (x, kv, kv_pos))
            h, new_sc = L.ssm_apply(lp["ssm"], L.norm_apply(cfg.norm, lp["ln"], x),
                                    cfg.ssm_spec, sc)
            return (x + h, kv, kv_pos), new_sc

        (x, kv, kv_pos), new_ssm = lax.scan(body2, (x, cache["kv"], cache["kv_pos"]),
            (params["layers"], cache["ssm"], is_attn, site_idx),
            unroll=cfg.unroll)
        new_cache = {"ssm": new_ssm, "kv": kv, "kv_pos": kv_pos}
        return output_logits(params, x, cfg), new_cache

    raise ValueError(cfg.arch_type)


def _dense_block_decode(lp, x, positions, cfg: ModelConfig, spec, kv, cpos):
    h, upd = L.attention_apply(lp["attn"], L.norm_apply(cfg.norm, lp["ln1"], x),
                               positions, spec, kv, cpos)
    new_kv, new_cpos = upd
    x = x + h
    mixer = lp.get("moe")
    aux = jnp.zeros((), jnp.float32)
    if mixer is not None:
        h, aux = L.moe_apply(mixer, L.norm_apply(cfg.norm, lp["ln2"], x), cfg.moe_spec)
    else:
        h = L.mlp_apply(lp["mlp"], L.norm_apply(cfg.norm, lp["ln2"], x), cfg.activation)
    return x + h, aux, (new_kv, new_cpos)
