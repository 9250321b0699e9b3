"""The Nemotron-H pair cell at a tiny size on the CPU: its configuration
file, the FLOP count by hand, a sound run that is correct, and each planted
fault of the expert layer caught. The cut is made here, on the real
configuration file; the limits, the mix and the readers are the real ones."""
import dataclasses
import json
from pathlib import Path

import pytest

from chipbench import run
from chipbench.reference import nemotron_h
from chipbench.tests import tiny

CONFIG = "pair-nemotron3-nano-a3b-qwen2-0.5b"
CELL, SEED, SECONDS = "pair-nemotron3-train", 3000000001, 3.0
FILE = Path(__file__).resolve().parents[1] / "configs" / f"{CONFIG}.json"

TRAIN = dict(hidden_size=48, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
             ssm_state_size=8, chunk_size=8, num_attention_heads=4,
             num_key_value_heads=2, head_dim=12, moe_intermediate_size=24,
             moe_shared_expert_intermediate_size=40, router_experts=32,
             n_routed_experts=8, vocab_size=256, seq_len=32)


def _cut(bench: run.Benchmark) -> run.Benchmark:
    path = bench.root / "configs" / f"{CONFIG}.json"
    c = json.loads(path.read_text())
    c["train"].update(TRAIN)
    c["serve"].update(tiny.TINY["pair-mamba2-780m-qwen2-0.5b"]["serve"])
    path.write_text(json.dumps(c))
    return bench


@pytest.fixture
def bench(tmp_path):
    return _cut(tiny.bench(tmp_path))


def test_file_states_the_published_widths_and_the_cut():
    c = json.loads(FILE.read_text())
    t = c["train"]
    repeated = set(c) & set(t)         # the catalog's keys, as run
    assert len(repeated) == 46
    assert all(c[key] == t[key] for key in repeated)
    assert (t["hidden_size"], t["mamba_num_heads"], t["mamba_head_dim"],
            t["n_groups"], t["ssm_state_size"], t["chunk_size"]) == \
        (2688, 64, 64, 8, 128, 128)
    assert (t["moe_intermediate_size"], t["moe_shared_expert_intermediate_size"],
            t["router_experts"], t["num_experts_per_tok"],
            t["routed_scaling_factor"]) == (1856, 3712, 128, 6, 2.5)
    assert (t["num_attention_heads"], t["num_key_value_heads"],
            t["head_dim"], t["layer_norm_epsilon"]) == (32, 2, 128, 1e-5)
    assert t["hybrid_override_pattern"] == "MEMEM*E"
    assert (t["num_hidden_layers"], t["n_routed_experts"],
            t["vocab_size"]) == (7, 8, 16384)
    assert {"num_hidden_layers", "n_routed_experts", "vocab_size",
            "hybrid_override_pattern"} <= set(c["reduced"])
    assert {"router_scoring", "position_embedding", "seq_len",
            "frozen_router"} <= set(c["assumed"])
    spec = run.Benchmark.load().spec
    entry = next(x for x in spec["configs"] if x["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
         "vocab_size"])


def test_train_flops_by_hand_at_a_tiny_size():
    c = dict(hidden_size=4, mamba_num_heads=2, mamba_head_dim=3, n_groups=1,
             ssm_state_size=2, conv_kernel=2, num_attention_heads=2,
             num_key_value_heads=1, head_dim=2, moe_intermediate_size=5,
             moe_shared_expert_intermediate_size=7, router_experts=8,
             n_routed_experts=4, num_experts_per_tok=2, vocab_size=10,
             hybrid_override_pattern="ME*E")
    seq = 3
    # M: din 6, gn 2, in_proj 4 -> 6+6+2+2 = 16... (2*6 + 2*2 + 2 = 18),
    # conv over 6 + 4 = 10 channels; SSD 2*3*(2 + 6)
    m = 2 * 4 * 18 + 2 * 6 * 4 + 2 * 2 * 10 + 2 * 3 * (2 + 6)
    # E: router 2*4*8, shared 2*2*4*7, routed 2 x 4/8 = 1 expert's 2*2*4*5
    e = 2 * 4 * 8 + 2 * 2 * 4 * 7 + 1 * 2 * 2 * 4 * 5
    # *: q 4, k 2, v 2, o 4 -> 2*4*(4+2+2+4); scores and values 2*2*3*4
    a = 2 * 4 * 12 + 2 * 2 * seq * 4
    per_token = m + 2 * e + a + 2 * 4 * 10
    assert nemotron_h.train_flops(c, batch=2, seq=seq) == \
        3 * 2 * seq * per_token


def test_train_flops_near_the_estimate_from_the_shapes():
    t = json.loads(FILE.read_text())["train"]
    assert nemotron_h.train_flops(t, 16, 512) == pytest.approx(13.2e12,
                                                                rel=0.01)


def test_system_param_count_is_the_reference_leaves():
    import math
    from chipbench.systems.nemotron_h import model_config
    t = json.loads(FILE.read_text())["train"]
    held = sum(math.prod(shape) for _, shape, _ in nemotron_h.leaves(t))
    assert model_config(t).param_count() == held
    assert held == pytest.approx(528e6, rel=0.005)


def test_system_refuses_what_it_does_not_implement():
    from chipbench.systems.nemotron_h import model_config
    t = json.loads(FILE.read_text())["train"]
    for key, val in (("mlp_hidden_act", "silu"), ("n_shared_experts", 2),
                     ("conv_kernel", 3), ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError):
            model_config({**t, key: val})


def _go(bench):
    return run.run_cell(bench, CELL, SEED, SECONDS, False,
                        require_chip=False)


def test_sound_run_is_correct(bench):
    out = _go(bench)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def _plant(monkeypatch, fault):
    from repro.models import layers
    if fault == "softmax_router":
        def route(p, x, spec):
            import jax
            import jax.numpy as jnp
            probs = jax.nn.softmax(jnp.einsum(
                "td,de->te", x.astype(jnp.float32), p["router"]), -1)
            _, idx = jax.lax.top_k(probs + p["router_bias"], spec.top_k)
            w = jnp.take_along_axis(probs, idx, -1)
            return idx, w / w.sum(-1, keepdims=True) * spec.scaling
        monkeypatch.setattr(layers, "held_moe_route", route)
        return
    apply = layers.held_moe_apply
    if fault == "no_shared_expert":
        def broken(p, x, spec):
            import jax
            import jax.numpy as jnp
            return apply({**p, "shared": jax.tree.map(jnp.zeros_like,
                                                      p["shared"])}, x, spec)
    else:                                      # the 2.5 scale dropped
        def broken(p, x, spec):
            return apply(p, x, dataclasses.replace(spec, scaling=1.0))
    monkeypatch.setattr(layers, "held_moe_apply", broken)


@pytest.mark.parametrize("fault", ["softmax_router", "no_shared_expert",
                                   "no_routed_scale"])
def test_planted_fault_is_caught(bench, monkeypatch, fault):
    _plant(monkeypatch, fault)
    out = _go(bench)
    assert out["correct"] is False, out["checks"]
