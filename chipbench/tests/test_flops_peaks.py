"""FLOP counts against hand counts, and the table of peaks."""
import json
from pathlib import Path

import pytest

from chipbench import peaks
from chipbench.reference import dense_lm, mamba2

PAIR = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "pair-mamba2-780m-qwen2-0.5b.json").read_text())


def test_mamba2_train_flops_by_hand_at_a_tiny_size():
    c = dict(d_model=4, n_layer=1, d_state=2, d_conv=2, expand=2, headdim=4,
             ngroups=1, vocab_size=10, pad_vocab_size_multiple=8)
    # din 8, gn 2, heads 2, in_proj 4 -> 8+8+2+2 = 20... (2*8 + 2*2 + 2)
    # = 22 outputs, conv over 8 + 4 = 12 channels, padded vocab 16
    per_token = (2 * 4 * 22 + 2 * 8 * 4 + 2 * 2 * 12 + 2 * 3 * (2 + 8)
                 + 2 * 4 * 16)
    assert mamba2.train_flops(c, batch=2, seq=3) == 3 * 2 * 3 * per_token


def test_mamba2_780m_step_near_six_params_tokens():
    t = PAIR["train"]
    z = mamba2.dims(t)
    d = t["d_model"]
    params = t["n_layer"] * (d * z["proj"] + z["din"] * d
                             + t["d_conv"] * z["conv"]) + z["vocab"] * d
    six_pt = 6 * params * 16 * t["seq_len"]
    assert six_pt == pytest.approx(9.6e12, rel=0.02)
    # the count adds the conv and the SSD's quadratic terms, and the tied
    # head's product once per token: within 5% of 6 x params x tokens
    assert mamba2.train_flops(t, 16, t["seq_len"]) == \
        pytest.approx(six_pt, rel=0.05)


def test_dense_forward_flops_by_hand_at_a_tiny_size():
    c = dict(hidden_size=4, num_attention_heads=2, num_key_value_heads=1,
             intermediate_size=6, num_hidden_layers=1, vocab_size=10,
             pad_vocab_size_multiple=8, vision_width=3, vision_tokens=1)
    # head dim 2: q 4, k 2, v 2, o 4 -> 2*4*(4+2+2+4); MLP 3 x 2*4*6;
    # scores and values 2 * 2 * seq * 4; head 2*4*16; vision 2*3*4
    seq = 5
    per_token = 2 * 4 * 12 + 3 * 2 * 4 * 6 + 2 * 2 * seq * 4 + 2 * 4 * 16
    assert dense_lm.forward_flops(c, batch=2, seq=seq) == \
        2 * (seq * per_token + 2 * 3 * 4)


def test_peaks_table_names_its_source_and_refuses_unknown_chips():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
