"""The yardstick's counts against hand counts, and the peaks table."""
import json
import os

import pytest

from chipbench import flops
from chipbench.peaks import peaks_for

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_qwen3_parameters_match_the_published_count():
    # 28 x (attention 6,291,456 + QK-norm 256 + MLP 9,437,184 + norms 2,048)
    # + tied table 151,936 x 1,024 + final norm 1,024
    c = _config("qwen3-0.6b")
    assert flops.param_count(c) == 596_049_920
    assert flops.param_count(c, active=True) == 596_049_920
    assert flops.weight_bytes(c) == 2 * 596_049_920


def test_olmoe_stage_parameters():
    # 4 x (attention 16,777,216 + QK-norm 256 + 64 experts x 6,291,456
    # + router 131,072 + norms 4,096) + two tables 50,304 x 2,048 + 2,048
    c = _config("olmoe-1b-7b")
    per_layer = 16_777_216 + 256 + 64 * 6_291_456 + 131_072 + 4_096
    assert flops.param_count(c) == 4 * per_layer + 2 * 50_304 * 2_048 + 2_048
    assert flops.param_count(c) == pytest.approx(1.885e9, rel=1e-3)
    active = 4 * (16_777_216 + 256 + 8 * 6_291_456 + 131_072 + 4_096) \
        + 2 * 50_304 * 2_048 + 2_048
    assert flops.param_count(c, active=True) == active
    # the float32 router adds 2 bytes per router weight
    assert flops.weight_bytes(c) == 2 * flops.param_count(c) \
        + 2 * 4 * 2_048 * 64


def test_attention_block_flops_at_olmoe_widths():
    # causal QK^T and PV at half the square: 2 x 2 x (2*16*2048*2048*128)/2,
    # plus the 2048 x 2048 output projection over 2 x 2048 rows
    c = _config("olmoe-1b-7b")
    attn = 2 * 2 * (2 * 16 * 2048 * 2048 * 128) // 2
    proj = 2 * (2 * 2048) * 2048 * 2048
    assert flops.attention_block_flops(c, 2, 2048) == attn + proj
    assert flops.attention_block_flops(c, 2, 2048) == pytest.approx(68.7e9,
                                                                   rel=1e-3)


def test_request_flops_by_hand():
    c = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 1,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
         "vocab_size": 10, "tie_word_embeddings": True}
    per_token = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16)
    head = 2 * 8 * 10
    # prompt 3: keys 1+2+3; decode 1 step at position 3: keys 4
    attn = 4 * 1 * 2 * 4 * (1 + 2 + 3 + 4)
    want = 3 * per_token + head + (per_token + head) + attn
    assert flops.request_flops(c, 1, 3, 2) == want
    assert flops.request_flops(c, 5, 3, 2) == 5 * want


def test_peaks_table():
    p = peaks_for("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bw, p.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
