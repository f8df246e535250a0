"""``work.py`` against counts made by hand from the published configs."""

import json
from pathlib import Path

import pytest

import work

INTERNLM2 = json.loads((Path(work.__file__).parent / "configs"
                        / "internlm2-1.8b.json").read_text())
# DeepSeek-V2-Lite's published config, depth cut to 1 dense + 6 MoE layers
DSV2_CUT = {
    "hidden_size": 2048, "intermediate_size": 10944, "vocab_size": 102400,
    "num_attention_heads": 16, "num_hidden_layers": 7, "kv_lora_rank": 512,
    "qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "v_head_dim": 128,
    "n_routed_experts": 64, "n_shared_experts": 2, "num_experts_per_tok": 6,
    "moe_intermediate_size": 1408, "first_k_dense_replace": 1,
    "tie_word_embeddings": False,
}


def test_internlm2_params():
    # per layer: q 2048*2048 + k, v 2048*1024 each + o 2048*2048
    # = 12,582,912; SwiGLU 3*2048*8192 = 50,331,648; two norms 4,096
    layer = 12_582_912 + 50_331_648 + 4_096
    # embedding and head 2 * 92544 * 2048, final norm 2048
    assert work.total_params(INTERNLM2) == 24 * layer + 2 * 189_530_112 + 2048
    assert work.total_params(INTERNLM2) == 1_889_110_016


def test_deepseek_cut_params():
    # MLA: q 2048*16*192 + kv_a 2048*576 + kv_a norm 512 + kv_b 512*16*256
    # + o 16*128*2048 = 13,764,096, and two norms 4,096
    attn = 6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304 + 4_096
    expert = 3 * 2048 * 1408  # 8,650,752
    moe_layer = attn + 64 * expert + 2 * expert + 2048 * 64
    dense_layer = attn + 3 * 2048 * 10944
    total = dense_layer + 6 * moe_layer + 2 * 102400 * 2048 + 2048
    assert work.total_params(DSV2_CUT) == total
    assert total == pytest.approx(4.0e9, rel=0.01)
    assert work.params(DSV2_CUT)["routed"] == 6 * 64 * expert


def test_decode_bytes_at_one_row():
    # every weight but the embedding table once, one embedding row, the
    # cache of 100 positions read and one row written (24*2*8*128*2 B)
    assert work.kv_bytes_per_token(INTERNLM2) == 98_304
    weights = 1_889_110_016 - 189_530_112
    assert work.decode_call_bytes(INTERNLM2, [100]) == (
        2 * (weights + 2048) + 98_304 * 101)
    # MoE: at one row only its 6 routed experts of each layer are read
    p = work.params(DSV2_CUT)
    one = work.decode_call_bytes(DSV2_CUT, [0])
    assert one == 2 * (p["layers"] + p["head"] + 6 * 6 * 3 * 2048 * 1408
                       + 2048) + work.kv_bytes_per_token(DSV2_CUT)
    # with 16 rows at most all 64 experts are read, never more
    many = work.decode_call_bytes(DSV2_CUT, [0] * 16)
    assert many - 16 * (2 * 2048 + work.kv_bytes_per_token(DSV2_CUT)) == (
        2 * (p["layers"] + p["head"] + p["routed"]))


def test_flops_count_useful_work_only():
    d, v = 2048, 92544
    active = work.total_params(INTERNLM2) - 2 * d * v  # layers only
    # one token at context 1: every layer weight once, the head once, and
    # QK plus PV over one position in each of 24 layers
    assert work.decode_token_flops(INTERNLM2, 1) == (
        2 * (active + d * v) + 2 * 16 * 1 * 256 * 24)
    # prefill: the head at the last position only
    one = work.prefill_flops(INTERNLM2, 1)
    assert one == work.decode_token_flops(INTERNLM2, 1)
    assert work.prefill_flops(INTERNLM2, 10) < 10 * work.decode_token_flops(
        INTERNLM2, 10)
