"""The MoE cell's FLOP and parameter counts against hand counts."""
import json

import pytest

from benchutil import BENCH

import costs_mla_moe as costs  # noqa: E402  (bench/, put on the path)


@pytest.fixture(scope="module")
def moonlight():
    return json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                      .read_text())


# MLA: W_q 2048 x 16 x 192, W_kv_a 2048 x 576, W_kv_b 512 x 16 x 256,
# W_o 16 x 128 x 2048; an expert 3 x 2048 x 1408
MLA = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
EXPERT = 3 * 2048 * 1408


def test_matmul_weights_are_the_hand_count(moonlight):
    assert costs.mla_matmul_params(moonlight) == MLA == 13_762_560
    assert costs.expert_matmul_params(moonlight) == EXPERT
    # 6 MLA layers, the dense SwiGLU, 5 x (2 shared experts + the router
    # at 64 outputs), the head over the 20,480-id slice
    dense = 6 * MLA + 3 * 2048 * 11264 + 5 * (2 * EXPERT + 2048 * 64) \
        + 20480 * 2048
    assert costs.dense_matmul_params(moonlight) == dense == 280_887_296


def test_param_count_matches_the_program(moonlight):
    """What the chip holds: 83.9 M in the embedding and head slices, 83.0 M
    in the dense layer, 100.4 M in each MoE layer."""
    import run
    driver = run.load_module(BENCH / "drivers" / "dp_train_moe.py")
    cfg = driver.program_config(moonlight, False)
    norms = 2 * 2048 + 512
    dense_layer = MLA + norms + 3 * 2048 * 11264
    moe_layer = MLA + norms + 8 * EXPERT + 2 * EXPERT + 2048 * 64 + 64
    hand = 2 * 20480 * 2048 + 2048 + dense_layer + 5 * moe_layer
    assert (round(dense_layer / 1e6, 1), round(moe_layer / 1e6, 1)) == (
        83.0, 100.4)
    assert cfg.n_params() == hand == 668_890_432


@pytest.mark.parametrize("seq,gflop", [(8192, 2.635), (4096, 2.257)])
def test_flops_a_token_at_the_mean_routing(moonlight, seq, gflop):
    """6 x the weights a token meets, 0.75 routed rows a MoE layer landing
    here (6 of 64 router outputs, 8 held), and causal MLA attention at
    q/k 192 and v 128: 3 x 6 layers x 16 heads x 320 x seq."""
    rows = 5 * 6 * 8 / 64
    want = 6 * (280_887_296 + rows * EXPERT) + 3 * 6 * 16 * 320 * seq
    assert costs.mean_flops_per_token(moonlight, seq) == pytest.approx(want)
    assert round(want / 1e9, 3) == gflop


def test_step_flops_count_the_rows_that_landed_here(moonlight):
    per_token = costs.step_flops(moonlight, 8192, 1, 0)
    assert costs.step_flops(moonlight, 8192, 16384, 12288) == \
        pytest.approx(16384 * per_token + 6 * EXPERT * 12288)
