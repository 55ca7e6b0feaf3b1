"""The yardstick's operation and byte counts against hand counts."""
import json

import pytest

from benchutil import BENCH

import costs  # noqa: E402  (bench/, put on the path by benchutil)


@pytest.fixture(scope="module")
def stablelm():
    return json.loads((BENCH / "configs" / "stablelm-1.6b.json").read_text())


# per layer: q,k,v,o 4 x 2048 x 2048, MLP 3 x 2048 x 5632, two norms;
# embedding and LM head 100,352 x 2048 each; one final norm
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 2 * 2048


@pytest.mark.parametrize("layers,hand", [(4, 616_581_120),
                                         (8, 822_118_400)])
def test_param_count_is_the_hand_count(stablelm, layers, hand):
    assert layers * LAYER + 2 * 100_352 * 2048 + 2048 == hand
    assert costs.dense_params({**stablelm,
                               "num_hidden_layers": layers}) == hand


def test_param_count_matches_the_program(stablelm):
    import run
    driver = run.load_module(BENCH / "drivers" / "dp_train.py")
    assert stablelm["num_hidden_layers"] == 8
    assert driver.program_config(stablelm, False).n_params() == 822_118_400


@pytest.mark.parametrize("layers,seq,matmul,flops", [
    # 6 x matmul weights + 6 x layers x seq x 2048 (causal attention)
    (4, 4096, 411_041_792, 2_667_577_344),
    (4, 2048, 411_041_792, 2_566_914_048),
    (8, 2048, 616_562_688, 3_900_702_720),
])
def test_model_flops_per_token(stablelm, layers, seq, matmul, flops):
    cfg = {**stablelm, "num_hidden_layers": layers}
    assert costs.dense_matmul_params(cfg) == matmul
    assert 6 * matmul + 6 * layers * seq * 2048 == flops
    assert costs.model_flops_per_token(cfg, seq) == flops


def test_four_layers_at_the_published_context_need_2_67_gflop_a_token(
        stablelm):
    cfg = {**stablelm, "num_hidden_layers": 4}
    assert round(costs.model_flops_per_token(cfg, 4096) / 1e9, 2) == 2.67


@pytest.mark.parametrize("fn,n,want", [
    (costs.fused_addto_bytes, 40_000, 480_000),
    (costs.read_bytes, 32_768, 9 * 32_768),
    (costs.scatter_addto_bytes, 65_536, 16 * 65_536),
    (costs.agtr_call_bytes, 262_144, 21 * 262_144),
])
def test_bytes_per_element(fn, n, want):
    assert fn(n) == want
