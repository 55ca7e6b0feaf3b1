"""The MoE training cell rehearsed end to end at toy size on the CPU."""
import pytest

from benchutil import CONTRACT_KEYS, rehearse

CHECKED = ("loss_gap", "grad_norm_gap", "update_norm_gap", "route_mismatch")


@pytest.mark.parametrize("trace", [0, 1])
def test_moe_cell_rehearsal_is_correct(trace):
    proc, result = rehearse("train_moonlight_1c", trace=trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None and CONTRACT_KEYS <= set(result)
    assert result["correct"] is True, result["checks"]
    assert result["device"]["platform"] == "cpu"
    for name in CHECKED:
        c = result["checks"][name]
        assert 0 <= c["value"] <= c["limit"]
    if trace:
        # no published peak for the CPU, so no utilization
        assert set(result["metrics"]) == {"moe.pad_share",
                                          "moe.load_max_over_mean"}
        assert 0 <= result["metrics"]["moe.pad_share"]["value"] < 100
        assert result["metrics"]["moe.load_max_over_mean"]["value"] >= 1
    else:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
