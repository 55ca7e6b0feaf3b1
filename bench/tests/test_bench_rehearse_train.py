"""The training cell rehearsed end to end at toy size on the CPU."""
import pytest

from benchutil import CONTRACT_KEYS, rehearse


@pytest.mark.parametrize("workload", ["train_stablelm_1c"])
def test_train_cell_rehearsal_is_correct(workload):
    proc, result = rehearse(workload)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result is not None and CONTRACT_KEYS <= set(result)
    assert result["correct"] is True, result["checks"]
    assert result["device"]["platform"] == "cpu"
    assert {"train_tokens_per_s", "setup_s"} <= set(result["metrics"])
    assert list(result)[-1] == "checks"
    for name in ("grad_norm_gap", "update_norm_gap"):
        c = result["checks"][name]
        assert 0 <= c["value"] <= c["limit"]
