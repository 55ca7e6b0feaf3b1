"""The serve cells rehearsed end to end at toy size on the CPU (kernels
interpreted): the result line keeps the contract and names the CPU."""
import pytest

from benchutil import CONTRACT_KEYS, rehearse


def _check_line(result, trace):
    assert result is not None
    assert CONTRACT_KEYS <= set(result)
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    # a CPU rehearsal is never reported as a chip result
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["kind"] == "cpu"
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("workload,trace", [
    ("agtr_1k", 0), ("agtr_1k", 1), ("agtr_32k", 0), ("agtr_32k", 1)])
def test_serve_cell_rehearsal_is_correct(workload, trace):
    proc, result = rehearse(workload, trace=trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    _check_line(result, trace)
    names = set(result["metrics"])
    if trace:
        assert "sched.calls_per_pass" in names
        assert "pipeline.host_us_per_call" in names
    else:
        assert {"agtr_elems_per_s", "setup_s"} <= names
        assert ("agtr_call_p99_ms" in names) == (workload == "agtr_1k")
    assert proc.stderr.rstrip().splitlines()[-1].startswith("check ")
