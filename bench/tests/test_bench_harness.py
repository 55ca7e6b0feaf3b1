"""The harness's refusals and its data-driven lookups, without a chip."""
import json
import re
import shutil
import subprocess
import sys
from argparse import Namespace
from types import SimpleNamespace

import pytest

from benchutil import BENCH, ROOT, child_env

import run  # noqa: E402  (bench/run.py)

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _ctx(workload: str) -> run.Ctx:
    args = Namespace(workload=workload, seed=1, seconds=1.0, trace=0,
                     rehearse=False)
    cell = next(w for w in SPEC["workloads"] if w["name"] == workload)
    return run.Ctx(args, SPEC, cell)


def _fake_devices(monkeypatch, kind: str, n: int):
    import jax
    devs = [SimpleNamespace(platform="tpu", device_kind=kind, id=i)
            for i in range(n)]
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_unknown_device_kind_is_refused(monkeypatch):
    _fake_devices(monkeypatch, "TPU v99 imaginary", 1)
    with pytest.raises(run.BenchError, match="not in bench/peaks.json"):
        run.check_machine(_ctx("agtr_1k"))


def test_known_device_kind_takes_its_published_peaks(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", 1)
    ctx = _ctx("agtr_1k")
    run.check_machine(ctx)
    assert ctx.peaks["bf16_flops_per_s"] == 197e12
    assert ctx.peaks["hbm_bytes_per_s"] == 819e9


def test_fewer_chips_than_the_cell_asks_for_is_refused(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", 1)
    ctx = _ctx("agtr_1k")
    ctx.chips = 4
    with pytest.raises(run.BenchError, match="needs 4 chips"):
        run.check_machine(ctx)


def _no_result(cwd) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "agtr_1k", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=child_env(),
        capture_output=True, text=True, timeout=120)


def test_no_tpu_gives_no_result():
    proc = _no_result(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _no_result(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_seeds_of_any_size_give_fixed_31_bit_words():
    big = 2 ** 31 + 12_345
    a, b = run.seed_words(big), run.seed_words(big)
    assert a == b and a != run.seed_words(big + 1)
    assert all(0 <= w < 2 ** 31 for w in a)


def test_every_name_finds_its_files():
    for conf in SPEC["configs"]:
        assert (ROOT / conf["file"]).is_file()
        assert NAME.match(conf["name"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                             .read_text())
        assert (BENCH / "drivers" / f"{traffic['kind']}.py").is_file()
        reported = [m["name"] for m in SPEC["end_to_end"]
                    if run.applies(m, w["name"])]
        assert "setup_s" in reported and len(reported) >= 2
        layers = [m for m in SPEC["per_layer"] if run.applies(m, w["name"])]
        assert layers and all(m["moves"] in reported for m in layers)
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    reader = run.load_module(BENCH / "metrics" / f"{metric}.py")
    empty = {"window": {"calls": 0, "elems": 1024, "seconds": 1.0,
                        "steps": 0, "tokens": 0, "seq": 2048, "arch": {}},
             "obs": None, "trace": None, "peaks": None, "chips": 1}
    assert reader.read(empty) is None
