"""`bench/run.py` refuses to run, and prints no result, where it cannot
measure: without a TPU, on a device missing from `bench/peaks.json`, with
fewer chips than the cell asks for."""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

import bench.run as run
from bench.run import ROOT

CMD = [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.chat",
       "--seed", str(2**33 + 3), "--seconds", "2", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _fake(monkeypatch, kind, n=1):
    import jax
    dev = SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


def test_device_missing_from_peaks(monkeypatch):
    _fake(monkeypatch, "TPU v99")
    with pytest.raises(run.NoChip, match="peaks"):
        run.device_info(1)


def test_too_few_chips(monkeypatch):
    _fake(monkeypatch, "TPU v5 lite", n=1)
    with pytest.raises(run.NoChip, match="4 chips"):
        run.device_info(4)


def test_known_device(monkeypatch):
    _fake(monkeypatch, "TPU v5 lite")
    stamp, peak = run.device_info(1)
    assert stamp == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert peak["flops_per_s"] == 197e12 and peak["bytes_per_s"] == 819e9


def test_every_cell_resolves_by_name():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        s = run.cell_spec(w["name"])
        assert s["config"]["deployment"]["sync"] == "device"
        names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
        for n in names:
            assert callable(run.load_reader(n)), n
        assert "setup_s" in names
