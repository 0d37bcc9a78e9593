"""A run that finds no TPU exits non-zero and prints no result."""

import os
import shutil
import subprocess
import sys

import pytest

import run
from conftest import BENCH, ROOT

ARGS = ["--workload", "resnet18.search", "--seed", "4294967311",
        "--seconds", "1", "--trace", "0"]


def test_no_tpu_fails_without_a_result(capsys):
    assert run.main(ARGS) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_too_few_chips(monkeypatch):
    class Chip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    import jax
    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    assert run.find_devices(1)
    with pytest.raises(run.NoChip, match="needs 4 chips"):
        run.find_devices(4)


def test_unknown_device_kind_is_an_error():
    assert run.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.NoChip, match="no peaks"):
        run.device_peaks("TPU v9 imaginary")


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "bench/run.py", *ARGS],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
