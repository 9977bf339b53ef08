"""The harness runs only on a TPU of a kind its peaks table knows, and only
from a checkout that holds the program."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

import core

ROOT = Path(__file__).resolve().parents[2]
PEAKS = json.loads((core.BENCH / "peaks.json").read_text())


def dev(platform, kind):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_known_tpu_gets_its_peaks():
    peaks = core.check_device([dev("tpu", "TPU v5 lite")], 1, PEAKS)
    assert peaks["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("devices,chips", [
    ([dev("cpu", "cpu")], 1),
    ([dev("tpu", "TPU v9 imaginary")], 1),
    ([dev("tpu", "TPU v5 lite")], 4),
], ids=["cpu", "unknown-kind", "too-few-chips"])
def test_refused(devices, chips):
    with pytest.raises(core.Refused):
        core.check_device(devices, chips, PEAKS)


def run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "wordcount.drift.sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_on_cpu_prints_no_result():
    p = run_cli(ROOT)
    assert p.returncode != 0
    assert "refused" in p.stderr
    assert '"correct"' not in p.stdout


def test_cli_without_the_program_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(core.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
