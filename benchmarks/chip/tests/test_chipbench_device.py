"""The run refuses a machine without the chips a cell asks for, and a
checkout without the program."""
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from chipbench import device  # noqa: E402


def fake(platform, kind, n):
    return [SimpleNamespace(platform=platform, device_kind=kind)] * n


def test_require_devices():
    assert device.require_devices(1, fake("tpu", "TPU v5 lite", 1)) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(device.NoChip, match="needs a TPU"):
        device.require_devices(1, fake("cpu", "cpu", 1))
    with pytest.raises(device.NoChip, match="needs 4 chips"):
        device.require_devices(4, fake("tpu", "TPU v5 lite", 1))


def run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "internlm2-1.8b.batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_refuses_a_cpu():
    p = run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "platform=cpu" in p.stderr and "needs a TPU" in p.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
