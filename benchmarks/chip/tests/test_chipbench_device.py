"""The peaks table and the harness's refusal to measure without a chip.

Runs on the CPU: a run of any cell must exit non-zero and print no
result line, here and in a directory that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402


def test_peaks_of_the_v5e_and_their_source():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16 * 2 ** 30
    table = json.loads((ROOT / "benchmarks/chip/peaks.json").read_text())
    assert "TPU v5e" in table["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(harness.Refused, match="no peaks"):
        harness.peaks(kind)


def _run(cwd: Path, workload: str):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "2147483911", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    proc = _run(ROOT, cell["name"])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_cell_on_a_cpu_refuses_before_building(monkeypatch):
    import jax
    cell = harness.load_cell(
        json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
        ["name"])
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(harness.Refused, match="no TPU"):
        harness.run(cell, 1, 1.0, False)


def test_benchmark_alone_cannot_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    proc = _run(tmp_path, cell["name"])
    assert proc.returncode != 0
    assert not proc.stdout.strip()
