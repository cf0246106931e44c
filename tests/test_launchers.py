"""Launcher CLI smoke tests (subprocess, real entry points).

Marked ``slow``: each test boots a fresh interpreter + JAX (the dryrun cell
additionally compiles against a 512-device host mesh), so the module is
excluded from the default tier-1 run (see pytest.ini) and exercised with
``pytest -m slow``.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", *args], capture_output=True, text=True,
        env=env, cwd=ROOT, timeout=timeout,
    )


def test_train_cli_single():
    out = _run(["repro.launch.train", "--arch", "qwen2-1.5b", "--steps", "6",
                "--batch", "2", "--seq", "16"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: loss" in out.stdout


def test_train_cli_hdp():
    out = _run(["repro.launch.train", "--mode", "hdp", "--arch", "qwen2-1.5b",
                "--steps", "6", "--seq", "16", "--grains", "4",
                "--pods", "3:1"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "plan[" in out.stdout


def test_train_cli_hdp_static_baseline():
    out = _run(["repro.launch.train", "--mode", "hdp", "--arch", "qwen2-1.5b",
                "--steps", "4", "--seq", "16", "--grains", "4",
                "--pods", "3:1", "--static"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "plan[" in out.stdout


def test_bench_hdp_cli(tmp_path):
    """Toy-scale smoke of the HDP benchmark: JSON emitted, both scenarios
    present, and the homogenized runtime beats the static plan on the step
    where the fault fires."""
    import json

    out_path = str(tmp_path / "BENCH_hdp.json")
    out = _run(["benchmarks.bench_hdp", "--grains", "64", "--steps", "4",
                "--fault-step", "2", "--out", out_path], timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    with open(out_path) as f:
        data = json.load(f)
    assert set(data["scenarios"]) == {"perf_halving", "kill"}
    for sc in data["scenarios"].values():
        assert sc["fault_step_speedup"] > 1.0
    halving = data["scenarios"]["perf_halving"]
    assert halving["adaptive"]["fault_step_quality"] <= 1.2
    assert halving["static"]["fault_step_quality"] >= 1.6


def test_serve_cli():
    out = _run(["repro.launch.serve", "--arch", "qwen2-1.5b", "--requests", "4",
                "--max-new", "3", "--max-seq", "32", "--replicas", "4x2:2x1",
                "--queue-depth", "4", "--scenario", "halving"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "served 4 requests" in out.stdout
    assert "tok/s" in out.stdout


def test_bench_serve_cli(tmp_path):
    """Toy-scale smoke of the serving benchmark: JSON emitted with the
    batched throughput and the fault-scenario quality."""
    import json

    out_path = str(tmp_path / "BENCH_serve.json")
    out = _run(["benchmarks.bench_serve", "--requests", "12", "--max-new", "4",
                "--out", out_path], timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    with open(out_path) as f:
        data = json.load(f)
    assert data["batched"]["tokens_out"] == 12 * 4
    assert data["batched"]["tokens_per_s"] > 0
    assert data["fault"]["worst_quality"] <= 1.3


@pytest.mark.parametrize("arch,shape", [("qwen2-1.5b", "decode_32k")])
def test_dryrun_cli_cell(arch, shape, tmp_path):
    out = _run(["repro.launch.dryrun", "--arch", arch, "--shape", shape,
                "--mesh", "single", "--out", str(tmp_path), "--no-extrapolate"],
               timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "all cells green" in out.stdout
    import json, glob

    files = glob.glob(str(tmp_path / "*.json"))
    assert len(files) == 1
    cell = json.load(open(files[0]))
    assert cell["status"] == "run" and cell["n_devices"] == 256
