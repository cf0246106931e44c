"""BENCHMARK.json and the files it names: every entry resolves, the file
keeps to the benchmark's contract, and a configuration, a traffic mix, a
cell and a per-layer metric are added by adding files and entries alone."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import tinybench
from chipbench import harness
from chipbench.bench import HERE, Bench

ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_contract_keys_and_names(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in spec[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m.get("workloads", cells)) <= cells
    for w in spec["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200


def test_every_cell_and_reader_resolves(spec):
    bench = Bench(ROOT)
    for w in spec["workloads"]:
        cell = bench.cell(w["name"])
        assert cell.config["reduced"] == next(
            c["reduced"] for c in spec["configs"] if c["name"] == w["config"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(bench.reader(m["name"]))


METRIC = '''"""Decode steps per second of the traced stretch."""


def read(ctx):
    steps = [c for c in ctx.calls if c.kind == "step"]
    return len(steps) / ctx.window_s if steps else None
'''


def test_a_cell_mix_config_and_metric_added_as_files(tmp_path):
    root = tinybench.write(str(tmp_path))
    data = os.path.join(root, "chipbench")
    with open(os.path.join(data, "metrics", "steps_per_s.py"), "w") as f:
        f.write(METRIC)
    with open(os.path.join(data, "traffic", "tiny-long.json"), "w") as f:
        json.dump({**tinybench.MIX, "prompt": {"median": 20, "sigma": 0.3, "min": 16,
                                                "max": 40}}, f)
    with open(os.path.join(data, "cells", "tiny-qwen3.mixed.long.json"), "w") as f:
        json.dump({"fleet": "a=1x3",
                   "max_seq": 64, "max_queue_depth": 3,
                   "check": {"sample_tokens": 16, "max_logit_gap": tinybench.LIMIT}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny-qwen3.mixed.long", "config": "tiny-qwen3",
                              "traffic": "tiny-long", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "steps_per_s", "unit": "1/s", "better": "higher",
                              "source": "host_clock", "layer": "engine",
                              "moves": "output_tokens_per_s",
                              "workloads": ["tiny-qwen3.mixed.long"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    bench = Bench(root, data)
    assert [m["name"] for m in bench.cell("tiny-qwen3.mixed.long").per_layer][-1] == "steps_per_s"
    assert "steps_per_s" not in [m["name"] for m in bench.cell("tiny-qwen3.mixed").per_layer]
    r = harness.run_cell(bench, "tiny-qwen3.mixed.long", 3, 0.2, True,
                         peak={"flops_per_s": 1e12, "bytes_per_s": 1e11}, t_start=0.0,
                         device={"platform": "cpu"})
    assert r["correct"]
    assert r["metrics"]["steps_per_s"]["value"] > 0
    assert r["metrics"]["decode_call_ms"]["value"] > 0


def _run(cwd, env_extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen2-1.5b.mixed.chat",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_with_no_result():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_fail_with_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench")
    p = _run(tmp_path, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
