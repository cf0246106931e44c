"""A benchmark of tiny cells, written as files into a directory, for tests
that drive the harness on the CPU.  It is built the way a later change adds
cells: files and ``BENCHMARK.json`` entries, no code."""

from __future__ import annotations

import json
import os
import shutil

from chipbench.bench import HERE

TINY = {
    "qwen2": {"architecture": "qwen2", "program_config": "qwen2-1.5b",
              "tie_word_embeddings": True},
    "qwen3": {"architecture": "qwen3", "program_config": "qwen3-8b", "head_dim": 16,
              "attention_bias": False, "tie_word_embeddings": False},
}
MIX = {"prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 24},
       "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
       "pool_per_slot": 2, "pairing_seed": 1}
FLEETS = {"mixed": "a=1x4,b=1x2", "disagg": "p=1^prefill,d=1x4^decode"}
# The tiny cells' max_logit_gap limit, set as the chip cells' are: above the
# program's readings on the CPU (at most 0.031 on seeds 1, 99 and 2**31 + 5
# of tiny-qwen3) and below the float8 control's (at least 0.113 there).
LIMIT = 0.08


def write(root: str, limit: float = LIMIT) -> str:
    """Tiny cells ``tiny-<arch>.<fleet>`` under ``root``; returns ``root``."""
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(root, "chipbench", sub), exist_ok=True)
    shutil.copytree(os.path.join(HERE, "metrics"), os.path.join(root, "chipbench", "metrics"),
                    dirs_exist_ok=True)
    spec = json.load(open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")))
    spec["configs"], spec["workloads"] = [], []
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)

    def dump(rel, obj):
        with open(os.path.join(root, "chipbench", rel), "w") as f:
            json.dump(obj, f)

    dump("traffic/tiny.json", MIX)
    for arch, extra in TINY.items():
        name = f"tiny-{arch}"
        dump(f"configs/{name}.json", {
            "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16",
            "reduced": [], **extra})
        spec["configs"].append({"name": name, "source": "tiny", "reduced": [], "why": "test",
                                "file": f"chipbench/configs/{name}.json"})
        for fleet, fs in FLEETS.items():
            cell = f"{name}.{fleet}"
            dump(f"cells/{cell}.json", {
                "fleet": fs, "max_seq": 64,
                "max_queue_depth": 4,
                "check": {"sample_tokens": 24, "max_logit_gap": limit}})
            spec["workloads"].append({"name": cell, "config": name, "traffic": "tiny",
                                      "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
