"""chip_smoke.py on the CPU: its phases at a tiny size, and its refusal to run
without a TPU (it has no CPU fallback and no tiny-size option)."""

import importlib.util
import os
import subprocess
import sys

import numpy as np

from repro.configs.registry import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


def test_phases_at_tiny_size():
    # Pallas on: on the CPU the prefill kernel runs in interpret mode, so the
    # logits check compares kernel and jnp path as it does on the chip.
    cfg = get_config("qwen2-1.5b", reduced=True, use_pallas=True)
    model, params = smoke.build_model(cfg, seed=0)

    def requests():
        return smoke.make_requests(0, 6, (4, 20), 4, cfg.vocab_size)

    for fleet in (smoke.MIXED_FLEET, smoke.DISAGG_FLEET):
        reqs = requests()
        rep, engines, tracer = smoke.serve(fleet, model, params, reqs,
                                           max_seq=32)
        assert rep.work_done == sum(r.max_new_tokens for r in reqs)
        assert sorted(e.data["rid"] for e in tracer.events
                      if e.kind == "request_done") == [r.rid for r in reqs]
        for name, e in engines.items():
            steps = [s for s in tracer.spans if s.name == "engine.step"
                     and s.worker == name and s.attrs["active"]]
            assert len(steps) == e.steps
            assert all(s.seconds > 0 for s in steps)
    # The disaggregated fleet prefilled on the prefill replica only.
    prefills = {s.worker for s in tracer.spans if s.name == "engine.prefill"}
    assert prefills == {"p"}
    prompt = max((r.prompt for r in reqs), key=len)
    diff = smoke.check_prefill_logits(model, params, prompt, max_seq=32)
    assert np.isfinite(diff) and diff < 1e-4    # float32 config


def test_make_requests_seeded():
    a = smoke.make_requests(3, 16, (32, 256), 32, 151936)
    b = smoke.make_requests(3, 16, (32, 256), 32, 151936)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert all(32 <= len(r.prompt) <= 256 for r in a)
    assert all(r.max_new_tokens == 32 for r in a)


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cp = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                        cwd=ROOT, env=env, capture_output=True, text=True,
                        timeout=120)
    assert cp.returncode != 0
    assert "no TPU" in cp.stderr
    assert '"ok"' not in cp.stdout
