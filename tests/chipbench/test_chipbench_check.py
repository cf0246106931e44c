"""The check that decides ``correct``, driven through whole runs of tiny
cells on the CPU: the program passes, the float8 control does not, and each
fault a serving cell can have, planted in the program, turns ``correct``
false."""

import pytest

import tinybench
from chipbench import control, harness
from chipbench.bench import Bench
from repro.models.model import Model
from repro.serve.engine import DecodeEngine

PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tinybench.write(str(tmp_path_factory.mktemp("tiny")))
    return Bench(root, f"{root}/chipbench")


def run(bench, cell, seed=5):
    return harness.run_cell(bench, cell, seed, 0.3, False, peak=PEAK, t_start=0.0,
                            device={"platform": "cpu"})


@pytest.mark.parametrize("seed", [1, 99, 2**31 + 5])
def test_control_fails_the_limit_the_program_meets(bench, seed):
    r = control.readings(bench, "tiny-qwen3.disagg", seed, 0)
    assert r["undelivered"] == 0
    assert r["program"] <= tinybench.LIMIT < r["control"]


@pytest.mark.parametrize("cell", ["tiny-qwen2.mixed", "tiny-qwen3.disagg"])
def test_sound_run_is_correct(bench, cell):
    r = run(bench, cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert list(r["checks"]) == ["max_logit_gap", "undelivered"]
    assert list(r)[-1] == "checks"


def _alter_tokens(monkeypatch):
    orig = DecodeEngine.step

    def step(self):
        live = [(s.req, len(s.req.out_tokens)) for s in self.slots if s.req is not None]
        done = orig(self)
        for req, n in live:
            for i in range(n, len(req.out_tokens)):
                req.out_tokens[i] = (req.out_tokens[i] + 1) % self.model.cfg.vocab_size
        return done

    monkeypatch.setattr(DecodeEngine, "step", step)


def _state_unchanged(monkeypatch):
    orig = Model.decode_step

    def decode_step(self, params, caches, inputs, pos, capacities=None):
        return orig(self, params, caches, inputs, pos)[0], caches

    monkeypatch.setattr(Model, "decode_step", decode_step)


def _answer_short(monkeypatch):
    orig = DecodeEngine.step

    def step(self):
        done = orig(self)
        for r in done:
            r.out_tokens.pop()
        return done

    monkeypatch.setattr(DecodeEngine, "step", step)


@pytest.mark.parametrize("cell", ["tiny-qwen2.mixed", "tiny-qwen3.disagg"])
@pytest.mark.parametrize("fault,check", [
    (_alter_tokens, "max_logit_gap"),
    (_state_unchanged, "max_logit_gap"),
    (_answer_short, "undelivered"),
])
def test_fault_makes_the_run_incorrect(bench, monkeypatch, cell, fault, check):
    fault(monkeypatch)
    r = run(bench, cell)
    assert not r["correct"]
    c = r["checks"][check]
    assert c["value"] > c["limit"]
