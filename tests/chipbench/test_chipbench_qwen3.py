"""The ``qwen3-8b-18l`` configuration: Qwen3-8B at its published widths, cut
to an 18-layer stage.  The file resolves to the program's registry entry
with every width equal and only the depth cut, states the cut, and the
decode step's bytes at these widths are the layers' and the head's."""

import dataclasses
import json
import os

import pytest

from chipbench import costs
from chipbench.bench import HERE, Bench
from chipbench.model import dims, program_config
from repro.configs.registry import get_config

ROOT = os.path.dirname(HERE)
NAME = "qwen3-8b-18l"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", f"{NAME}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", ["qwen3-8b-18l.mixed.chat",
                                  "qwen3-8b-18l.disagg.longprompt"])
def test_resolves_to_the_registry_entry_but_for_depth(cell):
    got = program_config(Bench(ROOT).cell(cell).config)
    published = get_config("qwen3-8b")
    assert published.n_layers == 36 and got.n_layers == 18
    want = dataclasses.asdict(published)
    diff = {k for k, v in dataclasses.asdict(got).items() if v != want[k]}
    assert diff == {"n_layers"}


def test_file_states_the_cut(cfg):
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in spec["configs"] if c["name"] == NAME]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 36}
    assert cfg["num_hidden_layers"] == 18
    widths = {"hidden_size": 4096, "intermediate_size": 12288,
              "num_attention_heads": 32, "num_key_value_heads": 8,
              "head_dim": 128, "vocab_size": 151936}
    assert {k: cfg[k] for k in widths} == widths


def test_decode_step_reads_the_layers_and_the_head(cfg):
    """Per layer 4096 x 128 x (32 + 32 + 8 + 8) attention and 3 x 4096 x
    12288 MLP weights; the untied head 4096 x 151936.  With the 622 M-row
    embedding, which a decode step reads one row of per token and the costs
    leave out, the stage holds 4.718 B parameters (9.435 GB in bf16)."""
    d = dims(cfg)
    layer = 4096 * 128 * 80 + 3 * 4096 * 12288
    assert d.layer_params == layer == 192_937_984
    head = 4096 * 151936
    assert 18 * layer + 2 * head == 4_717_543_424
    flops, nbytes = costs.decode_step(d, [0])
    kv = 2 * 8 * 128 * 18                                 # K and V, one position
    assert nbytes == 2 * (18 * layer + head + kv)
    assert 2 * (18 * layer + head) == 8_190_427_136       # 8.19 GB a step
    assert flops == 2 * (18 * layer + head) + 4 * 18 * 32 * 128
