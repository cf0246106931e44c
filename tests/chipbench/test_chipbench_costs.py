"""Operations and bytes from shapes, against hand arithmetic at the widths
of Qwen2-1.5B (d 1536, 12/2 heads of 128, d_ff 8960, vocab 151936, 28
layers, bf16)."""

import json
import os

import pytest

from chipbench import costs
from chipbench.bench import HERE
from chipbench.model import dims

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def d():
    with open(os.path.join(HERE, "configs", "qwen2-1.5b.json")) as f:
        return dims(json.load(f))


def test_layer_params(d):
    # q and o: 1536 x 12 x 128 each; k and v: 1536 x 2 x 128 each; MLP 3 x 1536 x 8960.
    assert d.layer_params == 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960 == 46_792_704


def test_decode_step(d):
    flops, nbytes = costs.decode_step(d, [0, 9])
    weights = 28 * 46_792_704 + 1536 * 151936            # layers + tied head
    ctx = 1 + 10                                          # positions attended
    assert flops == 2 * 2 * weights + 4 * 28 * 12 * 128 * ctx
    kv = 2 * 2 * 128 * 28                                 # K and V per position, all layers
    assert nbytes == 2 * (weights + kv * (ctx - 2) + kv * 2)
    t, bound = costs.least_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_prefill(d):
    assert costs.prefill(d, 100) == (2 * 100 * 28 * 46_792_704
                                     + 4 * 28 * 12 * 128 * 100 * 101 // 2
                                     + 2 * 1536 * 151936)


def test_prefill_attention_kernel():
    flops, nbytes = costs.prefill_attention(1500, 12, 2, 128)
    assert flops == 4 * 12 * 128 * 1500 * 1501 // 2
    assert nbytes == 2 * 1500 * 128 * (2 * 12 + 2 * 2)
    t, bound = costs.least_seconds(flops, nbytes, PEAK)
    assert bound == "compute" and t == pytest.approx(flops / 197e12)
