"""Compiles for a described TPU v5e chip, at real widths, with none attached.

The TPU compiler is installed beside the CPU backend, so the kernels of the
main path, the decode step of each chip cell's model and the Qwen3-8B prefill
are compiled here for one chip of a described ``v5e:2x2`` topology.  This
catches what interpret mode cannot: block shapes off the (8, 128) tiling,
primitives Mosaic cannot lower, and programs that do not fit the chip.  Nothing runs, so nothing here is a
time or a result.

The topology is described inside a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.flash_attention.ops import mha
from repro.kernels.mamba_scan.ops import ssd
from repro.kernels.matmul.ops import matmul
from repro.kernels.prefill.ops import prefill_attention
from repro.models.model import Model
from repro.serve.engine import decode_step

import repro.models.attention as attention

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # Else the TPU library writes its logs under the temporary directory.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_MIN_LOG_LEVEL", "3")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_cases():
    # Block sizes are the ops' TPU defaults: on the CPU the autotune registry
    # would otherwise hand them its CPU entries.
    def prefill(q, k, v):
        return prefill_attention(q, k, v, cache_dtype=BF16, block_q=256,
                                 block_k=256, use_pallas=True, interpret=False)

    def flash(q, k, v):
        return mha(q, k, v, causal=True, block_q=512, block_k=512,
                   use_pallas=True, interpret=False)

    def mm(x, y):
        return matmul(x, y, block_m=256, block_n=256, block_k=512,
                      use_pallas=True, interpret=False)

    def ssd_scan(x, dt, a, b, c):
        return ssd(x, dt, a, b, c, None, chunk=128, use_pallas=True,
                   interpret=False)

    # Qwen2-1.5B attention: 16 padded q-heads, 2 KV heads, head_dim 128;
    # mamba2-2.7b SSD: 80 heads of 64, state 128, one group.
    return {
        "prefill_flash": (prefill, [((1, 512, 16, 128), BF16),
                                    ((1, 512, 2, 128), BF16),
                                    ((1, 512, 2, 128), BF16)]),
        "flash_attention": (flash, [((1, 4096, 16, 128), BF16),
                                    ((1, 4096, 2, 128), BF16),
                                    ((1, 4096, 2, 128), BF16)]),
        "matmul": (mm, [((4096, 4096), BF16), ((4096, 4096), BF16)]),
        "ssd_scan": (ssd_scan, [((1, 1024, 80, 64), BF16),
                                ((1, 1024, 80), BF16),
                                ((80,), jnp.float32),
                                ((1, 1024, 1, 128), BF16),
                                ((1, 1024, 1, 128), BF16)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The engine's decode step (the model's, and each slot's greedy token) at
# chat engine ``a``'s shape in each chip cell: Qwen2-1.5B whole at 32 slots,
# and Qwen3-8B (32/8 heads of 128, d 4096, QK-norm) cut to an 18-layer stage
# at 16 slots; each row is (config, slots, max_seq).
DECODE = {
    "qwen2-1.5b": (lambda: get_config("qwen2-1.5b"), 32, 2048),
    "qwen3-8b-18l": (lambda: get_config("qwen3-8b", n_layers=18), 16, 2048),
}


def _on_chip(tree, one_chip):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)


def _decode_step(one_chip, name):
    config, slots, seq = DECODE[name]
    model = Model(config())
    params = _on_chip(model.abstract_params(), one_chip)
    caches = _on_chip(jax.eval_shape(lambda: model.init_cache(slots, seq)),
                      one_chip)
    toks = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(decode_step, static_argnums=0, donate_argnums=2).lower(
        model, params, caches, toks, pos).compile()
    return compiled, caches


@pytest.mark.parametrize("name", DECODE)
def test_decode_step_compiles_for_v5e(one_chip, name):
    compiled, _ = _decode_step(one_chip, name)
    tokens, _ = compiled.out_info
    # Only the greedy token of each slot leaves the program besides the cache.
    assert (tokens.shape, tokens.dtype) == ((DECODE[name][1],), jnp.int32)
    mem = compiled.memory_analysis()
    # Weights and cache stay within one chip's 16 GB.
    assert mem.argument_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("name", DECODE)
def test_decode_step_writes_cache_in_place(one_chip, name):
    """The step writes its row per slot and layer into the donated cache: no
    second cache, the output aliases the input, and no copy of the stacked
    cache.  Before the rows were scattered into a carried stack, the scan
    re-wrote each layer's whole cache into a new stack and copied that into
    the output: for Qwen2-1.5B at 32 x 2048, 1 946 479 616 bytes of
    temporaries for a 1 879 048 192-byte cache."""
    compiled, caches = _decode_step(one_chip, name)
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(caches))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.05 * cache_bytes
    assert mem.alias_size_in_bytes == cache_bytes
    stacked = "bf16[" + ",".join(
        map(str, jax.tree.leaves(caches)[0].shape)) + "]"
    copies = [line for line in compiled.as_text().splitlines()
              if f"= {stacked}" in line and " copy(" in line]
    assert not copies, copies


def test_qwen3_8b_prefill_runs_the_pallas_kernel(one_chip, monkeypatch):
    """Qwen3-8B's prefill at the 4096 bucket (one 18-layer stage, GQA-4:
    32 query heads over 8 KV heads, unpadded) compiles for the chip with the
    Pallas prefill kernel in it."""

    class AsOnTPU:
        """The attention module's view of JAX: the backend is a TPU, so it
        takes its TPU path (the compiled Pallas prefill)."""

        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def default_backend():
            return "tpu"

    monkeypatch.setattr(attention, "jax", AsOnTPU())
    model = Model(get_config("qwen3-8b", n_layers=18))
    params = _on_chip(model.abstract_params(), one_chip)
    toks = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    last = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def run(params, toks, last_pos):
        return model.prefill(params, {"tokens": toks}, last_pos=last_pos)

    compiled = jax.jit(run).lower(params, toks, last).compile()
    assert "tpu_custom_call" in compiled.as_text()
