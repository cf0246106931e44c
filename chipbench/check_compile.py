"""Compile a configuration's serving programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python chipbench/check_compile.py qwen2-1.5b 32x2048 16x2048 16x4096 --prefill 4096

compiles, without a chip, the program's decode step at each ``slots x
max_seq`` and its bucketed prefill at each ``--prefill`` length, for one chip
of a described ``v5e:2x2``, and prints each program's memory analysis beside
the weights' and the cache's bytes.  What the chip's compiler refuses, or a
program that does not fit, shows here at no chip time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("decode", nargs="*", help="slots x max_seq, e.g. 16x1024")
    ap.add_argument("--prefill", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.model import program_config
    from repro.models.model import Model

    import repro.models.attention as attention

    class _AsOnTPU:
        """The attention module's view of JAX: the backend is a TPU, so the
        program takes its TPU path (the compiled Pallas prefill)."""

        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def default_backend():
            return "tpu"

    attention.jax = _AsOnTPU()
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        cfg = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    model = Model(program_config(cfg))

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
                            tree)

    params = on_chip(model.abstract_params())
    gb = 1e-9
    print(f"{args.config}: weights {sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)) * gb:.3f} GB")
    for spec in args.decode:
        slots, seq = map(int, spec.split("x"))
        caches = on_chip(jax.eval_shape(lambda: model.init_cache(slots, seq)))
        toks = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=chip)
        pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
        c = jax.jit(model.decode_step, donate_argnums=1).lower(params, caches, toks, pos).compile()
        cache_b = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
        print(f"decode {slots}x{seq}: cache {cache_b * gb:.3f} GB; {c.memory_analysis()}")
    for bucket in args.prefill:
        toks = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip)
        last = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

        def run(params, toks, last_pos):
            return model.prefill(params, {"tokens": toks}, last_pos=last_pos)

        c = jax.jit(run).lower(params, toks, last).compile()
        print(f"prefill {bucket}: tpu_custom_call={'tpu_custom_call' in c.as_text()}; "
              f"{c.memory_analysis()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
