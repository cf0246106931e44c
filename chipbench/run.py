"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` a profiler trace of the window
gives its per-layer metrics.  The last line of standard output is the result
as one JSON object; the numbers the check compared, each beside its limit,
are the last lines of standard error and the result's last key.

The run fails, printing no result, when JAX finds no TPU, fewer chips than
the cell asks for, or a device missing from ``peaks.json``.  JAX's
persistent compilation cache is ``<checkout>/.jax_cache``, so only the
first run of a cell in a checkout compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from chipbench.bench import Bench
    from chipbench.harness import run_cell
    from repro.kernels.autotune import enable_compilation_cache

    bench = Bench(ROOT)
    chips = {w["name"]: w["chips"] for w in bench.spec["workloads"]}[args.workload]
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench: no TPU: JAX reports platform {devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"chipbench: {args.workload} needs {chips} chips, JAX reports {len(devices)}")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        sys.exit(f"chipbench: no peaks for device kind {kind!r} in peaks.json")
    enable_compilation_cache()

    result = run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        peak=peaks[kind], t_start=T_START,
        device={"platform": devices[0].platform, "kind": kind, "count": len(devices)})
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
