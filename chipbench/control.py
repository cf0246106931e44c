"""The readings a cell's ``max_logit_gap`` limit is set from.

    python chipbench/control.py --workload <cell> --seeds 11,12,13 [--seconds 0]

For each seed, in one process: set the cell up, serve its window (with
``--seconds 0``, one pool), then read over the same sample of served
requests the program's widest gap (the lower reading) and the control's:
the reference computed with every matmul operand in float8, the precision
below the configuration's bf16 (the upper reading).  Prints one JSON line
per seed.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(bench, name: str, seed: int, seconds: float) -> dict:
    from chipbench import harness, reference, stats

    s = harness.setup(bench, name, seed)
    pools, t_open, t_close = harness.window(s, seed, seconds)
    failed = harness.delivery_failures(pools, s.log)
    metrics = stats.window_metrics(pools, s.log, t_open, t_close)
    harness.release(s)
    sample = harness.check_sample(pools, seed, s.cell.check["sample_tokens"])
    t = time.perf_counter()
    program = harness.max_gap(s, sample)
    control = harness.max_gap(s, sample, reference.control_gaps)
    return {"workload": name, "seed": seed, "program": program, "control": control,
            "undelivered": len(failed), "sample_requests": len(sample),
            "sample_tokens": sum(r.max_new_tokens for r in sample),
            "window_s": t_close - t_open, "check_s": time.perf_counter() - t, **metrics}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench.bench import Bench
    from repro.kernels.autotune import enable_compilation_cache

    enable_compilation_cache()
    bench = Bench(ROOT)
    for seed in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(readings(bench, args.workload, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
