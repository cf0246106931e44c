"""Benchmark runner: one section per paper figure + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV (values that aren't times keep the
value column; the derived column says what they are).

Also home of the shared ``BENCH_*.json`` writer: every bench artifact goes
through :func:`write_bench_json`, which stamps a ``provenance`` block
(git sha, UTC date, tier-1 test count) so the bench trajectory is comparable
across PRs.
"""

from __future__ import annotations

import datetime
import functools
import json
import os
import re
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=1)
def provenance() -> dict:
    """Git sha + UTC date + tier-1 test count, best-effort (None on failure).
    Cached so a multi-bench run pays the collection cost once."""
    sha = None
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=30,
        ).stdout.strip() or None
    except Exception:
        pass
    tier1 = None
    try:
        # The collecting child must never ask for an accelerator: this
        # process may already hold it.
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (os.path.join(_REPO_ROOT, "src"),
                        env.get("PYTHONPATH")) if p
        )
        cp = subprocess.run(
            [sys.executable, "-m", "pytest", "--collect-only", "-q", "tests"],
            cwd=_REPO_ROOT, capture_output=True, text=True, timeout=300,
            env=env,
        )
        m = re.search(r"(\d+) tests collected", cp.stdout)
        if m:
            tier1 = int(m.group(1))
    except Exception:
        pass
    return {
        "git_sha": sha,
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "tier1_tests": tier1,
    }


def write_bench_json(path: str, payload: dict, *,
                     backend: str = "sim") -> dict:
    """Write a ``BENCH_*.json`` artifact with the provenance block attached.
    ``backend`` records which execution backend produced the numbers (the
    ``RunReport.backend`` label: ``"sim"``, ``"wallclock[4d]"``, ...), so a
    measured artifact is never mistaken for a modeled one.  Returns the
    stamped payload."""
    stamped = dict(payload)
    stamped["provenance"] = dict(provenance(), backend=backend)
    with open(path, "w") as f:
        json.dump(stamped, f, indent=2)
        f.write("\n")
    return stamped


def main() -> None:
    # Tuned-substrate opt-in (launch/env.py): --tuned or REPRO_TUNED=1.
    # LD_PRELOAD needs scripts/tuned_run.sh; everything else applies here.
    if "--tuned" in sys.argv[1:] or os.environ.get("REPRO_TUNED") == "1":
        from repro.launch.env import apply as _apply_tuned
        _apply_tuned()

    from . import kernel_bench, paper_figs, roofline

    rows: list[tuple] = []
    for name, fn in paper_figs.ALL.items():
        try:
            rows.extend(fn())
        except Exception as e:  # keep the harness running; report the failure
            rows.append((f"{name}/ERROR", 0.0, repr(e)))
    try:
        rows.extend(kernel_bench.bench())
    except Exception as e:
        rows.append(("kernel/ERROR", 0.0, repr(e)))
    try:
        rows.extend(roofline.rows())
    except Exception as e:
        rows.append(("roofline/ERROR", 0.0, repr(e)))

    print("name,us_per_call,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.6g},{derived}")

    bad = [r for r in rows if "ERROR" in r[0]]
    if bad:
        sys.exit(1)


if __name__ == "__main__":
    main()
