"""Tuned-substrate launcher profile: the env recipe as checked-in code.

The TPU-pod training repos this project cribs from (olmax, HomebrewNLP-Jax)
all carry the same shell preamble: tcmalloc preloaded ahead of glibc malloc,
its large-alloc warning threshold pushed out of numpy's way, TF's C++ logging
silenced, and ``--xla_force_host_platform_device_count`` pinned so the host
platform exposes a deterministic device count.  Copying that preamble between
run scripts is how it rots — so it lives here once, with two consumers:

  - ``scripts/tuned_run.sh`` (the shell wrapper): evals ``python -m
    repro.launch.env --export`` and execs the real command under the full
    profile — the only way ``LD_PRELOAD`` can take effect, since the dynamic
    linker reads it before Python starts.
  - ``apply()`` (in-process opt-in for ``benchmarks/run.py`` and the
    train/serve CLIs via ``--tuned`` / ``REPRO_TUNED=1``): sets everything
    that still works after the process is up — env defaults for libraries
    not yet loaded, plus the persistent JAX compilation cache.  Existing
    environment values always win, so the wrapper and ``apply()`` compose.
"""

from __future__ import annotations

import argparse
import os
import shlex

from ..kernels.autotune import enable_compilation_cache

__all__ = ["TUNED_ENV", "tcmalloc_path", "tuned_env", "apply", "main"]

#: The static half of the recipe (values are strings: this is environ).
TUNED_ENV = {
    # tcmalloc reports every allocation past this as a potential leak;
    # numpy's buffer pools trip it constantly. 60 GB ~= never.
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
    # Silence TF's C++ dataset/stream_executor chatter.
    "TF_CPP_MIN_LOG_LEVEL": "4",
}

#: Where distros put tcmalloc (first hit wins; absent -> no preload).
_TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def tcmalloc_path() -> str | None:
    for p in _TCMALLOC_CANDIDATES:
        if os.path.exists(p):
            return p
    return None


def tuned_env(n_host_devices: int | None = None,
              base: dict | None = None) -> dict[str, str]:
    """The full profile as a dict of env additions.  Values already present
    in ``base`` (default: the current environment) are left alone."""
    if base is None:
        base = os.environ
    out: dict[str, str] = {}
    for k, v in TUNED_ENV.items():
        if k not in base:
            out[k] = v
    tc = tcmalloc_path()
    if tc is not None and "LD_PRELOAD" not in base:
        out["LD_PRELOAD"] = tc
    if n_host_devices is not None:
        flag = f"--xla_force_host_platform_device_count={n_host_devices}"
        existing = base.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in existing:
            out["XLA_FLAGS"] = f"{existing} {flag}".strip()
    return out


def apply(n_host_devices: int | None = None) -> dict[str, str]:
    """In-process opt-in: merge the profile into ``os.environ`` (existing
    values win) and switch on the persistent JAX compilation cache.  Returns
    what was applied.  ``LD_PRELOAD`` is skipped here — the dynamic linker
    already ran; use ``scripts/tuned_run.sh`` for the malloc half."""
    applied = tuned_env(n_host_devices)
    applied.pop("LD_PRELOAD", None)
    os.environ.update(applied)
    enable_compilation_cache()
    return applied


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        description="print the tuned-substrate env profile")
    ap.add_argument("--devices", type=int, default=None,
                    help="host-platform device count to force via XLA_FLAGS")
    ap.add_argument("--export", action="store_true",
                    help="emit eval-able 'export K=V' lines (shell wrapper)")
    args = ap.parse_args(argv)
    for k, v in sorted(tuned_env(args.devices).items()):
        if args.export:
            print(f"export {k}={shlex.quote(v)}")
        else:
            print(f"{k}={v}")


if __name__ == "__main__":
    main()
